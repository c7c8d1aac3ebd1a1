"""Fleet table, after the device is done: per wave, ``kernel.fetch`` plus the
``kernel.host`` stretch with ``phase`` post (result decode). None where no
``kernel.host`` carries a ``phase``."""

from ._per_wave import host_phases_named, median_of_sums


def read(ctx):
    def value(s):
        if s["name"] == "kernel.fetch":
            return s["duration_s"]
        if s["name"] == "kernel.host" and s["attrs"].get("phase") == "post":
            return s["duration_s"]
        return None

    if not host_phases_named(ctx["spans"]):
        return None
    return median_of_sums(ctx["spans"], ctx["waves"], value)
