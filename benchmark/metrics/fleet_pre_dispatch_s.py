"""Fleet table, before the device starts: per wave, the ``kernel.host``
stretches with ``phase`` upsert / sync / prep plus ``kernel.dispatch``. None
where no ``kernel.host`` carries a ``phase`` (a program that still lumps its
host stretches into one span)."""

from ._per_wave import host_phases_named, median_of_sums

BEFORE = ("upsert", "sync", "prep")


def read(ctx):
    def value(s):
        if s["name"] == "kernel.dispatch":
            return s["duration_s"]
        if s["name"] == "kernel.host" and s["attrs"].get("phase") in BEFORE:
            return s["duration_s"]
        return None

    if not host_phases_named(ctx["spans"]):
        return None
    return median_of_sums(ctx["spans"], ctx["waves"], value)
