"""Megabytes a wave fetches from the device: per wave, the ``fetch_mb`` the
``kernel.fetch`` spans carry."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("fetch_mb")
        if s["name"] == "kernel.fetch" else None)
