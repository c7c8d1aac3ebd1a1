"""Spread-constrained rows a wave re-selects on the host: per wave, the
``computed`` the ``scheduler.select`` spans carry (rows answered from the
row cache are its ``hits``)."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("computed")
        if s["name"] == "scheduler.select" else None)
