"""Rows whose namespace, demand or partition the HOST derived in a wave: per
wave, the ``host_rows`` the ``scheduler.quota`` spans carry (0 where the
fleet table admits the batch from its row state; the batch where the
engine partitions it). Has to read 0 here. Nothing where the program
stamps no such attribute."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("host_rows")
        if s["name"] == "scheduler.quota" else None)
