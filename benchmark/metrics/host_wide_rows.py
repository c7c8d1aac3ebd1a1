"""Rows a wave sent to the general host path for the replica or
previous-site bound: per wave, the ``wide_rows`` the ``scheduler.eligible``
spans carry (stamped only where rows leave, each row counted once). It
reads the generator's own count of the rows past the configuration's row
bounds. Nothing where the program stamps no such attribute."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("wide_rows")
        if s["name"] == "scheduler.eligible" else None)
