"""Rows a wave sent off the fleet table to the host path: per wave, the
``host_rows`` the ``scheduler.solve`` spans carry (the program stamps 0 on
its fast paths). Has to read 0 here: no placement has more affinity groups,
and no binding more eviction tasks, than the fleet table's row state holds.
Nothing where the program stamps no such attribute."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["attrs"].get("host_rows")
        if s["name"] == "scheduler.solve" else None)
