"""Plane controllers: self time of the ``controller.*`` spans per wave."""

from ..spans import per_wave_median


def read(ctx):
    return per_wave_median(ctx["spans"], ctx["waves"],
                           lambda n: n.startswith("controller."))
