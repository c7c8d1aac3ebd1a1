"""Estimator refresh, host side: self time of the ``estimator.*`` spans per
wave (refresh: generation confirms; sync: moved members stacked and
uploaded; dispatch; fold). None where the program records no such span."""

from ..spans import per_wave_median


def read(ctx):
    return per_wave_median(ctx["spans"], ctx["waves"],
                           lambda n: n.startswith("estimator."))
