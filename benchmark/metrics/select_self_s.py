"""Engine host prologue, the Select stage alone: self time of the
``scheduler.select`` spans per wave (SelectClusters on the host for the
batch's spread-constrained rows; a child of ``scheduler.pack``)."""

from ..spans import per_wave_median


def read(ctx):
    return per_wave_median(ctx["spans"], ctx["waves"],
                           lambda n: n == "scheduler.select")
