"""Engine host prologue, the ordered affinity groups alone: self time of the
``scheduler.terms`` spans per wave (the host's share of the fleet table's
term kernel: the multi-term rows' vector and the dispatch; a child of
``scheduler.solve``). Nothing where the program records no such span."""

from ..spans import per_wave_median


def read(ctx):
    return per_wave_median(ctx["spans"], ctx["waves"],
                           lambda n: n == "scheduler.terms")
