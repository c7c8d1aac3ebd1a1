"""Engine host prologue: self time of the ``scheduler.*`` spans per wave
(pack, solve, host, pass: what core.py does outside the fleet table)."""

from ..spans import per_wave_median


def read(ctx):
    return per_wave_median(ctx["spans"], ctx["waves"],
                           lambda n: n.startswith("scheduler."))
