"""Engine host prologue, quota admission alone: self time of the
``scheduler.quota`` spans per wave (the host's share of a wave's admission:
on the fleet table the upload of ``remaining`` and the two dispatches, a
child of ``scheduler.solve``; where the engine partitions the batch, the
whole per-row walk, a child of ``scheduler.schedule``). Nothing where the
program records no such span."""

from ..spans import per_wave_median


def read(ctx):
    return per_wave_median(ctx["spans"], ctx["waves"],
                           lambda n: n == "scheduler.quota")
