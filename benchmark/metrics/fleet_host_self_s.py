"""Fleet table host work: kernel.host + kernel.dispatch + kernel.fetch self
time per wave (kernel.device, the fence, is the device's and not counted)."""

from ..spans import per_wave_median

NAMES = ("kernel.host", "kernel.dispatch", "kernel.fetch")


def read(ctx):
    return per_wave_median(ctx["spans"], ctx["waves"], lambda n: n in NAMES)
