"""Seconds a wave spent on phase B, the entry wire: per wave, the summed
durations of the ``kernel.entries`` spans (an exact dispatch, fetch and fold,
a speculative fetch and fold, or a speculation's drain), the median. A wave
whose ``kernel.fetch`` carries ``entry_rows`` and holds no ``kernel.entries``
reads 0; None where no ``kernel.fetch`` carries ``entry_rows``."""

from ._per_wave import median_of_sums


def _seconds(s):
    if s["name"] == "kernel.entries":
        return s["duration_s"]
    if s["name"] == "kernel.fetch" and "entry_rows" in s["attrs"]:
        return 0.0
    return None


def read(ctx):
    return median_of_sums(ctx["spans"], ctx["waves"], _seconds)
