"""Engine host prologue, the batch-identity check alone: per wave, the summed
duration of the ``scheduler.identity`` spans (the ``id()`` sweep over the
batch that decides whether the armed batch came again; on a miss, the diff
against it). None where the program stamps no such span."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["duration_s"]
        if s["name"] == "scheduler.identity" else None)
