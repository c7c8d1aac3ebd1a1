"""Engine host prologue, the general host path: per wave, the summed
durations of the ``scheduler.host`` spans (the rows that left the fleet
table, packed, estimated, selected, divided and unpacked on the host; its
stages are the span's ``scheduler.host.*`` children). Nothing where no wave
holds such a span."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["duration_s"] if s["name"] == "scheduler.host" else None)
