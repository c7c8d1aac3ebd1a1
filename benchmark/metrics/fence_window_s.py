"""The host's wait at the device fence: per wave, the ``kernel.device``
spans' duration. Beside ``kernel_device_s`` (what the device ran) it shows
what lies between the dispatch and the execution."""

from ._per_wave import median_of_sums


def read(ctx):
    return median_of_sums(
        ctx["spans"], ctx["waves"],
        lambda s: s["duration_s"] if s["name"] == "kernel.device" else None)
