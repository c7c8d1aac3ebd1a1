"""1 - union of the device's busy intervals over the traced stretch, %."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
