"""Device seconds of the feasibility-bitset kernel per traced wave, from the
trace (the jitted ``_fleet_bits``; its stage is the ``fleet.bits`` scope).
One bits pass a wave: the wave reads a Duplicated row."""

KERNEL = "jit__fleet_bits"


def read(ctx):
    t = ctx["trace"]
    total = t["op_s"].get(KERNEL, 0.0)
    return total / t["waves"] if total > 0 else None
