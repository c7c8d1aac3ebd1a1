"""Device seconds of the term kernel per traced wave, from the trace (the
jitted ``_fleet_terms``; its stage is the ``fleet.terms`` scope). One pass a
wave over the batch's multi-term rows. Nothing where no such kernel ran."""

KERNEL = "jit__fleet_terms"


def read(ctx):
    t = ctx["trace"]
    total = t["op_s"].get(KERNEL, 0.0)
    return total / t["waves"] if total > 0 else None
