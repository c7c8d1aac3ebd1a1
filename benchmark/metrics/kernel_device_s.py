"""Device seconds of the fleet kernels per traced wave, from the trace."""


def read(ctx):
    t = ctx["trace"]
    total = sum(t["op_s"].get(k, 0.0) for k in ctx["kernels"])
    return total / t["waves"] if total > 0 else None
