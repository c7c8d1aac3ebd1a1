"""Device seconds of the node-sum kernel per traced wave, from the trace
(the jitted ``node_sum_table``; its stage is the ``estimator.node_sum``
scope)."""

KERNEL = "jit_node_sum_table"


def read(ctx):
    t = ctx["trace"]
    total = t["op_s"].get(KERNEL, 0.0)
    return total / t["waves"] if total > 0 else None
