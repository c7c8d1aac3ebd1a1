"""Device seconds of a wave's quota admission per traced wave, from the
trace: the jitted ``quota_admit`` (the FIFO admission) and, where the
program derives the rows' namespaces and demands on the device,
``_fleet_quota`` (its stage is the ``fleet.quota`` scope). One admission a
wave (a quota generation a wave). Nothing where no such kernel ran."""

KERNELS = ("jit_quota_admit", "jit__fleet_quota")


def read(ctx):
    t = ctx["trace"]
    total = sum(t["op_s"].get(k, 0.0) for k in KERNELS)
    return total / t["waves"] if total > 0 else None
