"""Least time of one static-assignment ceiling over the deployment's profile
slots and members, from its sizes and the chip's peaks
(benchmark/roofline_quota.py), over the device time of
``quota_cluster_caps`` per traced wave (the profile table is rebuilt, and
the ceiling folded into it, once a snapshot generation: once a wave).
Percent. Nothing where no such kernel ran."""

from ..roofline_quota import cell_counts, least_seconds

KERNEL = "jit_quota_cluster_caps"


def read(ctx):
    t = ctx["trace"]
    total = t["op_s"].get(KERNEL, 0.0)
    if total <= 0 or "tenants" not in ctx["cfg"]:
        return None
    dev = total / t["waves"]
    least, bound = least_seconds(cell_counts(ctx["cfg"])[1], ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"quota_caps_roofline bound={bound} least_s={least:.6g} "
        f"device_s={dev:.6g}")
    return 100.0 * least / dev
