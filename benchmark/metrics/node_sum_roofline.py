"""Least time of one node-sum over the whole federation, from the cell's
shapes and the chip's peaks (benchmark/roofline_estimator.py), over the
device time of ``node_sum_table`` per traced wave. Percent. One node-sum a
wave: every member's nodes are re-estimated every wave."""

from ..roofline_estimator import least_seconds, node_sum_count
from . import estimator_device_s


def read(ctx):
    dev = estimator_device_s.read(ctx)
    if dev is None or "nodes" not in ctx["cfg"].get("fleet", {}):
        return None
    cfg = ctx["cfg"]
    count = node_sum_count(
        int(cfg["clusters"]), int(cfg["fleet"]["nodes"]),
        int(cfg["resource_dims"]), len(cfg["request_profiles"]))
    least, bound = least_seconds(count, ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"node_sum_roofline bound={bound} least_s={least:.6g} device_s={dev:.6g}")
    return 100.0 * least / dev
