"""Least time of one full fleet pass, from the cell's shapes and the chip's
peaks (benchmark/roofline.py), over the device time of jit__fleet_pass per
traced wave. Percent. Full-storm cells only: a dirty-row wave runs the
kernel over a fraction of the rows."""

from ..roofline import fleet_pass_count, least_seconds


def read(ctx):
    t, cfg = ctx["trace"], ctx["cfg"]
    dev = t["op_s"].get("jit__fleet_pass", 0.0) / t["waves"]
    if dev <= 0:
        return None
    count = fleet_pass_count(
        int(cfg["bindings"]), int(cfg["clusters"]), int(cfg["resource_dims"]),
        len(cfg["request_profiles"]),
        int(cfg["bindings_mix"]["prev_sites_max"]))
    least, bound = least_seconds(count, ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"fleet_pass_roofline bound={bound} least_s={least:.6g} device_s={dev:.6g}")
    return 100.0 * least / dev
