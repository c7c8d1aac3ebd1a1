"""Least time of one feasibility-bitset pass over the batch, from the cell's
shapes and the chip's peaks (benchmark/roofline_bits.py), over the device
time of ``_fleet_bits`` per traced wave. Percent."""

from ..roofline_bits import fleet_bits_count, least_seconds
from . import fleet_bits_device_s


def read(ctx):
    dev = fleet_bits_device_s.read(ctx)
    cfg = ctx["cfg"]
    if dev is None or "placements" not in cfg:
        return None
    count = fleet_bits_count(
        int(cfg["bindings"]), int(cfg["clusters"]), len(cfg["placements"]),
        int(cfg["bindings_mix"]["prev_sites_max"]))
    least, bound = least_seconds(count, ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"fleet_bits_roofline bound={bound} least_s={least:.6g} "
        f"device_s={dev:.6g}")
    return 100.0 * least / dev
