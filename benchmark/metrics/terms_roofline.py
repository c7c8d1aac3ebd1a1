"""Least time of one choice of affinity group for the batch's multi-term
rows, from the cell's shapes and the chip's peaks
(benchmark/roofline_terms.py), over the device time of ``_fleet_terms`` per
traced wave. Percent."""

from ..roofline_terms import fleet_terms_count, least_seconds
from . import terms_device_s


def read(ctx):
    dev = terms_device_s.read(ctx)
    cfg = ctx["cfg"]
    if dev is None or "row_state" not in cfg:
        return None
    multi = [p for p in cfg["placements"] if len(p["terms"]) > 1]
    regions = int(cfg["layout"]["regions"])
    count = fleet_terms_count(
        b=round(int(cfg["bindings"]) * sum(float(p["share"]) for p in multi)),
        t=max(len(p["terms"]) for p in multi),
        c=int(cfg["clusters"]),
        k_prev=int(cfg["bindings_mix"]["prev_sites_max"]),
        k_evict=int(cfg["row_state"]["k_evict"]),
        u=sum(len(p["terms"]) * (regions if p.get("per_home_region") else 1)
              for p in cfg["placements"]),
        p=len(cfg["request_profiles"]))
    least, bound = least_seconds(count, ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"terms_roofline bound={bound} least_s={least:.6g} "
        f"device_s={dev:.6g}")
    return 100.0 * least / dev
