"""Least time of one admission of the deployment's wave, from its sizes and
the chip's peaks (benchmark/roofline_quota.py), over the device time of the
admission kernels per traced wave. Percent."""

from ..roofline_quota import cell_counts, least_seconds
from . import quota_admit_device_s


def read(ctx):
    dev = quota_admit_device_s.read(ctx)
    if dev is None or "tenants" not in ctx["cfg"]:
        return None
    least, bound = least_seconds(cell_counts(ctx["cfg"])[0], ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"quota_admit_roofline bound={bound} least_s={least:.6g} "
        f"device_s={dev:.6g}")
    return 100.0 * least / dev
