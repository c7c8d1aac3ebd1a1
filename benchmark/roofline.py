"""The least a full fleet pass has to do, counted from the cell's shapes
only: B bindings, C clusters, R resource dims, P request profiles, K_PREV
previous sites a row. Whatever arrays an implementation materialises is its
own business; this count does not follow it.

Bytes read once: the binding x cluster candidate grid, one byte a cell
(filters are per placement and per previous site, so the grid is input to
the division); the cluster table (R int64 capacities a cluster); the
profile table (R int64 a profile); per row replicas, profile, placement
slot (int32 each), a fresh flag, and K_PREV (site, count) int32 pairs.
Bytes written once: at least one (site, count) int32 pair a row.
Integer operations: for each candidate cell one multiply, one divide and
one add of the weighted floor, and one compare of the selection that hands
out the remainder (a selection, not a sort, is the least).
"""

from __future__ import annotations


def fleet_pass_count(b: int, c: int, r: int, p: int, k_prev: int) -> dict:
    read = (b * c                      # candidate grid
            + c * r * 8 + p * r * 8    # cluster and profile tables
            + b * (3 * 4 + 1 + k_prev * 8))
    written = b * 8
    ops = b * c * 4
    return {"bytes": read + written, "int_ops": ops}


def least_seconds(count: dict, peak: dict) -> tuple:
    """(seconds, which bound) for a chip's peaks: bytes over HBM bandwidth
    against integer operations over the int8 peak (the only published
    integer peak; generous to the chip, so the share reads low, not high)."""
    t_bytes = count["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = count["int_ops"] / peak["int8_ops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "int_ops")
