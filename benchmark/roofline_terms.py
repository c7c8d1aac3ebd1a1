"""The least one choice of affinity group has to do for a batch's multi-term
rows, counted from the cell's shapes only: B multi-term rows, T groups a
placement (the most the configuration has), C members, U (placement, group)
pairs, P request profiles, K_PREV previous sites and K_EVICT eviction tasks
a row. Whatever arrays an implementation materialises is its own business;
this count does not follow it.

The choice answers, for every multi-term row, the first of its groups
whose candidates can hold it. Bytes read once: per row its T group slots,
its profile slot and its replicas (int32 each), K_PREV previous (site,
count) int32 pairs (the predicate weighs what the row holds) and K_EVICT
task sites (int32 each); one affinity plane of ceil(C/8) bytes a
(placement, group) pair; the profile table's rows, C int32 availabilities
a profile. Bytes written once: the chosen slot (int32) and the group's
index (one byte) a row. Integer operations: for each (row, group, member)
cell the ANDs of the candidate expression and the two masked adds of the
predicate's sums, and one compare a previous site and a task site."""

from __future__ import annotations

from .roofline import least_seconds  # noqa: F401  (the readers' one import)


def fleet_terms_count(b: int, t: int, c: int, k_prev: int, k_evict: int,
                      u: int, p: int) -> dict:
    w8 = -(-c // 8)
    read = (b * (t * 4 + 4 + 4 + k_prev * 8 + k_evict * 4)
            + u * w8 + p * c * 4)
    written = b * 5
    ops = b * c * (t * 6 + k_prev + k_evict)
    return {"bytes": read + written, "int_ops": ops}
