"""The least one node-sum has to do, counted from the cell's shapes only:
C members of N nodes, R resource dims, P request profiles. Whatever arrays
an implementation materialises is its own business (the [P, C, N]
intermediate is); this count does not follow it.

Bytes read once: the node table, one int64 a (member, node, dim), and the
profile table, R int64 a profile. Bytes written once: one int32 a (profile,
member). Integer operations: for each (profile, member, node) one divide
and one min a requested dim, and one add into the member's sum. Every
profile of these cells requests every dim (cpu, memory, and one pod a
replica)."""

from __future__ import annotations

from .roofline import least_seconds  # noqa: F401  (the readers' one import)


def node_sum_count(c: int, n: int, r: int, p: int) -> dict:
    read = c * n * r * 8 + p * r * 8
    written = p * c * 4
    ops = p * c * n * (2 * r + 1)
    return {"bytes": read + written, "int_ops": ops}
