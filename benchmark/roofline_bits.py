"""The least one feasibility-bitset pass has to do, counted from the cell's
shapes only: B bindings, C clusters, U placements, K_PREV previous sites a
row. Whatever arrays an implementation materialises is its own business;
this count does not follow it.

The pass answers, for every row of the batch, which members it may be
placed on: the row's placement's affinity and taint planes, its API's
enablement plane, its own spread selection, and the leniency its previous
sites buy. Bytes read once: per row the placement slot and the API slot
(int32 each), K_PREV previous sites (int32 each; a count is not needed to
know a site is held) and the row's selection mask, ceil(C/8) bytes; the
placement table (two planes of ceil(C/8) bytes a placement) and one
enablement plane. Bytes written once: ceil(C/8) a row. Integer operations:
for each (row, member) cell the three ANDs and two ORs of the feasibility
expression and one compare a previous site (a held member is found by
comparing, not by scattering)."""

from __future__ import annotations

from .roofline import least_seconds  # noqa: F401  (the readers' one import)


def fleet_bits_count(b: int, c: int, u: int, k_prev: int) -> dict:
    w8 = -(-c // 8)
    read = b * (2 * 4 + k_prev * 4 + w8) + u * 2 * w8 + w8
    written = b * w8
    ops = b * c * (5 + k_prev)
    return {"bytes": read + written, "int_ops": ops}
