"""ISSUE 28: the two places where ``_fleet_pass`` used a per-element TPU
scatter and no longer does, held to what the scatters gave.

- ``_row_masks``' previous-assignment grid (a compare-and-sum over the
  K_PREV (site, count) pairs of a row) against ``np.add.at`` on the host,
  and its lowered text against the word ``scatter``;
- the flat wire of ``_fleet_pass`` byte for byte against a plain numpy
  builder of the documented layout, with the old resident crafted so that
  rows carry exactly the delta counts at the edges of the format.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import karmada_tpu.scheduler.fleet as fleet_mod
from karmada_tpu.scheduler import ClusterSnapshot, TensorScheduler
from karmada_tpu.scheduler.fleet import K_PREV, MAX_REPLICAS_FAST
from karmada_tpu.utils.builders import synthetic_fleet

from test_delta_solve import build_problems


# --------------------------------------------------------------------------
# the previous-assignment grid
# --------------------------------------------------------------------------


def _prev_pairs(chunk: int, c: int, duplicates: bool, seed: int):
    """(sites, counts) as ``_pack_row`` leaves them: a row's first few
    slots hold (site, count), the rest the (0, 0) padding."""
    rng = np.random.default_rng(seed)
    n_used = rng.integers(0, K_PREV + 1, chunk)
    n_used[0], n_used[-1] = 0, K_PREV  # an empty row and a full one
    sites = np.zeros((chunk, K_PREV), np.int32)
    counts = np.zeros((chunk, K_PREV), np.int32)
    for i, k in enumerate(n_used):
        if duplicates:
            sites[i, :k] = rng.integers(0, c, k)
        else:
            k = min(k, c)
            sites[i, :k] = rng.choice(c, k, replace=False)
        counts[i, :k] = rng.integers(1, MAX_REPLICAS_FAST + 1, k)
    return sites, counts


def _row_masks_args(chunk: int, c: int, sites, counts):
    w8 = -(-c // 8)
    return (
        jnp.full((1, 2 * w8), 0xFF, jnp.uint8),  # cp_bits
        jnp.ones((1, c), jnp.int32),  # cp_static
        jnp.full((1, w8), 0xFF, jnp.uint8),  # gvk_bits
        jnp.zeros((c,), bool),  # incomplete_en
        jnp.zeros((chunk,), jnp.int32),  # cpc
        jnp.zeros((chunk,), jnp.int32),  # gvc
        jnp.asarray(sites),
        jnp.asarray(counts),
        jnp.full((chunk, fleet_mod.K_EVICT), -1, jnp.int32),  # evc: no task
        jnp.ones((chunk,), bool),  # vc
        jnp.full((chunk, w8), 0xFF, jnp.uint8),  # sbc: no row selected down
    )


@pytest.mark.parametrize("duplicates", [False, True], ids=["distinct", "dup"])
@pytest.mark.parametrize("c", [7, 100, 130, 5000])
@pytest.mark.parametrize("chunk", [8, 256])
def test_row_masks_prev_equals_host_scatter_add(chunk, c, duplicates):
    sites, counts = _prev_pairs(chunk, c, duplicates, seed=chunk * 31 + c)
    want = np.zeros((chunk, c), np.int64)
    np.add.at(want, (np.arange(chunk)[:, None], sites), counts)
    prev, _, feasible = fleet_mod._row_masks(
        *_row_masks_args(chunk, c, sites, counts), chunk, c
    )
    assert prev.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(prev), want)
    assert np.asarray(feasible).all()  # every plane set: nothing masked


def test_row_masks_lowers_to_no_scatter():
    chunk, c = 256, 100
    sites, counts = _prev_pairs(chunk, c, True, seed=1)
    lowered = jax.jit(fleet_mod._row_masks, static_argnums=(11, 12)).lower(
        *_row_masks_args(chunk, c, sites, counts), chunk, c
    )
    # without debug info: the locations carry this test's own name
    assert "scatter" not in lowered.as_text()
    scoped = lowered.as_text(debug_info=True)
    assert "fleet.prev" in scoped and "fleet.evict" in scoped


# --------------------------------------------------------------------------
# the flat wire
# --------------------------------------------------------------------------

N_PAD, CHUNK, M_CAP = 768, 256, 1024


def _le(v: int, n: int) -> np.ndarray:
    return np.array([(v >> (8 * i)) & 0xFF for i in range(n)], np.uint8)


def _le_stream(words: np.ndarray, n: int) -> np.ndarray:
    words = np.asarray(words, np.int64)
    return np.stack(
        [(words >> (8 * i)) & 0xFF for i in range(n)], axis=-1
    ).astype(np.uint8).reshape(-1)


def host_wire(old_d, new_d, old_m, new_m, valid, m_cap, d_cap):
    """The wire as ``_fleet_pass`` documents it, built row by row:
    [4B total][bitmask][m_cap x 2B changed metas][4B dtotal][d_cap x 3B
    cell deltas (site << 9 | new count + 1), site-ascending a row, rows
    with more than 62 changed cells left to phase B]."""
    cell = (old_d != new_d) & valid[:, None]
    dcount = cell.sum(axis=1)
    changed = (cell.any(axis=1) | (old_m != new_m)) & valid
    metas = np.zeros(m_cap, np.int64)
    stream = []
    for k, r in enumerate(np.flatnonzero(changed)):
        if k < m_cap:
            metas[k] = int(new_m[r]) | (min(int(dcount[r]), 63) << 10)
        if dcount[r] <= 62:
            stream += [
                (int(s) << 9) | (int(new_d[r, s]) + 1)
                for s in np.flatnonzero(cell[r])
            ]
    deltas = np.zeros(d_cap, np.int64)
    deltas[: min(len(stream), d_cap)] = stream[:d_cap]
    return np.concatenate([
        _le(int(changed.sum()), 4),
        np.packbits(changed, bitorder="little"),
        _le_stream(metas, 2),
        _le(len(stream), 4),
        _le_stream(deltas, 3),
    ]), len(stream)


class _Pass:
    """One table's inputs to ``_fleet_pass`` and the kernel's own answer
    over them (dense grid, state words) from an all-zero resident."""

    def __init__(self, c: int):
        snap = ClusterSnapshot(synthetic_fleet(c, seed=11, taint_fraction=0.08))
        eng = TensorScheduler(snap, trace_manifest="")
        eng.fleet_threshold = 1
        problems = build_problems(snap, 600, with_dup=False)
        eng.schedule(problems)
        table = eng._fleet
        self.c, self.cap = c, table._res_dense.shape[0]
        self.tables, self.state = table._dev_tables, table._dev_state
        rows = np.arange(N_PAD, dtype=np.int32)
        rows[600:] = -1
        self.rows, self.valid = jnp.asarray(rows), rows >= 0
        _, _, rd, rm = self.run(
            np.zeros((self.cap, c), np.uint8), np.zeros(self.cap, np.int32), 0
        )
        self.dense, self.meta = np.asarray(rd), np.asarray(rm)

    def run(self, res_dense, res_meta, d_cap):
        return fleet_mod._fleet_pass(
            *self.tables, self.rows, *self.state,
            jnp.asarray(res_dense), jnp.asarray(res_meta),
            chunk=CHUNK, n_chunks=N_PAD // CHUNK, wide=False, fast=None,
            has_aggregated=False, all_rows=False, m_cap=M_CAP, d_cap=d_cap,
        )

    def resident_with(self, cells_by_row: dict, meta_rows=()):
        """The resident a pass would have left had ``cells_by_row[r]``
        cells of row r (and the state words of ``meta_rows``) differed."""
        old_d, old_m = self.dense.copy(), self.meta.copy()
        for r, k in cells_by_row.items():
            old_d[r, :k] ^= 1
        for r in meta_rows:
            old_m[r] ^= 1 << 8
        return old_d, old_m


@pytest.fixture(scope="module", params=[48, 100], ids=["c48", "c100"])
def fleet_pass(request):
    return _Pass(request.param)


def _scenario(p: _Pass, name: str):
    rng = np.random.default_rng(5)
    live = np.flatnonzero(p.valid)
    if name == "none":
        return p.resident_with({})
    if name == "every":
        return p.resident_with(
            {int(r): int(rng.integers(1, min(p.c, 62) + 1)) for r in live}
        )
    # the format's edges: rows at 62 (the last that rides the stream), 63
    # (the sentinel) and past the 64 slots where the fleet is that wide,
    # every cell of a narrow fleet's row, a state-only change, and rows
    # straddling the chunk boundary
    over = [62, 63, 65, p.c] if p.c > 64 else [p.c, p.c - 1]
    cells = {int(r): int(rng.integers(1, 30)) for r in live[::3]}
    for r, k in zip((1, 255, 256, 257, 511, 599), over * 2):
        cells[r] = k
    return p.resident_with(cells, meta_rows=(5, 258))


@pytest.mark.parametrize(
    "scenario,d_rel",
    [("none", None), ("every", None), ("edges", None), ("edges", -1),
     ("edges", 0), ("edges", 1)],
    ids=["none", "every", "edges", "edges-cap-under", "edges-cap-at",
         "edges-cap-over"],
)
def test_fleet_pass_wire_bytes(fleet_pass, scenario, d_rel):
    """``d_rel``: the delta buffer holds one entry fewer than the stream,
    exactly the stream, one more; None: the floor."""
    p = fleet_pass
    old_d, old_m = _scenario(p, scenario)
    _, dtotal = host_wire(
        old_d[:N_PAD], p.dense[:N_PAD], old_m[:N_PAD], p.meta[:N_PAD],
        p.valid, M_CAP, 0,
    )
    d_cap = 65536 if d_rel is None else dtotal + d_rel
    want, _ = host_wire(
        old_d[:N_PAD], p.dense[:N_PAD], old_m[:N_PAD], p.meta[:N_PAD],
        p.valid, M_CAP, d_cap,
    )
    flat, _, rd, rm = p.run(old_d, old_m, d_cap)
    got = np.asarray(flat)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(rd), p.dense)
    np.testing.assert_array_equal(np.asarray(rm), p.meta)
    if scenario == "edges":
        assert dtotal > 0 and (dtotal < d_cap) == (d_rel in (None, 1))
