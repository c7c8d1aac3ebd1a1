"""Device-resident fleet scheduling: the informer->cache analogue.

Ref: pkg/scheduler/cache/cache.go:42-62 — the reference keeps a cluster
cache fed by informers so each scheduling attempt touches only deltas.
This module is that idea taken device-side: per-binding state (placement
slot, request profile slot, previous assignment sites, replicas, flags)
and every row's last answer live in HBM between scheduling passes, and
each pass is

    host delta scatter  ->  ONE fused XLA dispatch  ->  ONE compact fetch.

Why this exists: round 1's engine packed every BindingProblem from scratch
per pass (Python loops over sparse entries + per-chunk np.pad + per-chunk
device syncs), which capped the engine at ~4k bindings/s while the kernel
alone did 100k x 5k in 0.74 s. The fleet table removes all per-pass O(B)
host packing for unchanged bindings and all but one device round-trip.

There is ONE resident layout: the dense assignment, [cap, C] cells, with
one meta word a row, donated into every pass and updated in place. A cell
is one byte (uint8) while every resident Divided row asks at most
NARROW_CELL_MAX replicas, and two (uint16) from the first row that asks
more: the cell width is the table's, widened once from what its rows
observe, as its cap grows, and the meta, cell-delta and entry words widen
with it. A table whose cap x C would pass DENSE_RESIDENT_MAX_BYTES raises
FleetTableTooLarge where it grows: there is no second layout to fall back
to.

What the layout minimises: bytes moved between host and device per pass,
and blocking host<->device round-trips per pass. Where a pass's time goes
on the v5e is measured cell by cell in PERF.md sections 5 and 6 (at
100k x 100, all rows moving: the kernel 24 ms, the 7.5 MB fetch 11 ms, the
decode 9 ms):

- all per-row state is gathered ON DEVICE from resident arrays (`rows` is
  the only per-pass index upload, and the all-rows storm case keeps even
  that cached on device);
- placement/taint/static-weight masks are interned per unique placement
  and gathered per chunk with plain [B]-index row gathers (re-probed on
  the current backend across U=2..3500: compiles cleanly and runs at
  bandwidth; the historical one-hot-matmul workaround for a scan-gather
  compile hang remains in ops.estimate.gather_profile_rows for other
  callers);
- DELTA FETCH: _fleet_pass diffs each row's new dense vector against the
  resident and ships home a changed-row bitmask, the changed rows' meta
  words and their changed CELLS (site << 9 | count + 1), folded into a
  host-side mirror of every row's (site << 8 | count) entry vector (with
  two-byte cells site << 17 | count + 1 and site << 16 | count). A
  steady rebalance storm re-divides all 100k bindings on device and
  fetches a few tens of KB; a churn pass ships the cells that moved;
- rows with more than 62 changed cells, and passes whose deltas overflow
  their buffer, take phase B: _fleet_entries gathers exactly those rows
  from the dense resident and compacts each into its entry vector by ONE
  ascending single-operand sort (the packed word orders by site). The
  dispense finds its largest-remainder bonus threshold by binary search
  instead of top_k;
- no per-element scatter or gather where the data is already ordered.
  Measured on the v5e (PERF.md section 6, PR 28): an element scatter
  costs 4.6-8 ns an update and an element gather 9 ns, whatever the
  bandwidth. So the previous-assignment grid is a compare-and-sum
  (_row_masks: 0.6 ms of a 100k x 100 pass, the scatter-add 26.8), and
  the rows' valid prefixes are joined into the wire's stream by log-step
  shifts (_compact_rows: 1.7 ms for 6.55M slots, the scatter 30.2, a
  gather by row offsets 20-21 alone);
- feasible bitsets ride a second, lazily-dispatched kernel (_fleet_bits)
  only when the batch contains Duplicated or zero-replica bindings;
- a spread-constrained row's SelectClusters result is ROW STATE: one packed
  selection mask a row (``sel_bits``, ceil(C/8) bytes, all ones for a row
  without constraints), ANDed into the candidate mask in _row_masks. It is
  COMPUTED ON THE DEVICE, in every pass that holds such rows, by one
  batched kernel (_fleet_select: scheduler/select.py's tensor form of
  spread.py + groups.py) over the same resident state the pass divides on,
  and written straight into the resident ``sel_bits``: the host neither
  computes, packs, compares nor uploads it. A snapshot with more regions
  than the kernel's subset table (R_CAP) keeps the host selection, uploaded
  with the rows whose selection moved (_apply_selections). The placement
  table holds the placements users wrote, however long the federation runs;
- failover is row state too. The placement table interns one slot for each
  (placement, affinity term): term t's slot carries that term's affinity
  plane, the placement's taint plane and its static weights. A row holds
  its ordered term slots (``term_slots``, T_CAP of them, -1 = unused) and
  its graceful-eviction tasks (``evict_sites``, K_EVICT cluster indices,
  -1 = unused); ``cp_idx`` is the slot _row_masks gathers: the CHOSEN
  term's. _row_masks masks the evicted members of every row by one
  compare-and-reduce (the form ``fleet.prev`` has), and in every pass that
  holds multi-term rows one batched kernel (_fleet_terms) builds each
  term's candidate set with _row_masks' own algebra, applies the divider's
  schedulability predicate (ops.masks._first_fit_group_kernel, in int32)
  on the availability the pass divides on, and writes the first fitting
  term's slot into the resident ``cp_idx`` and its index into ``term_sel``
  (one byte a row: what a result's ``affinity_name`` reads);
- a tenant's FederatedResourceQuota is row state as well. A row holds its
  namespace's index in the engine's QuotaSnapshot (``ns_idx``, -1 = not
  quota'd), kept BESIDE the state the pass reads. At every quota
  generation one kernel (_fleet_quota) derives each row's delta demand
  from the resident replicas, previous counts and request profile, runs
  ops.quota.quota_admit over the batch in presented order, and answers a
  denied bit a row: a denial is a bit beside the row's answer (the
  division the pass computed for it is ignored), never the row's absence,
  so a quota move keeps the batch, its length and the identity fast path.

- a previous result of more than K_PREV sites is row state too: the row
  takes a slot of the WIDE table (int32[W, C], the whole previous result
  as a dense row; W the slots in use, rounded up to a power of two) and
  its first previous site names the slot (-1 - slot; its K_PREV columns
  hold nothing else). Every reader of the previous result (_row_masks, so
  _fleet_pass, _fleet_bits, _fleet_select and _fleet_terms) adds the
  slot's row to the compare-and-sum of the columns; _fleet_quota reads
  the row's held total from ``prev_rest``. The table and its argument
  exist from the first wide row on: a table that never held one runs the
  traces it ran before the form existed.

Eligibility: a binding rides the fleet path when its placement has at most
T_CAP affinity terms and, with more than one term, no spread constraints
(a single-term placement rides with spread constraints or without: a
FitError of the device selection is the row's empty candidate set; where
the host selects, the selection must have accepted the row), and the
binding holds <= K_EVICT eviction tasks and (for Divided strategies)
replicas <= replicas_bound(C): what a two-byte cell holds
(MAX_REPLICAS_FAST) below WIDE_CLUSTERS members, what a one-byte cell
holds from there on. Any previous result rides. Everything else takes the
general host path, row by row in the same batch — the two paths are
differentially fuzz-tested for identical placements.
"""

from __future__ import annotations

import logging
import time

from functools import partial
from typing import NamedTuple, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.divide import (
    AGGREGATED,
    DUPLICATED as S_DUPLICATED,
    DYNAMIC_WEIGHT,
    _divide_batch,
)
from ..ops.estimate import MAX_INT32, merge_estimates
from ..ops.explain import explain_pass as _explain_pass
from ..ops.masks import _first_fit_group_kernel
from ..ops.preempt import preempt_select as _preempt_select
from ..ops.quota import (
    DEMAND_CLAMP,
    UNLIMITED,
    quota_admit as _quota_admit,
    quota_cluster_caps as _quota_cluster_caps,
)
from .select import (
    MODE_NONE,
    N_PARAMS,
    R_CAP,
    constraint_params,
    region_table,
    select_rows,
    subset_table,
)

log = logging.getLogger("karmada_tpu")

#: trace-key prefix -> kernel family, for the per-bucket compile counter
#: (karmada_tpu_kernel_compiles_total) every _mark_trace feeds
_TRACE_KERNELS = {
    "A": "fleet_pass",
    "E": "fleet_entries",
    "B": "fleet_bits",
    "T": "fleet_select",
    "R": "fleet_terms",
    "Q": "fleet_quota",
    "S": "state_scatter",
    "G": "meta_gather",
    "F": "estimate_fold",
}

K_PREV = 32  # (site, count) columns of a row's previous result (small
# fleets legitimately spread one binding over dozens of clusters; a row
# with more takes a slot of the wide table)
NARROW_CELL_MAX = 0xFF  # the most a one-byte cell of the dense resident holds
MAX_REPLICAS_FAST = 0xFFFF  # a Divided row's replicas: a two-byte cell holds
# any of its counts
WIDE_CLUSTERS = 1 << 14  # two-byte cells need fewer members: the cell-delta
# word (site << 17 | count + 1) and the term and select kernels' int32 sums
# ((replicas + 1) x C, twice) hold there
T_CAP = 4  # ordered affinity terms a row holds as term slots (ClusterAffinities
# is "primary, then backup": placements past this take the general host path)
K_EVICT = 8  # graceful-eviction tasks a row holds as cluster indices (a task
# drains within its grace period; rows past this take the general host path)
MAX_SLOTS = 8192  # unique placements/gvks/profiles FLOOR before slot
# eviction engages. Sizing (bitpacked layout): a slot costs two packed
# mask planes (2*ceil(C/8) uint8) + an int32 static-weight row (4C) ~
# 21 KB at C=5000; plain row gathers make the per-pass cost independent
# of U. The EFFECTIVE cap scales with the cluster count up to
# CP_TABLE_MAX_BYTES (a 5k-cluster fleet carries the MAX_SLOTS_HARD
# 65536 uniques within ~1.4 GB), and crossing it first evicts slots no
# live row references — only a fleet whose LIVE rows reference more
# uniques than the budget allows falls back to a rebuild per call.
CP_TABLE_MAX_BYTES = 1536 << 20  # device cp-table budget (HBM)
MAX_SLOTS_HARD = 65536  # interning-dict / host-staging sanity bound
E_ROUND = 1 << 18  # entry-buffer quantum (bounds trace churn)


def replicas_bound(c: int) -> int:
    """The most replicas a Divided row may ask to ride a table of ``c``
    members: what a two-byte cell holds where its words fit int32 (fewer
    than WIDE_CLUSTERS members), else what a one-byte cell holds."""
    return MAX_REPLICAS_FAST if c < WIDE_CLUSTERS else NARROW_CELL_MAX


def row_rides(p, cp) -> bool:
    """The per-binding half of THE fleet-eligibility predicate (the
    placement half is CompiledPlacement.fleet_terms, or the selection a
    spread-constrained row was given): the row state has room for the
    binding's eviction tasks (any previous result has room: past K_PREV
    sites in a wide slot), and a cell holds a Divided row's counts."""
    return len(p.evict_clusters) <= K_EVICT and (
        cp.strategy == S_DUPLICATED
        or p.replicas <= replicas_bound(len(cp.taint_ok))
    )


def _le_bytes(x, n: int):
    """int32[...] -> uint8[..., n] flattened: the low ``n`` bytes of each
    value, little-endian (the wire's byte order)."""
    return jnp.stack(
        [x & 0xFF] + [(x >> (8 * k)) & 0xFF for k in range(1, n)], axis=-1
    ).astype(jnp.uint8).reshape(-1)


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _cap_round(v: int) -> int:
    """Entry-buffer quantization: powers of two (floor 1024) up to the
    quantum, then QUARTER-OCTAVE buckets (5/8, 6/8, 7/8, 8/8 of the next
    power of two). Fixed-size quanta broke at scale: a 32M-entry churn
    demand drifting ±1% per pass landed in a different 256k-multiple each
    time, recompiling the (minutes-long at 1M rows) solve every pass;
    quarter-octaves bound the overshoot at 25% with 4 traces per octave."""
    v = max(v, 1)
    if v <= E_ROUND:
        return _pow2(max(v, 1024))
    p = _pow2(v)  # v in (p/2, p]
    for frac in (5, 6, 7):
        if v * 8 <= p * frac:
            return p * frac // 8
    return p


def _slot_cap(n: int) -> int:
    """Device slot-table capacity: pow2 up to 8192, then multiples of 4096
    — pow2 beyond that wastes up to half the (hundreds-of-MB) cp table,
    while the coarse quantum keeps the solve's trace count bounded."""
    return _pow2(max(n, 16)) if n <= 8192 else -(-n // 4096) * 4096


def _pack21(stream, e_cap: int):
    """Pack int32 values < 2^21 (site<<8|count with site < 2^13) into a
    21-bit little-endian bitstream: 2.625 bytes/entry instead of 3 on
    the churn wire (device->host bytes per pass). Each output byte draws from at most two adjacent fields
    (field width 21 > 8), so two static gathers + shifts produce it."""
    nb = (e_cap * 21 + 7) // 8
    # index math as traced iota, NOT host numpy: numpy arrays close over
    # the trace as dense HLO literals — three nb-length constants made the
    # serialized module ~24 B per e_cap entry (123 MB at the 100k tier's
    # 5M-entry cap, 1.3 GB at the 1M tier). As iota the module is ~0.1 MB
    # at any cap.
    idx = jnp.arange(nb, dtype=jnp.int64) * 8
    k1 = (idx // 21).astype(jnp.int32)
    off = (idx - 21 * k1).astype(jnp.int32)
    s_ext = jnp.concatenate([stream, jnp.zeros((1,), jnp.int32)])
    lo = s_ext[k1] >> off
    hi = s_ext[jnp.minimum(k1 + 1, e_cap)] << (21 - off)
    return ((lo | hi) & 0xFF).astype(jnp.uint8)


def _entry_wire(stream, e_cap: int, pack21: bool, cell_bytes: int = 1):
    """The entry stream's byte-wire serialization (_decode_entry_wire is
    its inverse): 21-bit packed (+3 pad bytes for the host's
    4-byte-window decoder), plain 3-byte entries, or with two-byte cells
    4-byte entries."""
    if pack21:
        return jnp.concatenate(
            [_pack21(stream, e_cap), jnp.zeros((3,), jnp.uint8)]
        )
    return _le_bytes(stream, cell_bytes + 2)


def _compact_rows(slots, counts, cap: int):
    """Row-major compaction without a scatter. ``slots`` int32[n, w]: row
    r's first ``counts[r]`` (<= w) slots are live, in order. Returns
    (int32[cap]: the live slots of all rows back to back, zero-padded and
    cut at ``cap``; their total).

    Every live slot of row r has to move left by r*w - offs[r], a shift
    that never falls from one row to the next (counts <= w). Such a move
    needs no scatter: for each bit of the shift, low to high, the slots
    with that bit set move left by 2^bit, one select over the flat array.
    Two slots never meet: at equal low bits the later one lies at least as
    far right as it will end up ahead. On the v5e, 102,400 x 64 slots into
    2.4M, alone: 4.5 ms for the 23 selects (1.7 ms inside the pass)
    against 33.0 ms for the scatter of 6.55M updates (4.6 ns each) and
    20-21 ms for a gather of the 2.4M (9 ns each): PERF.md section 6,
    PR 28."""
    n, w = slots.shape
    counts = counts.astype(jnp.int32)
    offs = jnp.cumsum(counts, dtype=jnp.int32) - counts
    total = offs[-1] + counts[-1]
    live = jnp.arange(w, dtype=jnp.int32)[None, :] < counts[:, None]
    left = jnp.arange(n, dtype=jnp.int32) * w - offs
    vals = jnp.where(live, slots, 0).reshape(-1)
    shift = jnp.where(live, left[:, None], 0).reshape(-1)
    for b in range((n * w - 1).bit_length()):
        k = 1 << b
        v_in = jnp.pad(vals[k:], (0, k))
        s_in = jnp.pad(shift[k:], (0, k))
        arrives = ((s_in >> b) & 1) == 1
        leaves = ((shift >> b) & 1) == 1
        vals = jnp.where(arrives, v_in, jnp.where(leaves, 0, vals))
        shift = jnp.where(arrives, s_in, jnp.where(leaves, 0, shift))
    return jnp.pad(vals, (0, max(cap - n * w, 0)))[:cap], total


# --------------------------------------------------------------------------
# fused solve
# --------------------------------------------------------------------------


def _unpack_bits(bits_u8, c: int):
    """uint8[B, W8] (little bit order) -> bool[B, C]: the device-side
    inverse of np.packbits(bitorder='little'). Pure shifts/compares — the
    cost is one [B, C] elementwise pass, bought back eightfold in gather
    bandwidth."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    x = (bits_u8[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    return x.reshape(bits_u8.shape[0], -1)[:, :c] != 0


def _wide_prev_rows(wide_prev, psc):
    """int32[chunk, C]: each row's previous result kept in a slot of the
    wide table (its first previous site names the slot as -1 - slot),
    zeros for a row without one. A caller zeroes the sites of padding."""
    slot = -1 - psc[:, 0]
    live = slot >= 0
    return jnp.where(live[:, None], wide_prev[jnp.where(live, slot, 0)], 0)


def _live_sites(ps, valid, wide_prev):
    """``ps`` with the previous sites of padding zeroed where a wide table
    is read (a padding position reads row 0, which may name a slot)."""
    if wide_prev is None:
        return ps
    return jnp.where(valid[:, None], ps, 0)


def _row_masks(cp_bits, cp_static, gvk_bits, incomplete_en, cpc, gvc, psc,
               pcc, evc, vc, sbc, chunk: int, c: int, wide_prev=None):
    """Per-chunk previous-assignment grid + THE feasibility algebra,
    shared by every kernel that needs it (_fleet_pass, _fleet_bits) so
    the mask expression cannot drift between the solve and the
    lazily-computed feasibility bitsets. Returns (prev, static_w,
    feasible); callers apply their own sharding constraints. With
    ``wide_prev`` (the wide table, where the table holds one) a wide
    row's previous result is its slot's row.

    The affinity and taint planes ship BITPACKED (uint8, 8 clusters per
    byte): the per-row cp gather was the second-largest term of the 1M
    steady pass (60 KB/row as int32 planes -> 21 KB packed+static,
    measured 0.57 s -> ~0.2 s over 245 chunks), and the slot table's HBM
    footprint drops ~3x with it. ``sbc`` uint8[chunk, W8] is each row's
    own selection mask, packed the same way: the SelectClusters result of
    a spread-constrained row, all ones for every other row. ``evc``
    int32[chunk, K_EVICT] is each row's graceful-eviction tasks as cluster
    indices (-1 = unused): the ClusterEviction filter, for every row."""
    with jax.named_scope("fleet.prev"):
        # compare-and-sum, not a scatter-add: the K_PREV (site, count)
        # pairs of a row against the cluster iota, summed over the pairs.
        # Integer adds commute, so duplicate sites and the (0, 0) padding
        # give what a scatter-add gives; it fuses into the reduction
        # ([chunk, K, C] is never materialised) and is 0.6 ms of a
        # 100k x 100 pass where the scatter-add was 26.8 (PERF.md
        # section 6, PR 28)
        iota_c = jnp.arange(c, dtype=jnp.int32)
        prev = jnp.where(
            psc[:, :, None] == iota_c, pcc[:, :, None], 0
        ).sum(axis=1, dtype=jnp.int32)
        if wide_prev is not None:
            prev = prev + _wide_prev_rows(wide_prev, psc)
    with jax.named_scope("fleet.evict"):
        # the same compare-and-reduce: a row's K_EVICT task sites against
        # the cluster iota (-1 meets no cluster)
        evicted = (evc[:, :, None] == iota_c).any(axis=1)
    prev_mask = prev > 0
    # plain [B]-index row gathers: re-probed on the current backend at
    # U in {2..3500} x W in {5k, 15k} — compiles fine and runs at
    # bandwidth vs 0.29s+ for the one-hot matmul at heterogeneous U (the
    # matmul workaround predates this backend; ops.estimate.
    # gather_profile_rows keeps it for other callers)
    bits = cp_bits[cpc]  # [chunk, 2*W8] u8
    w8 = bits.shape[1] // 2
    aff_ok = _unpack_bits(bits[:, :w8], c)  # affinity & spread-field
    taint_ok = _unpack_bits(bits[:, w8:], c)
    static_w = cp_static[cpc]  # [chunk, C] i32
    gvk_ok = _unpack_bits(gvk_bits[gvc], c)
    feasible = (
        aff_ok
        & (gvk_ok | (prev_mask & incomplete_en[None, :]))
        & (taint_ok | prev_mask)  # taints (leniency)
        & ~evicted  # graceful-eviction tasks
        & _unpack_bits(sbc, c)  # the row's spread selection
        & vc[:, None]
    )
    return prev, static_w, feasible


# --------------------------------------------------------------------------
# two-phase solve: pass kernel (A) + changed-rows entry kernel (B)
# --------------------------------------------------------------------------
#
# The table keeps the DENSE assignment resident (uint8[cap, C], with one
# meta word a row beside it) and a pass is split in two:
#
#   A (_fleet_pass): divide every row, diff against the dense resident and
#      update it in place; the wire home is 4B changed-count + a
#      changed-row BITMASK (n/8 bytes) + the changed rows' meta words
#      (tuned cap m_cap) + the changed CELLS of rows with <= 62 of them
#      (tuned cap d_cap). No sort of unchanged rows, no entry stream: a
#      steady 100k pass ships ~13 KB plus the delta floor.
#   B (_fleet_entries): only for changed rows the cell deltas could not
#      carry — gather exactly those rows from the dense resident and
#      sort-compact THEM into the (site << 8 | count) entry stream. Every
#      placed site holds >= 1 replica, so a row has <= k_out entries and
#      the packed word sorts by site: one ascending single-operand sort
#      and a static prefix slice IS the row's entry vector (on the v5e at
#      C=5k: 0.29 s against 1.8 s for binary-search position extraction
#      and 2.5 s for scatter compaction). The entry cap is the sum of the
#      changed rows' n_placed, which the host already holds from A's
#      metas, so the buffer cannot overflow.

#: the most bytes the dense resident (cap x C, one uint8 a cell: the one
#: O(rows x clusters) tenant of the device) may take; FleetTable._grow
#: refuses a table past it. A 1M x 5k table's 5.2 GB resident plus the
#: solve's working set was measured RESOURCE_EXHAUSTED on a 16 GB v5e;
#: 6 GiB is 1.28M rows at 5,000 clusters and 64M rows at 100.
DENSE_RESIDENT_MAX_BYTES = 6 << 30


class FleetTableTooLarge(ValueError):
    """The fleet table would outgrow DENSE_RESIDENT_MAX_BYTES."""


M_ROUND = 1 << 15  # changed-meta buffer quantum (bounds trace churn)
D_ROUND = 1 << 16  # cell-delta buffer quantum (bounds trace churn)
D_FLOOR = 8192  # cell-delta floor: 24 KB of wire on every steady pass
SHRINK_SUSTAIN = 5  # passes a frozen shrink must stay desired to compile


def d_round(v: int) -> int:
    v = max(v, 1)
    return -(-v // D_ROUND) * D_ROUND if v > D_FLOOR else D_FLOOR


@partial(
    jax.jit,
    static_argnames=(
        "chunk", "n_chunks", "wide", "fast", "has_aggregated",
        "all_rows", "m_cap", "d_cap", "mesh", "shard_c", "cell_bytes",
    ),
    donate_argnames=("res_dense", "res_meta"),
)
def _fleet_pass(
    cp_bits,  # uint8[U, 2*W8]: bitpacked [aff&spread_field | taint]
    cp_static,  # int32[U, C]: static weights
    gvk_bits,  # uint8[G, W8] bitpacked enablement masks
    prof_table,  # int32[P, C] general availability (-1 = no answer)
    incomplete_en,  # bool[C] — ~CompleteAPIEnablements
    rows,  # int32[n_pad] table rows (-1 = padding)
    cp_idx, gvk_idx, prof_idx,  # int32[cap]
    replicas, strategy,  # int32[cap]
    fresh,  # bool[cap]
    prev_sites, prev_counts,  # int32[cap, K_PREV]
    evict_sites,  # int32[cap, K_EVICT] eviction-task cluster indices (-1 = none)
    sel_bits,  # uint8[cap, W8] bitpacked spread selection a row (ones = none)
    res_dense,  # uint8|uint16[cap, C] last pass's dense assignment (donated)
    res_meta,  # int32[cap] last pass's meta words (donated)
    wide_prev=None,  # int32[W, C] the wide table, where the table holds one
    *,
    chunk: int,
    n_chunks: int,
    wide: bool,
    fast: Optional[tuple],
    has_aggregated: bool,
    all_rows: bool,
    m_cap: int,
    d_cap: int = 0,
    mesh=None,
    shard_c: bool = False,
    cell_bytes: int = 1,
):
    """Phase A: divide every row, diff against the dense resident, ship the
    changed bitmask + changed metas — and, when ``d_cap`` > 0, the CELL
    deltas of changed rows (site<<9 | newcount+1, site-ascending per row)
    so a typical churn pass (a few cells move per changed row) needs no
    phase B at all. Returns (flat_wire_u8, changed_rowbuf, new_res_dense,
    new_res_meta); feasibility bitsets are _fleet_bits' separate, lazily
    dispatched job.

    ``cell_bytes`` is the resident's cell width: a count takes ``sb`` = 8
    or 16 bits, the meta word is n_placed | unsched << sb | has_cand <<
    sb + 1 (+ the changed-cell count << sb + 2 on the wire, cell_bytes + 1
    bytes a word), a cell delta site << sb + 1 | count + 1 (cell_bytes + 2
    bytes)."""
    c = cp_static.shape[1]
    cap = res_dense.shape[0]
    c_ax = "c" if (mesh is not None and shard_c) else None
    sb = 8 * cell_bytes
    # per-row delta slots: 62 exact + the 63 overflow sentinel fit the
    # meta word's 6 spare bits; rows with more changed cells fall back to
    # a full-row phase B fetch
    d_slots = min(64, c)

    def shard(a, *axes):
        if mesh is None:
            return a
        return lax.with_sharding_constraint(a, NamedSharding(mesh, P(*axes)))

    with jax.named_scope("fleet.gather"):
        valid = rows >= 0
        r = jnp.maximum(rows, 0)
        cp = cp_idx[r]
        gv = gvk_idx[r]
        pf = prof_idx[r]
        reps = jnp.where(valid, replicas[r], 0)
        st = strategy[r]
        fr = fresh[r] & valid
        ps = _live_sites(prev_sites[r], valid, wide_prev)
        pc = jnp.where(valid[:, None], prev_counts[r], 0)
        # an all-rows pass reads row i at position i (the padding past the
        # last row is masked by ``valid``), so the eviction sites and the
        # selection masks are sliced from the residents as they lie: no
        # gather
        ev = evict_sites if all_rows else evict_sites[r]
        sel = sel_bits if all_rows else sel_bits[r]

    def body(carry, i):
        rd, rm = carry
        with jax.named_scope("fleet.gather"):
            sl = lambda a: lax.dynamic_slice_in_dim(
                a, i * chunk, chunk, axis=0
            )
            cpc, gvc, pfc = sl(cp), sl(gv), sl(pf)
            repsc, stc, frc, vc = sl(reps), sl(st), sl(fr), sl(valid)
            psc, pcc, evc, sbc = sl(ps), sl(pc), sl(ev), sl(sel)
            rc = sl(r)
            repsc, stc, frc, vc = (
                shard(repsc, "b"), shard(stc, "b"), shard(frc, "b"),
                shard(vc, "b"),
            )
            cpc, gvc, pfc = (
                shard(cpc, "b"), shard(gvc, "b"), shard(pfc, "b")
            )
            psc, pcc = shard(psc, "b", None), shard(pcc, "b", None)
            evc, sbc = shard(evc, "b", None), shard(sbc, "b", None)
        with jax.named_scope("fleet.masks"):
            prev, static_w, feasible = _row_masks(
                cp_bits, cp_static, gvk_bits, incomplete_en, cpc, gvc, psc,
                pcc, evc, vc, sbc, chunk, c, wide_prev,
            )
            prev = shard(prev, "b", c_ax)
            feasible = shard(feasible, "b", c_ax)
        with jax.named_scope("fleet.estimate"):
            general = prof_table[pfc]
            avail = shard(merge_estimates(repsc, (general,)), "b", c_ax)
        with jax.named_scope("fleet.divide"):
            assignment, unsched = _divide_batch(
                stc, repsc, feasible, static_w, avail, prev, frc,
                has_aggregated, wide, fast,
            )
            # Duplicated rows ride the feasibility bitset; their dense rows
            # are zero so the resident diff ignores them (meta carries their
            # state)
            assignment = shard(
                jnp.where((stc == S_DUPLICATED)[:, None], 0, assignment),
                "b", c_ax,
            )
            # a cell holds every count: the table widens its cells before
            # a row asks more than a one-byte cell holds (row_rides bounds
            # the two-byte cell)
            dense8 = assignment.astype(
                jnp.uint8 if cell_bytes == 1 else jnp.uint16
            )
            n_placed = (assignment > 0).sum(axis=1).astype(jnp.int32)
            has_cand = feasible.any(axis=1)
            meta = (
                n_placed
                | (unsched.astype(jnp.int32) << sb)
                | (has_cand.astype(jnp.int32) << (sb + 1))
            )
        with jax.named_scope("fleet.diff"):
            # diff + in-place resident update. all_rows reads/writes
            # contiguous slices; partial batches use row gather/scatter (few
            # rows: the per-row scatter overhead is what made this form wrong
            # for the 100k storm, which is exactly the all_rows case)
            if all_rows:
                # int32 offsets: the SPMD partitioner mixes its s32
                # shard-offset arithmetic with the slice start, and an s64
                # start fails HLO verification on the row-sharded residents
                off = (i * chunk).astype(jnp.int32)
                z32 = jnp.int32(0)
                old_d = lax.dynamic_slice(rd, (off, z32), (chunk, c))
                old_m = lax.dynamic_slice_in_dim(rm, off, chunk, 0)
                rd = lax.dynamic_update_slice(rd, dense8, (off, z32))
                rm = lax.dynamic_update_slice_in_dim(rm, meta, off, 0)
            else:
                old_d = rd[rc]
                old_m = rm[rc]
                safe_r = jnp.where(vc, rc, cap)
                rd = rd.at[safe_r].set(dense8, mode="drop")
                rm = rm.at[safe_r].set(meta, mode="drop")
            cell_changed = (dense8 != old_d) & vc[:, None]
            dcount = cell_changed.sum(axis=1).astype(jnp.int32)
            changed = (cell_changed.any(axis=1) | (meta != old_m)) & vc
        with jax.named_scope("fleet.deltas"):
            if d_cap:
                # per-row delta compaction via sort, skipped entirely on
                # steady chunks (the sort over [chunk, C] is the only
                # non-trivial cost and a steady pass has no changed cells)
                idxs32 = jnp.arange(c, dtype=jnp.int32)[None, :]

                def _deltas(op):
                    d8, chm = op
                    dp = jnp.where(
                        chm,
                        (idxs32 << (sb + 1)) | (d8.astype(jnp.int32) + 1),
                        jnp.int32(2**31 - 1),
                    )
                    srt = lax.sort(dp, is_stable=False)[:, :d_slots]
                    return jnp.where(srt == 2**31 - 1, 0, srt)

                deltas = lax.cond(
                    cell_changed.any(),
                    _deltas,
                    lambda op: jnp.zeros((chunk, d_slots), jnp.int32),
                    (dense8, cell_changed),
                )
            else:
                deltas = jnp.zeros((chunk, 0), jnp.int32)
        return (rd, rm), (changed, meta, dcount, deltas)

    (res_dense, res_meta), outs = lax.scan(
        body, (res_dense, res_meta), jnp.arange(n_chunks)
    )
    with jax.named_scope("fleet.wire"):
        # pin the updated residents to their allocation layout (row-sharded
        # under a mesh): matching in/out shardings keep the donation
        # aliased, so the dense grid never double-buffers across passes
        res_dense = shard(res_dense, "b", c_ax)
        res_meta = shard(res_meta, "b")
        # the wire build below is GLOBAL prefix-scan + scatter compaction:
        # replicate its inputs explicitly so the residents' row sharding
        # cannot back-propagate into the cumsums (the CPU SPMD partitioner
        # emits corrupt streams for sharded global scans: changed totals
        # beyond the theoretical bound were observed)
        changed = shard(outs[0].reshape(-1), None)  # bool[n_pad]
        meta = shard(outs[1].reshape(-1), None)
        dcounts = shard(outs[2].reshape(-1), None)

        # wire: [4B total][bitmask n_pad/8 B][m_cap x 2B changed metas in
        # row order][4B dtotal][d_cap x 3B cell deltas] (delta section only
        # when d_cap > 0). n_pad is a multiple of 256, so the bitmask packs
        # evenly. The wire meta word carries state (n_placed | flags, 10
        # bits) plus min(dcount, 63) in the 6 spare bits; res_meta stores
        # STATE ONLY — dcount is pass-relative and must not trip the next
        # pass's meta diff.
        wire_meta = meta | (jnp.minimum(dcounts, 63) << (sb + 2))
        cnt = jnp.cumsum(changed.astype(jnp.int32)) - changed
        total = cnt[-1] + changed[-1].astype(jnp.int32)
        write = jnp.where(changed & (cnt < m_cap), cnt, m_cap)
        mbuf = jnp.zeros((m_cap + 1,), jnp.int32).at[write].set(wire_meta)
        mstream = mbuf[:m_cap]
        # changed TABLE rows, compacted in the same bitmask order — stays
        # on device so a speculative phase B can consume it without waiting
        # for the host to decode the bitmask (saves one host<->device
        # round-trip per churn pass)
        rowbuf = (
            jnp.full((m_cap + 1,), -1, jnp.int32).at[write].set(r)[:m_cap]
        )
        w32 = changed.reshape(-1, 32).astype(jnp.uint32)
        shifts = jnp.arange(32, dtype=jnp.uint32)[None, :]
        words = (w32 << shifts).sum(axis=-1, dtype=jnp.uint32)
        mask_u8 = jnp.stack(
            [(words >> s) & 0xFF for s in (0, 8, 16, 24)], axis=-1
        ).astype(jnp.uint8).reshape(-1)
        total_u8 = jnp.stack(
            [(total >> s) & 0xFF for s in (0, 8, 16, 24)]
        ).astype(jnp.uint8)
        meta_u8 = _le_bytes(mstream, cell_bytes + 1)
        parts = [total_u8, mask_u8, meta_u8]
        if d_cap:
            # cell-delta stream: deltas of changed rows whose dcount fits
            # the meta field (<= 62), compacted in bitmask row order;
            # overflow rows (sentinel 63) ship via phase B instead
            deltas_all = shard(
                outs[3].reshape(changed.shape[0], -1), None, None
            )
            contrib = changed & (dcounts <= 62)
            dstream, dtotal = _compact_rows(
                deltas_all, jnp.where(contrib, dcounts, 0), d_cap
            )
            dtotal_u8 = jnp.stack(
                [(dtotal >> s) & 0xFF for s in (0, 8, 16, 24)]
            ).astype(jnp.uint8)
            parts += [dtotal_u8, _le_bytes(dstream, cell_bytes + 2)]
        flat = jnp.concatenate(parts)
    return flat, rowbuf, res_dense, res_meta


@partial(
    jax.jit,
    static_argnames=(
        "chunk", "n_chunks", "k_out", "e_cap", "byte_wire", "pack21",
        "mesh", "cell_bytes",
    ),
)
def _fleet_entries(
    res_dense,  # uint8|uint16[cap, C] — the dense resident phase A updated
    rows,  # int32[m_pad] changed table rows (-1 = padding)
    *,
    chunk: int,
    n_chunks: int,
    k_out: int,
    e_cap: int,  # exact-or-larger (host sums changed n_placed): no overflow
    byte_wire: bool,
    pack21: bool = False,
    mesh=None,  # the resident's mesh: gathers cross shards; scans replicate
    cell_bytes: int = 1,
):
    """Phase B: sort-compact ONLY the changed rows' dense vectors into the
    row-major (site << 8 | count) entry stream (site << 16 | count with
    two-byte cells). Runs at the changed-row count, not the table size."""
    cap, c = res_dense.shape
    sb = 8 * cell_bytes
    idxs = jnp.arange(c, dtype=jnp.int32)[None, :]

    def body(carry, i):
        with jax.named_scope("fleet.gather"):
            rc = lax.dynamic_slice_in_dim(rows, i * chunk, chunk, 0)
            vc = rc >= 0
            dense = res_dense[jnp.maximum(rc, 0)].astype(jnp.int32)
            dense = jnp.where(vc[:, None], dense, 0)
        with jax.named_scope("fleet.compact"):
            packed_full = jnp.where(
                dense > 0, (idxs << sb) | dense, jnp.int32(2**31 - 1)
            )
            srt = lax.sort(packed_full, is_stable=False)[:, :k_out]
        return carry, jnp.where(srt == 2**31 - 1, 0, srt)

    _, ents = lax.scan(body, 0, jnp.arange(n_chunks))
    with jax.named_scope("fleet.wire"):
        # replicate before the global compaction scan: the dense resident
        # input is row-sharded on mesh engines, and a sharded cumsum is
        # exactly the CPU-SPMD corruption _fleet_pass's wire guards against
        if mesh is not None:
            ents = lax.with_sharding_constraint(
                ents, NamedSharding(mesh, P())
            )
        entries = ents.reshape(-1, k_out)  # [m_pad, k_out]
        # the sort left each row's placed sites first
        stream, total = _compact_rows(
            entries, (entries > 0).sum(axis=1), e_cap
        )
        if byte_wire:
            total_u8 = jnp.stack(
                [(total >> s) & 0xFF for s in (0, 8, 16, 24)]
            ).astype(jnp.uint8)
            e_u8 = _entry_wire(stream, e_cap, pack21, cell_bytes)
            return jnp.concatenate([total_u8, e_u8])
        return jnp.concatenate([total[None], stream])


def _decode_entry_wire(
    raw2, cap_used: int, byte_wire: bool, pack21: bool, cell_bytes: int = 1
):
    """(total, stream) from a phase-B entry wire buffer."""
    from .. import native

    if byte_wire:
        total2 = native.le32(raw2)
        if pack21:
            stream = native.decode21(raw2[4:], cap_used)
        elif cell_bytes == 1:
            stream = native.decode3(raw2[4:])
        else:
            stream = native.decode4(raw2[4:])
        return total2, stream
    return int(raw2[0]), raw2[1:]


@partial(jax.jit, static_argnames=("chunk", "n_chunks"))
def _fleet_bits(
    cp_bits, cp_static, gvk_bits, prof_table, incomplete_en, rows,
    cp_idx, gvk_idx, prof_idx, replicas, strategy, fresh,
    prev_sites, prev_counts, evict_sites, sel_bits, wide_prev=None, *,
    chunk: int, n_chunks: int,
):
    """Feasibility bitsets as their own lazily-DISPATCHED kernel: only
    Duplicated / zero-replica rows ever read them (their result IS the
    feasible set), and computing + packing them inside every solve pass
    cost a Duplicated-bearing 100k storm ~0.6 s/pass whether or not any
    result was examined. The mask expression is the solve kernels'
    feasibility verbatim; inputs are the pass-time device arrays (JAX
    arrays are immutable, so a batch holding these refs stays consistent
    even after later passes rebuild the live tables)."""
    c = cp_static.shape[1]
    with jax.named_scope("fleet.bits"):
        valid = rows >= 0
        r = jnp.maximum(rows, 0)
        cp = cp_idx[r]
        gv = gvk_idx[r]
        ps = _live_sites(prev_sites[r], valid, wide_prev)
        pc = jnp.where(valid[:, None], prev_counts[r], 0)
        ev = evict_sites[r]
        sb = sel_bits[r]

        def body(carry, i):
            sl = lambda a: lax.dynamic_slice_in_dim(
                a, i * chunk, chunk, axis=0
            )
            cpc, gvc, vc = sl(cp), sl(gv), sl(valid)
            psc, pcc, sbc = sl(ps), sl(pc), sl(sb)
            _, _, feasible = _row_masks(
                cp_bits, cp_static, gvk_bits, incomplete_en, cpc, gvc, psc,
                pcc, sl(ev), vc, sbc, chunk, c, wide_prev,
            )
            pad = (-c) % 32
            f = jnp.pad(feasible, ((0, 0), (0, pad)))
            w32 = f.reshape(chunk, -1, 32).astype(jnp.uint32)
            shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
            return carry, (w32 << shifts).sum(axis=-1, dtype=jnp.uint32)

        _, out = lax.scan(body, 0, jnp.arange(n_chunks))
        return out.reshape(-1, out.shape[-1])


#: rows a chunk of _fleet_select: its [chunk, C, R_CAP] temporaries stay
#: under SELECT_TEMP_BYTES whatever the cluster count
SELECT_CHUNK = 2048
SELECT_TEMP_BYTES = 256 << 20


def _select_chunk(n: int, c: int) -> int:
    by_temp = SELECT_TEMP_BYTES // (4 * R_CAP * max(c, 1))
    by_temp = 1 << max(by_temp, 256).bit_length() - 1
    return min(SELECT_CHUNK, by_temp, _pow2(max(n, 256)))


@partial(jax.jit, static_argnames=("chunk", "n_chunks"))
def _fleet_select(
    cp_bits, cp_static, gvk_bits, prof_table, incomplete_en,
    sp_params,  # int32[U, N_PARAMS] constraint_params a placement slot
    region_of,  # int32[C] region_table of the snapshot
    sub_bits, sub_prefix,  # subset_table(R_CAP)
    rows,  # int32[n_pad] the spread-constrained table rows (-1 = padding)
    cp_idx, gvk_idx, prof_idx, replicas, prev_sites, prev_counts,
    evict_sites, sel_bits, wide_prev=None, *, chunk: int, n_chunks: int,
):
    """The Select stage (SelectClusters) of the batch's spread-constrained
    rows, from the resident row state, written into the resident
    ``sel_bits`` at those rows. Feasibility and availability are the
    pass's own expressions (_row_masks with no selection applied,
    merge_estimates over the resident profile table), so the selection
    ranks on exactly what _fleet_pass will divide on. An empty selection
    is a FitError: the row then has no candidate and _fleet_pass clears
    ``has_cand`` in its meta word. Returns (sel_bits, int32[2]: rows with
    an empty selection, rows whose selection moved)."""
    c = cp_static.shape[1]
    cap, w8 = sel_bits.shape
    with jax.named_scope("fleet.select"):
        valid = rows >= 0
        r = jnp.maximum(rows, 0)
        cp = cp_idx[r]
        gv = gvk_idx[r]
        pf = prof_idx[r]
        reps = jnp.where(valid, replicas[r], 0)
        ps = _live_sites(prev_sites[r], valid, wide_prev)
        pc = jnp.where(valid[:, None], prev_counts[r], 0)
        ev = evict_sites[r]
        old = sel_bits[r]
        unselected = jnp.full((chunk, w8), 0xFF, jnp.uint8)
        weights = jnp.int32(1) << jnp.arange(8, dtype=jnp.int32)

        def body(carry, i):
            sb, fit_errors, moved = carry
            sl = lambda a: lax.dynamic_slice_in_dim(
                a, i * chunk, chunk, axis=0
            )
            cpc, gvc, pfc, repsc, vc = sl(cp), sl(gv), sl(pf), sl(reps), sl(valid)
            prev, _, feasible = _row_masks(
                cp_bits, cp_static, gvk_bits, incomplete_en, cpc, gvc,
                sl(ps), sl(pc), sl(ev), vc, unselected, chunk, c, wide_prev,
            )
            avail = merge_estimates(repsc, (prof_table[pfc],))
            params = sp_params[cpc]
            sel = select_rows(
                feasible, prev, avail, repsc, params, region_of,
                sub_bits, sub_prefix,
            )
            packed = (
                jnp.pad(sel, ((0, 0), (0, w8 * 8 - c)))
                .reshape(chunk, w8, 8)
                .astype(jnp.int32)
                * weights
            ).sum(axis=-1, dtype=jnp.int32).astype(jnp.uint8)
            sb = sb.at[jnp.where(vc, sl(r), cap)].set(packed, mode="drop")
            empty = vc & (params[:, 0] != MODE_NONE) & ~sel.any(axis=1)
            differs = vc & (packed != sl(old)).any(axis=1)
            return (
                sb,
                fit_errors + empty.sum(dtype=jnp.int32),
                moved + differs.sum(dtype=jnp.int32),
            ), None

        (sel_bits, fit_errors, moved), _ = lax.scan(
            body, (sel_bits, jnp.int32(0), jnp.int32(0)),
            jnp.arange(n_chunks),
        )
        return sel_bits, jnp.stack([fit_errors, moved])


#: bytes the [chunk, C] temporaries of one _fleet_terms chunk may take (a
#: handful of int32 grids and T_CAP candidate planes)
TERMS_TEMP_BYTES = 256 << 20


def _terms_chunk(n: int, c: int) -> int:
    by_temp = TERMS_TEMP_BYTES // (4 * (4 + T_CAP) * max(c, 1))
    by_temp = 1 << max(by_temp, 256).bit_length() - 1
    return min(4096, by_temp, _pow2(max(n, 256)))


@partial(jax.jit, static_argnames=("chunk", "n_chunks"))
def _fleet_terms(
    cp_bits, cp_static, gvk_bits, prof_table, incomplete_en,
    rows,  # int32[n_pad] the multi-term table rows (-1 = padding)
    term_slots,  # int32[cap, T_CAP] a row's ordered term slots (-1 = unused)
    term_sel,  # uint8[cap] the index of the term each row was last given
    cp_idx, gvk_idx, prof_idx, replicas, strategy, fresh,
    prev_sites, prev_counts, evict_sites, wide_prev=None,
    *, chunk: int, n_chunks: int,
):
    """Ordered ClusterAffinities on the device: for the batch's multi-term
    rows, the first affinity term whose candidates the divider can place
    the row on (scheduler.go scheduleResourceBindingWithClusterAffinities:
    try a group, on failure the next), from the resident row state. Each
    term's candidate set is the pass's own expression (_row_masks gathered
    at that term's slot, with no selection: a multi-term row rides without
    spread constraints), availability is the pass's (merge_estimates over
    the resident profile table), and the predicate is the host path's
    (_first_fit_group_kernel, in int32: the divider's cohort math; a
    Duplicated or static-weight row fits where a candidate exists). The
    chosen term's slot goes into the resident ``cp_idx`` at those rows and
    its index into ``term_sel``; a row no term fits keeps its LAST live
    term, whose division reports the failure. Returns (cp_idx, term_sel,
    int32[2]: rows whose chosen term is not the first, rows no term
    fits)."""
    c = cp_static.shape[1]
    cap = cp_idx.shape[0]
    with jax.named_scope("fleet.terms"):
        valid = rows >= 0
        r = jnp.maximum(rows, 0)
        unselected = jnp.full((chunk, (c + 7) // 8), 0xFF, jnp.uint8)

        def body(carry, i):
            cp_out, sel_out, fallback, unfit = carry
            sl = lambda a: lax.dynamic_slice_in_dim(
                a, i * chunk, chunk, axis=0
            )
            rc, vc = sl(r), sl(valid)
            ts = term_slots[rc]
            gvc, evc = gvk_idx[rc], evict_sites[rc]
            psc = _live_sites(prev_sites[rc], vc, wide_prev)
            pcc = jnp.where(vc[:, None], prev_counts[rc], 0)
            # a Divided row rides the fleet at replicas <=
            # replicas_bound(C), which never passes MAX_REPLICAS_FAST: the
            # cut changes none of them, and keeps the sums of the other rows
            # (whose predicate is "a candidate exists") inside int32 as well
            reps = jnp.where(
                vc, jnp.minimum(replicas[rc], MAX_REPLICAS_FAST), 0
            )
            st = strategy[rc]
            planes = []
            for t in range(T_CAP):
                slot = ts[:, t]
                prev, _, feasible = _row_masks(
                    cp_bits, cp_static, gvk_bits, incomplete_en,
                    jnp.maximum(slot, 0), gvc, psc, pcc, evc,
                    vc & (slot >= 0), unselected, chunk, c, wide_prev,
                )
                planes.append(feasible)
            avail = merge_estimates(reps, (prof_table[prof_idx[rc]],))
            rank, fit = _first_fit_group_kernel(
                jnp, planes, (ts >= 0).sum(axis=1, dtype=jnp.int32), avail,
                reps, prev, (st == DYNAMIC_WEIGHT) | (st == AGGREGATED),
                fresh[rc] & vc,
            )
            chosen = jnp.take_along_axis(ts, rank[:, None], axis=1)[:, 0]
            at = jnp.where(vc, rc, cap)
            cp_out = cp_out.at[at].set(chosen, mode="drop")
            sel_out = sel_out.at[at].set(rank.astype(jnp.uint8), mode="drop")
            return (
                cp_out, sel_out,
                fallback + (vc & fit & (rank > 0)).sum(dtype=jnp.int32),
                unfit + (vc & ~fit).sum(dtype=jnp.int32),
            ), None

        (cp_idx, term_sel, fallback, unfit), _ = lax.scan(
            body, (cp_idx, term_sel, jnp.int32(0), jnp.int32(0)),
            jnp.arange(n_chunks),
        )
        return cp_idx, term_sel, jnp.stack([fallback, unfit])


@jax.jit
def _fleet_quota(
    prof_reqs,  # int64[P, R] the request vector of each profile slot
    rows,  # int32[n_pad] the batch's table rows in PRESENTED order (-1 = padding)
    ns_idx,  # int32[cap] a row's quota namespace (-1 = not quota'd)
    prev_rest,  # int32[cap] replicas a row holds that prev_counts does not
    prof_idx, replicas, prev_counts,  # the resident row state it reads
):
    """What ops.quota.quota_admit takes of a batch, derived on the device
    from the resident row state, in the batch's PRESENTED order (the
    position in ``rows``: the FIFO order of admission, whatever slots the
    table gave the rows): each position's namespace index, and its delta
    demand ``max(replicas - held, 0)`` times its profile's request vector
    (``held`` = the previous counts the row's columns keep + ``prev_rest``:
    those on members that left the snapshot, and a wide row's whole
    previous result, which its columns do not keep), clamped to DEMAND_CLAMP as
    QuotaSnapshot.demand_row clamps it. Where the product would pass the
    clamp (request > DEMAND_CLAMP // delta) the clamp is taken and the
    product never read, so an absurd-but-legal request times a large delta
    cannot wrap int64 into an admission. A row that asks for nothing (its
    delta is not positive) is not the quota's to deny, as upstream's
    enforcement lets such a delta through: it is handed on as a row without
    a quota (-1), whatever its namespace has left. Returns (int32[n_pad]
    namespace, int64[n_pad, R] demand, int32 the batch's rows in a quota'd
    namespace, those that ask nothing included)."""
    with jax.named_scope("fleet.quota"):
        r = jnp.maximum(rows, 0)
        ns = jnp.where(rows >= 0, ns_idx[r], -1)
        held = prev_counts[r].astype(jnp.int64).sum(axis=1) + prev_rest[r]
        delta = jnp.maximum(replicas[r].astype(jnp.int64) - held, 0)
        req = prof_reqs[prof_idx[r]]
        fits = DEMAND_CLAMP // jnp.maximum(delta, 1)
        demand = jnp.where(
            req > fits[:, None], jnp.int64(DEMAND_CLAMP), req * delta[:, None]
        )
        demand = jnp.where(((ns >= 0) & (delta > 0))[:, None], demand, 0)
        asks = demand.any(axis=1)
        return (
            jnp.where(asks, ns, -1), demand, (ns >= 0).sum(dtype=jnp.int32)
        )


@partial(jax.jit, static_argnames=("cell_bytes",))
def _gather_meta(res_meta, rows, *, cell_bytes: int = 1):
    """Changed-meta fallback when phase A's tuned meta buffer overflows:
    one cheap gather instead of a full-solve rerun."""
    m = jnp.where(rows >= 0, res_meta[jnp.maximum(rows, 0)], 0)
    return _le_bytes(m, cell_bytes + 1)


# row_coupled: the graftlint-dep delta-safety declarations (IR006-
# checked against the traced jaxprs, see tools/graftlint/dep.py). The
# pass/entries kernels compact globally across the resident cap
# axis (coupled); bits/meta are per-row — bits' scan windowing keeps the
# analyzer's verdict 'unproven', so neither is delta_safe yet.
_fleet_pass.row_coupled = True
_fleet_entries.row_coupled = True
_fleet_bits.row_coupled = False
# writes land at ``rows`` and the two counts sum over every row
_fleet_select.row_coupled = True
# the same: the chosen slots land at ``rows``, the counts sum over the rows
_fleet_terms.row_coupled = True
# rows gathered into presented order (data-dependent placement) and one
# count over every row
_fleet_quota.row_coupled = True
_gather_meta.row_coupled = False


#: THE solve-family kernel registry: prewarm's manifest replay
#: (scheduler/prewarm._jit_registry) and the graftlint IR tier's
#: entry-point registry (tools/graftlint/ir.py) both resolve kernels
#: through this mapping, so a kernel added here is automatically
#: replayable at boot and IR-audited in tier-1. prewarm._KERNELS (the
#: jax-free load-time filter) mirrors these names and is asserted against
#: this dict at replay time; graftlint IR004 fails on any drift.
FLEET_KERNELS = {
    "fleet_pass": _fleet_pass,
    "fleet_entries": _fleet_entries,
    "fleet_bits": _fleet_bits,
    "fleet_select": _fleet_select,
    "fleet_terms": _fleet_terms,
    # what admission takes of a batch that rides the table whole (each
    # row's namespace and demand), derived from its row state
    "fleet_quota": _fleet_quota,
    # quota plane (ops.quota): quota_admit dispatched by the table over
    # fleet_quota's outputs, or engine-side over demands the host derives
    # (TensorScheduler's partition of a batch that leaves the table in
    # part); the cap fold engine-side. Registered here so prewarm replay and
    # the graftlint IR tier see them like every other solve-family kernel
    "quota_admit": _quota_admit,
    "quota_cluster_caps": _quota_cluster_caps,
    # provenance plane (ops.explain): the armed-only per-pass "why"
    # dispatch, engine-side like the quota kernels — registered so
    # prewarm replay and the graftlint IR tier audit it with the rest
    "explain_pass": _explain_pass,
    # scarcity plane (ops.preempt): the armed-only plane-wide victim
    # selection, engine-side like quota/explain — same registration
    # contract (prewarm replay + graftlint IR audit)
    "preempt_select": _preempt_select,
}


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------


class _FleetBatch:
    """Shared per-pass outputs (results hold views).

    Entry data lives in the table's persistent host entry array (rows
    updated in place for CHANGED rows only — the delta-fetch base); the
    feasibility bitsets are a lazily-fetched device output. Views are valid
    until the next schedule() pass on the same engine — consumers patch
    results synchronously (scheduler_controller). A generation counter
    captured at construction ENFORCES that window: decoding a result after
    a later pass (or a compaction) has rewritten the mirror raises instead
    of silently yielding another pass's — or another binding's — entries."""

    __slots__ = (
        "names", "host_entries", "rows", "_bits_dev", "_bits_np",
        "_table", "_gen", "_term_sel", "cell_bits",
    )

    def __init__(self, names, host_entries, rows, bits_dev, table, gen,
                 term_sel=None, cell_bits=8):
        self.names = names
        # int32[cap, k_out] (site << cell_bits | count)
        self.host_entries = host_entries
        self.cell_bits = cell_bits
        self.rows = rows  # int32[n] table row per result position
        # device uint32[n_pad, W], a zero-arg thunk that DISPATCHES the
        # bitset kernel over this pass's captured inputs (the lazy form —
        # only Duplicated/zero-replica results ever need it), or None
        self._bits_dev = bits_dev
        self._bits_np = None
        self._table = table
        self._gen = gen
        # the pass-time ``term_sel`` resident (device uint8[cap], immutable;
        # its copy to the host started with the term kernel's dispatch),
        # read by the first multi-term result that names its term
        self._term_sel = term_sel

    def term_of(self, pos: int) -> int:
        """Index of the affinity term the row at ``pos`` was divided on."""
        if not isinstance(self._term_sel, np.ndarray):
            self._term_sel = np.asarray(self._term_sel)
        return int(self._term_sel[self.rows[pos]])

    def entries_for(self, pos: int) -> np.ndarray:
        if self._table is not None and self._table._result_gen != self._gen:
            raise RuntimeError(
                "stale FleetResult: a later schedule() pass (or table "
                "compaction) has rewritten the entry mirror; decode "
                "results before re-scheduling"
            )
        return self.host_entries[self.rows[pos]]

    def _fetch_bits(self) -> None:
        """The lazy feasibility-bitset pass, as a phase of its own: the
        ``_fleet_bits`` dispatch over this pass's captured inputs, the
        fence, the fetch. One ``kernel.bits`` span at its true interval
        (it lies outside ``scheduler.solve``: the first reader of a
        Duplicated or zero-replica row pays it, once a batch)."""
        from ..utils.metrics import kernel_phase_seconds
        from ..utils.tracing import tracer

        t0 = time.perf_counter()
        bits_dev = (
            self._bits_dev() if callable(self._bits_dev) else self._bits_dev
        )
        t1 = time.perf_counter()
        bits_dev.block_until_ready()
        t2 = time.perf_counter()
        # force little-endian word layout before the byte view so the
        # bit positions are host-endianness-independent (the entry
        # stream is decoded with shifts for the same reason)
        self._bits_np = np.ascontiguousarray(
            np.asarray(bits_dev).astype("<u4", copy=False)
        )
        dur = time.perf_counter() - t0
        tracer.record(
            "kernel.bits", dur, start=t0, rows=len(self.rows),
            fetch_mb=self._bits_np.nbytes / 1e6,
            dispatch_s=t1 - t0, device_s=t2 - t1,
        )
        kernel_phase_seconds.observe(dur, phase="bits")

    def feasible_names(self, pos: int) -> tuple:
        if self._bits_np is None:
            self._fetch_bits()
        row = self._bits_np[pos]
        idx = np.nonzero(
            np.unpackbits(row.view(np.uint8), bitorder="little")
        )[0]
        names = self.names
        return tuple(names[j] for j in idx if j < len(names))


class FleetResult:
    """Lazy ScheduleResult-compatible view over a fleet batch.

    `clusters`/`feasible` materialize on first access: the scheduling data
    already sits in host numpy arrays; building 100k Python dicts eagerly
    would cost more than the whole device pass."""

    __slots__ = (
        "key", "affinity_name", "error",
        "_batch", "_pos", "_n", "_dup_replicas", "_zero",
        "_clusters", "_feasible",
    )

    def __init__(self, key, affinity_name, error, batch, pos, n,
                 dup_replicas, zero):
        self.key = key
        self.affinity_name = affinity_name
        self.error = error
        self._batch = batch
        self._pos = pos
        self._n = n
        self._dup_replicas = dup_replicas  # Duplicated row: count everywhere
        self._zero = zero  # zero-replica (non-workload) row
        self._clusters = None
        self._feasible = None

    @property
    def success(self) -> bool:
        return not self.error

    @property
    def clusters(self) -> dict:
        if self._clusters is None:
            if not self.success:
                self._clusters = {}
            elif self._dup_replicas is not None:
                self._clusters = {
                    n: self._dup_replicas
                    for n in self._batch.feasible_names(self._pos)
                }
            else:
                b = self._batch
                names = b.names
                sb = b.cell_bits
                mask = (1 << sb) - 1
                self._clusters = {
                    names[int(e) >> sb]: int(e) & mask
                    for e in b.entries_for(self._pos)[: self._n]
                }
        return self._clusters

    @property
    def feasible(self) -> tuple:
        if self._feasible is None:
            self._feasible = (
                self._batch.feasible_names(self._pos)
                if (self._zero and self.success)
                else ()
            )
        return self._feasible


class _QuotaVerdict:
    """What one quota admission left of a batch: whether each position was
    denied, from quota_admit's answer (its copy to the host started at the
    dispatch; read at the first denied-or-not question); with it whose
    verdict this is (the batch's row vector and the quota generation), so a
    pass over the same rows at the same generation replays it."""

    __slots__ = (
        "rows_np", "generation", "n", "_denied", "quota_rows", "denied_rows",
    )

    def __init__(self, rows_np, generation, n, denied):
        self.rows_np = rows_np
        self.generation = generation
        self.n = n
        # bool[n] denied, or until its first read the kernel's admitted
        # bool[n_pad] on the device
        self._denied = denied
        # the batch's rows in a quota'd namespace, and those denied: set
        # when the pass that dispatched the admission ends
        self.quota_rows = self.denied_rows = 0

    def denied(self) -> np.ndarray:
        """bool[n]: whether the quota denied the row at each position."""
        d = self._denied
        if not isinstance(d, np.ndarray):
            d = self._denied = ~np.asarray(d)[: self.n]
        return d


class _FleetResultList:
    """Column-oriented result container: the scheduling data lives in the
    fetched numpy arrays; per-binding `FleetResult` views materialize on
    access (and are cached for identity stability). Building 100k Python
    objects eagerly would cost more host time than the whole device pass —
    consumers that iterate pay the same total, but batch callers that
    sample (bench verification, partial write-backs) don't pay for rows
    they never touch. Where the pass admitted the batch against a quota,
    ``quota`` holds the verdict: a denied row answers a plain
    ScheduleResult carrying QUOTA_EXCEEDED_ERROR and no placement (what
    the pass divided for it is never read)."""

    __slots__ = (
        "_problems", "_terms", "_batches", "_slice_rows", "_n_placed",
        "_unsched", "_has_cand", "_is_dup", "_cache", "quota", "_denied",
    )

    def __init__(self, problems, terms, batches, slice_rows, n_placed,
                 unsched, has_cand, is_dup):
        self.quota: Optional[_QuotaVerdict] = None
        self._denied: Optional[np.ndarray] = None
        self._problems = problems
        self._terms = terms
        self._batches = batches
        self._slice_rows = slice_rows
        self._n_placed = n_placed
        self._unsched = unsched
        self._has_cand = has_cand
        self._is_dup = is_dup
        self._cache: dict[int, FleetResult] = {}

    def __len__(self) -> int:
        return len(self._problems)

    def _make(self, i: int) -> FleetResult:
        res = self._cache.get(i)
        if res is not None:
            return res
        p = self._problems[i]
        if self.quota is not None:
            if self._denied is None:
                self._denied = self.quota.denied()
            if self._denied[i]:
                from .core import ScheduleResult
                from .quota import QUOTA_EXCEEDED_ERROR

                res = self._cache[i] = ScheduleResult(
                    key=p.key, error=QUOTA_EXCEEDED_ERROR
                )
                return res
        if not self._has_cand[i]:
            err = "no clusters fit the placement"
        elif self._unsched[i]:
            err = "clusters available replicas are not enough"
        else:
            err = ""
        dup = (
            p.replicas
            if (self._is_dup[i] and p.replicas > 0 and not err)
            else None
        )
        batch, pos = self._batches[i // self._slice_rows], i % self._slice_rows
        # a single-term row's name is a constant; a multi-term row holds its
        # terms' names and reads which one the term kernel chose
        name = self._terms[i]
        if name.__class__ is tuple:
            name = name[batch.term_of(pos)]
        res = FleetResult(
            p.key, name, err, batch, pos,
            int(self._n_placed[i]), dup, p.replicas == 0,
        )
        self._cache[i] = res
        return res

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._make(j) for j in range(*i.indices(len(self)))]
        if i < 0:
            i += len(self)
        if not 0 <= i < len(self):
            raise IndexError(i)
        return self._make(i)

    def __iter__(self):
        for i in range(len(self)):
            yield self._make(i)


# --------------------------------------------------------------------------
# the table
# --------------------------------------------------------------------------

_STATE_FIELDS = (
    "cp_idx", "gvk_idx", "prof_idx", "replicas", "strategy", "fresh",
    "prev_sites", "prev_counts", "evict_sites", "sel_bits",
)


#: the row state _fleet_select reads (``strategy`` folds into the placement's
#: constraint parameters, ``fresh`` bears on the division alone)
_SELECT_STATE = tuple(
    _STATE_FIELDS.index(k) for k in _STATE_FIELDS
    if k not in ("strategy", "fresh")
)


#: the row state _fleet_quota reads (beside the two quota columns)
_QUOTA_STATE = tuple(
    _STATE_FIELDS.index(k) for k in ("prof_idx", "replicas", "prev_counts")
)


@jax.jit
def _scatter_rows(state, rows, vals):
    return tuple(a.at[rows].set(v) for a, v in zip(state, vals))


# data-dependent row placement: writes land at ``rows``, so one update
# moves another slot's data — cross-row by construction (IR006-proven)
_scatter_rows.row_coupled = True


@jax.jit
def _fold_estimates(table, answers, n_live):
    """Min-merge the estimators' by-profile answers into the profile table,
    cell by cell with merge_estimates' -1 rule: an answer of -1 keeps the
    table's cell, a table cell of -1 takes the answer, else the minimum.
    Rows from ``n_live`` on (the pow2 padding) stay as they are."""
    live = jnp.arange(table.shape[0], dtype=jnp.int32)[:, None] < n_live
    out = table
    for est in answers:
        merged = jnp.where(out < 0, est, jnp.minimum(out, est))
        out = jnp.where((est < 0) | ~live, out, merged)
    return out


class _BatchDerived(NamedTuple):
    """What a pass derives from the row state of a batch's rows, kept with
    the resident batch (FleetTable._batch_derived): the same rows unpacked
    derive the same values, whatever the snapshot."""

    rows_np: np.ndarray  # the batch's table rows (identity: whose they are)
    # affinity term name(s) by position; the result list of every pass over
    # the record holds this list, so it is never written in place
    terms: list
    max_n: int  # the largest ``replicas``
    max_prev: int  # the largest previous count (a wide row's slot included)
    # rows in a wide slot or Divided past a one-byte cell: the rows only the
    # wide form carries
    wide_rows: int
    has_agg: bool  # a row divides Aggregated
    is_dup: np.ndarray  # bool[n]: the row is Duplicated
    need_bits: bool  # a row answers by its feasibility bitset
    is_all: bool  # the rows are the whole table in order (all-rows storm)


class _TermRows(NamedTuple):
    """The multi-term rows of a batch, as _fleet_terms takes them."""

    rows_np: np.ndarray  # the batch's table rows (identity: whose they are)
    rows_dev: jax.Array  # int32[chunk * n_chunks] multi-term rows, -1 padded
    n: int
    chunk: int
    n_chunks: int
    evicted: int  # rows of the batch that hold an eviction task


class _SelectRows(NamedTuple):
    """The rows of a batch that _fleet_select selects, as dispatched."""

    select: np.ndarray  # positions in the batch
    rows_np: np.ndarray  # the batch's table rows those positions index
    rows_dev: jax.Array  # int32[chunk * n_chunks] table rows, -1 padded
    n: int
    chunk: int
    n_chunks: int


def _few(moved: int, n: int) -> bool:
    """THE majority rule: at most half of a batch's ``n`` positions moved,
    so visiting (or dispatching) them alone beats the walk."""
    return moved * 2 <= n


class _Diff(NamedTuple):
    """A batch diffed against the armed one (ResidentBatch.diff)."""

    ids: np.ndarray  # int64[n]: id() of every position's object
    # sorted positions whose object moved or whose key the caller named
    # dirty: what the table visits
    moved: np.ndarray
    # at most half of the positions moved (_few): the prologue visits
    # them alone
    few: bool
    # nothing moved, no key was named dirty and the armed compiled list
    # stands: the identity route
    hit: bool
    took: float  # the seconds the scheduler.identity record spans


class ResidentBatch:
    """The batch the fleet table last scheduled: ONE record of it, which
    the table owns and the engine reads. Its lists pin every position's
    objects (so no id() in it is given out again), ``rows_np`` holds each
    position's table row, and what the passes derive from those rows hangs
    off it (``derived``, ``terms``, ``select_rows``): they go with the
    record when a pass replaces it, unless the replacement keeps the same
    row vector. A compaction or a growth remaps the rows: the record loses
    its rows and what hangs off them in place (``rows_np`` None), keeps its
    lists and armed state, and the next pass walks the batch.

    The engine arms a record once a whole batch of its rode the table
    (``arm``); an armed record is what a later batch is diffed against
    (``diff``). ``gen`` and ``token`` are the engine's snapshot generation
    and ``mask_token`` the engine armed it at (``token`` None where rows of
    the batch hold the host's selections: they follow the capacities, so
    the record stands only at its own generation); ``placements`` the
    armed batch by placement: (distinct placements, each one's flags,
    intp[n] position -> placement), None until a diff needs it."""

    __slots__ = (
        "problems", "compiled", "rows_np", "select", "unique", "gen",
        "token", "live", "epoch", "ids", "placements", "key_pos",
        "derived", "terms", "select_rows",
    )

    def __init__(self, problems, compiled, rows_np, select, unique, live,
                 epoch):
        self.problems = problems
        self.compiled = compiled
        self.rows_np: Optional[np.ndarray] = rows_np
        # positions whose SelectClusters stage runs on the device, or None
        self.select = select
        self.unique = unique  # no table row comes twice in the batch
        # the pass the rows were last live at: it stands in for the
        # last-used stamps of the rows no pass walked since (_compact)
        self.live = live
        # the mirror epoch that covers the rows' answers (-1: none)
        self.epoch = epoch
        self.ids: Optional[np.ndarray] = None  # None: not armed
        self.gen = self.token = None
        self.placements: Optional[tuple] = None
        self.key_pos: Optional[dict] = None  # key -> position, built lazily
        self.derived: Optional[_BatchDerived] = None
        self.terms: Optional[_TermRows] = None
        self.select_rows: Optional[_SelectRows] = None

    @property
    def armed(self) -> bool:
        return self.ids is not None

    def arm(self, ids: np.ndarray, placements: Optional[tuple], gen,
            token) -> None:
        """Let the engine diff a later batch against this one: ``ids`` is
        id() of every position's object, ``placements`` the batch by
        placement where the pass built it, ``gen`` and ``token`` as the
        class says."""
        self.ids, self.placements = ids, placements
        self.gen, self.token = gen, token

    def unmap(self) -> None:
        """The rows were remapped (a compaction, a growth): none of the
        record's rows, nor what hangs off them, stands."""
        self.rows_np = self.derived = self.terms = self.select_rows = None
        self.epoch = -1

    def diff(self, problems: Sequence, dirty_keys, stands: bool) -> _Diff:
        """THE diff of a batch of the armed batch's length against it: one
        id() sweep, the positions whose object is another, and the
        positions of the caller's ``dirty_keys`` (advisory: a key the
        batch does not hold maps nowhere; a row that truly changed shows
        in the sweep as well), and whether at most half of them moved (the
        majority rule). Records ``scheduler.identity`` (``hit``: no
        position moved, no key was named and the armed compiled list
        ``stands``, the identity route), the one place that does."""
        from ..utils.tracing import tracer

        t0 = time.perf_counter()
        n = len(problems)
        ids = np.fromiter(map(id, problems), np.int64, n)
        moved = np.flatnonzero(ids != self.ids)
        if dirty_keys:
            kp = self.key_pos
            if kp is None:
                kp = self.key_pos = {
                    p.key: i for i, p in enumerate(self.problems)
                }
            extra = [kp[k] for k in dirty_keys if k in kp]
            if extra:
                moved = np.union1d(moved, np.asarray(extra, np.int64))
        k = int(moved.size)
        hit = stands and k == 0 and not dirty_keys
        took = time.perf_counter() - t0
        tracer.record(
            "scheduler.identity", took, start=t0,
            rows=n, hit=int(hit), moved=k,
        )
        return _Diff(ids, moved, _few(k, n), hit, took)


def _distinct(rows: np.ndarray, n_rows: int) -> bool:
    """Whether no table row comes twice in ``rows``."""
    seen = np.zeros(n_rows, bool)
    seen[rows] = True
    return int(seen.sum()) == len(rows)


class FleetTable:
    """Device-resident binding table bound to one TensorScheduler."""

    def __init__(self, engine):
        self.engine = engine
        # floor to a power of two (>= 256): the dense wire packs the
        # changed bitmask in 32-bit words and phase B divides the meta
        # buffer by the chunk, so n_pad must stay pow2-aligned — the
        # engine's chunk_size is a perf knob, not a semantic one
        self.chunk = 1 << max(engine.chunk_size, 256).bit_length() - 1
        # engine-level mesh, validated ONCE against the table's quanta:
        # chunk/cap/n_pad are all pow2 (>= 256), so any pow2 "b" extent
        # up to the chunk divides every bucket this table will ever pad
        # to — the mesh-divisible-bucket contract. A non-pow2 or oversized
        # extent falls back to single-device for the whole table (loudly:
        # silently dropping chips would fake a scaling number).
        mesh = getattr(engine, "mesh", None)
        if mesh is not None:
            b_sz = mesh.shape.get("b", 1)
            if b_sz & (b_sz - 1) or b_sz > self.chunk:
                log.warning(
                    "fleet mesh disabled: binding axis %d is not a power "
                    "of two dividing the %d-row chunk quantum; the solve "
                    "runs single-device", b_sz, self.chunk,
                )
                mesh = None
        self._mesh = mesh
        self.cap = 0
        self.n_rows = 0
        self._key_row: dict[str, int] = {}
        # the object each row's state was packed from, or one of equal
        # content that came after it: what upsert compares a newcomer with
        self._problems: list = []
        self._terms: list = []  # affinity term name per row
        self._pass = 0
        # interning slots: one a (placement, affinity term), keyed by the
        # Placement object (pinned below, so its id() is never reused) and
        # the term's index. A compiled placement is a pure function of
        # (placement, snapshot): the engine may compile the same placement
        # again (a mask-token move clears its cache) and the slot stands
        self._cp_slot: dict[tuple, int] = {}
        self._cp_pl: list = []  # slot -> (placement, compiled, term) pinned
        self._cp_uploaded = 0  # slots currently valid on the device table
        self._cp_remapped = False  # slot ids changed: full upload needed
        self._gvk_slot: dict[str, int] = {}
        self._gvk_list: list[str] = []
        self._prof_slot: dict[bytes, int] = {}
        self._profiles: list[np.ndarray] = []
        # cap-namespace id per interned profile (-1 = uncapped): profiles
        # of bindings in namespaces with static-assignment quotas intern
        # per (request vector, cap ns) so the profile table row carries
        # the cap-folded availability — the quota ceiling reaches the
        # divide kernel with no kernel-signature change. Stable for the
        # table's lifetime: the engine drops the table on cap changes.
        self._prof_ns: list[int] = []
        self._prof_capped = False  # a slot of a capped namespace exists
        self._caps_fold: Optional[tuple] = None
        # requests-tuple -> profile slot memo over _prof_slot: skips the
        # per-row dim-vector build (zeros + dim_index loop + tobytes) that
        # dominates bulk onboarding (a restart's first wave packs EVERY
        # row). Keyed per snapshot object — dims can change across swaps
        self._req_slot: dict[tuple, int] = {}
        self._req_slot_snap = None
        # host staging
        self._st: dict[str, np.ndarray] = {}
        # device
        self._dev_state: Optional[tuple] = None
        self._dev_tables: Optional[tuple] = None
        # what _fleet_select reads beside the slot tables: (constraint
        # parameters a placement slot, the snapshot's region table), built
        # with them; None where the snapshot holds more than R_CAP regions
        self._dev_spread: Optional[tuple] = None
        self._dev_subsets: Optional[tuple] = None  # subset_table, uploaded once
        # the last select dispatch's (perf_counter start, end, rows,
        # device counts), until schedule() records its span
        self._select_mark: Optional[tuple] = None
        # the rows' ordered term slots and the term each was last given
        # (device int32[cap, T_CAP] / uint8[cap]); the last term
        # dispatch's (start, end, rows, device counts, evicted rows) until
        # schedule() records its span
        self._dev_term_slots = None
        self._dev_term_sel = None
        self._terms_mark: Optional[tuple] = None
        # whether the current pass kept or built what it derives from its
        # batch's row state (the record's ``derived``)
        self._derived_outcome = "kept"
        # quota admission from row state (_admit_on_device). The staging's
        # ``ns_idx`` column is derived from ONE QuotaSnapshot.ns_index,
        # kept here (None = no quota set: the column is not kept up).
        # On the device: (ns_idx, prev_rest) beside the state the pass
        # reads, with the rows packed since their upload; the profile
        # slots' request vectors, by how many they were. The last verdict
        # (replayed while rows and generation stand), the quota the
        # current pass admits against (None: it admits nothing), what
        # schedule() records of the admission, and the admitted demand the
        # engine has yet to debit
        self._ns_src: Optional[dict] = None
        self._dev_quota: Optional[tuple] = None
        self._quota_dirty: set[int] = set()
        self._dev_prof_reqs: Optional[tuple] = None
        self._quota_verdict: Optional[_QuotaVerdict] = None
        self._quota_pass = None
        self._quota_mark: Optional[tuple] = None
        self.quota_debit: Optional[np.ndarray] = None
        self._all_rows_dev = None
        self._all_rows_n = -1
        # the wide form, engaged from what the rows observe: the bytes of a
        # dense resident cell (2 from the first Divided row past
        # NARROW_CELL_MAX), and the wide table (host int32[W, C], W a
        # power of two; None until a row holds more than K_PREV previous
        # sites) with its free slots, the slots handed out so far, the
        # slots written since the upload, and its device copy
        self._cell_bytes = 1
        self._wide_prev: Optional[np.ndarray] = None
        self._wide_free: list[int] = []
        self._wide_n = 0
        self._wide_dirty: set[int] = set()
        self._dev_wide = None
        self._dirty: set[int] = set()
        self._tables_dirty = True
        self._avail_max = 0
        # extra-estimator fold (_fold_extra_estimates): the table it starts
        # from, the estimators' refresh tokens at the last fold, and the
        # fold's stretch inside the current pass
        self._prof_base: Optional[tuple] = None
        self._folded_ests: tuple = ()
        self._folded_tokens: Optional[tuple] = None
        self._est_window: Optional[tuple] = None
        self._static_max = 0
        self._snapshot_gen = getattr(engine, "_snapshot_gen", 0)
        # the last pass's changed-entry total: sizes the speculative phase
        # B's entry buffer (a miss falls back to the exact fetch)
        self._last_total: Optional[int] = None  # None = no pass observed yet
        # host mirror of every row's entry vector ([cap, k_res]): what
        # results read from, folded from each pass's changed rows. None =
        # next pass reports every row changed and refills it.
        self._host_entries: Optional[np.ndarray] = None
        self._k_res = 1  # running max entry width (grow-only)
        # mesh layout (canonical shape tuple) the residents were born on:
        # rides every resident-bearing trace key, and a layout change
        # reallocates the residents (next pass re-reports every row)
        self._resident_mesh = None
        # two-phase dense path (see _fleet_pass/_fleet_entries): the dense
        # assignment + meta words live on device; _host_meta mirrors the
        # meta resident so results decode without a full per-pass fetch
        self._res_dense = None  # uint8[cap, C] device
        self._res_meta = None  # int32[cap] device
        self._host_meta: Optional[np.ndarray] = None
        self._m_cap_cur: Optional[int] = None
        self._last_changed: Optional[int] = None
        # cell-delta wire (phase A tail): tuned like m_cap; _delta_live
        # records that the last churn pass folded via deltas, which turns
        # the speculative full-row phase B dispatch off (wasted device
        # sort + wire when deltas carry the pass)
        self._d_cap_cur: Optional[int] = None
        self._last_dtotal: Optional[int] = None
        self._delta_live = False
        # (target, consecutive passes desired) for a frozen shrink — see
        # the cap tuning in _solve_dense
        self._shrink_desire: tuple = (None, 0)
        # the batch the table last scheduled (ResidentBatch): a pass that
        # brings the same list objects again reuses its rows
        self.batch: Optional[ResidentBatch] = None
        # whether the last pass replayed its untouched rows from the
        # mirrors (_schedule_delta), or dispatched every row
        self.replayed = False
        # mirror staleness fence for the replay: bumps whenever a
        # resident/mirror pair is (re)allocated zeroed; the record keeps
        # the epoch whose mirrors cover its rows (the end of every full
        # pass), and a delta replays its untouched rows only while the
        # two agree
        self._mirror_epoch = 0
        # bumped whenever _host_entries is rewritten (each pass, and on
        # compaction remaps); _FleetBatch captures it so stale result
        # views fail loudly instead of decoding another pass's entries
        self._result_gen = 0
        # per-phase wall times of the last pass (bench breakdown surface)
        self.last_breakdown: dict[str, float] = {}
        # (breakdown key, start, end) perf_counter stamps of the current
        # pass's timed phases, in order: what the phase spans are placed
        # from (_phase / _emit_phase_spans)
        self._phase_marks: list[tuple] = []
        # (route, start, end, rows, entries, fetched bytes) of each phase-B
        # stretch of the current pass, in order: the kernel.entries spans
        self._entry_marks: list[tuple] = []
        # rows (re)packed by the current pass (_pack_rows adds): the
        # packed-vs-replayed split the history ring records per wave; and
        # the positions its upsert phase looked at (0 on the identity path)
        self._packed_this_pass = 0
        self._visited_this_pass = 0
        # placement slots added since the last publish (_pack_rows
        # increments; schedule() counts them and stamps its span)
        self._slots_minted_this_pass = 0
        from ..utils.metrics import (
            fleet_batch_derived,
            fleet_changed_rows,
            fleet_delta_cells,
            fleet_entry_speculations,
            fleet_upsert_rows,
            fleet_wide_rows,
        )

        # what the upsert phase made of the rows of each pass, added once a
        # pass: (same, equal, packed)
        self._upsert_tally = tuple(
            fleet_upsert_rows.labels(outcome=o)
            for o in ("same", "equal", "packed")
        )
        # the rows of each pass in the wide form, added once a pass
        self._wide_tally = fleet_wide_rows.labels()
        # whether each pass kept or built what it derives from its batch's
        # row state, added once a pass
        self._derived_tally = {
            o: fleet_batch_derived.labels(outcome=o) for o in ("kept", "built")
        }
        # the wire home of each dispatched pass, added once a pass: changed
        # rows by route (delta, entries), the cells the delta wire carried,
        # and what became of a speculative phase B
        self._wire_tally = tuple(
            fleet_changed_rows.labels(route=r) for r in ("delta", "entries")
        )
        self._cells_tally = fleet_delta_cells.labels()
        self._spec_tally = {
            o: fleet_entry_speculations.labels(outcome=o)
            for o in ("used", "drained")
        }
        # host->device bytes of the current pass (state upload/scatter +
        # row indices), reset by _sync_device; surfaces as upload_mb
        self._last_upload_bytes = 0
        # trace-signature ledger: every distinct static-arg combination we
        # dispatch is one XLA trace. Warmup loops poll
        # ``new_trace_last_pass`` until a pass introduces no unseen
        # signature, so timed windows only ever run already-compiled traces.
        self._seen_traces: set = set()
        self.new_trace_last_pass = False
        # durable ledger (scheduler.prewarm): fresh solve-family traces are
        # persisted with their full compile inputs so a future process can
        # AOT-prewarm them before its first pass. Seeding the in-memory
        # ledger from the manifest is gated on the manifest having been
        # REPLAYED in this process (prewarm.warmup) — otherwise the first
        # pass would claim new_trace=False while a compile still runs.
        from .prewarm import prewarm_on_rebuild

        # the engine resolved its manifest once at construction (including
        # the env-default fallback); re-resolving None here would resurrect
        # an inherited KARMADA_TPU_TRACE_MANIFEST after an explicit
        # trace_manifest="" opt-out
        self._manifest = getattr(engine, "trace_manifest", None)
        if self._manifest is not None:
            # warmed_keys() is empty before replay, and excludes records
            # whose compile FAILED during replay — those traces would
            # still compile at first dispatch, so seeding them would fake
            # a warm pass
            self._seen_traces |= self._manifest.warmed_keys()
        prewarm_on_rebuild(self._manifest)

    @property
    def shrink_pending(self) -> bool:
        """A sustained-shrink desire is accumulating: within SHRINK_SUSTAIN
        passes a smaller cap pair may compile a fresh trace. Bench warm
        loops poll this alongside ``new_trace_last_pass`` — breaking warmup
        while a desire is pending parks the compile inside the timed
        window (an 18s dispatch stall on the 1M tier)."""
        return bool(self._shrink_desire[1])

    def exhaustion_summary(self) -> str:
        """One line of WHY this table reports slots_exhausted — printed by
        the engine before a rebuild (a rebuild costs a full repack +
        re-trace; the slot-rotation bench observed one with the slot count
        apparently under the cap, and this breadcrumb is how the next
        occurrence gets root-caused)."""
        return (
            f"slots={len(self._cp_pl)} max={self._max_slots()} "
            f"gvk={len(self._gvk_list)} profiles={len(self._profiles)} "
            f"rows={self.n_rows} cap={self.cap}"
        )

    def _mark_trace(self, *key) -> bool:
        """Record a dispatched trace signature; flips the per-pass
        new-trace flag when the signature is unseen (a compile will run).
        Returns True for a fresh signature so dispatch sites can persist
        the compile record to the trace manifest. Every fresh signature
        also feeds the per-bucket compile counter — the metric face of
        the compile-lifecycle subsystem (manifest-seeded signatures never
        pass through here, so prewarmed traces don't count as serving-
        path compiles)."""
        if key not in self._seen_traces:
            self._seen_traces.add(key)
            self.new_trace_last_pass = True
            from ..utils.metrics import kernel_compiles

            bucket = "x".join(
                str(v) for v in key[1:] if isinstance(v, (int, bool))
            )[:64]
            kernel_compiles.inc(
                kernel=_TRACE_KERNELS.get(key[0], str(key[0])),
                bucket=bucket,
            )
            return True
        return False

    def _record_trace(self, kernel: str, key, arrays, **statics) -> None:
        """Persist a fresh trace's compile inputs (shapes + statics) to
        the manifest. A meshed dispatch records its mesh as the canonical
        SHAPE tuple (parallel.mesh.mesh_shape) — the Mesh object is not
        serializable but its shape is the compile identity, and replay
        rebuilds a live mesh over the booting process's devices (a boot
        that cannot host the recorded shape counts the record failed and
        never seeds the ledger from it). Best-effort: manifest failures
        must never reach the scheduling path."""
        if self._manifest is None:
            return
        if statics.get("mesh") is not None:
            from ..parallel.mesh import mesh_shape

            statics = {**statics, "mesh": mesh_shape(statics["mesh"])}
        try:
            self._manifest.record(kernel, key, arrays, statics)
        except Exception as exc:  # noqa: BLE001 — manifest failures must
            # never abort a scheduling wave (durability is optional, the
            # placement is not) — but they are LOGGED, never swallowed:
            # an unrecorded trace costs the NEXT boot a full compile.
            # Class name only at warning (orchestrators scrape merged
            # stdout/stderr for JSON lines; reprs can be multi-line)
            log.warning(
                "trace manifest record of %s failed (%s); next boot "
                "re-compiles this trace", kernel, type(exc).__name__,
            )
            log.debug("manifest record failure detail", exc_info=exc)

    # -- rows --------------------------------------------------------------

    COMPACT_IDLE_PASSES = 4  # rows unused this many passes are evictable

    def _compact(self) -> bool:
        """Drop rows whose keys haven't been scheduled recently (deleted
        bindings leave stale rows behind — without eviction a create/delete
        churn workload grows the table and its pinned problems without
        bound). Returns True if at least half the rows were reclaimed."""
        if not self.n_rows:
            return False
        cutoff = self._pass - self.COMPACT_IDLE_PASSES
        lu = self._st["last_used"][: self.n_rows]
        rec = self.batch
        if rec is not None and rec.rows_np is not None:
            # the resident batch's rows are stamped where a pass walked
            # them; the passes that did not were live at ``rec.live``
            lu[rec.rows_np] = rec.live
        keep = np.flatnonzero(lu >= cutoff).tolist()
        if len(keep) * 2 > self.n_rows:
            return False
        for k in ("_problems", "_terms"):
            setattr(self, k, [getattr(self, k)[r] for r in keep])
        idx = np.asarray(keep, np.int64)
        for name, arr in self._st.items():
            arr[: len(keep)] = arr[idx]
        # the rows past the kept ones name no wide slot (a row taken there
        # later must not free one a kept row holds); the slots only the
        # dropped rows held are free again
        self._st["prev_sites"][len(keep) : self.n_rows] = 0
        if self._wide_prev is not None:
            first = self._st["prev_sites"][: len(keep), 0]
            held = set((-1 - first[first < 0]).tolist())
            self._wide_free = [
                s for s in range(self._wide_n - 1, -1, -1) if s not in held
            ]
        self._key_row = {p.key: i for i, p in enumerate(self._problems)}
        self.n_rows = len(keep)
        self._dirty.clear()
        self._dev_state = None  # full re-upload with the compacted layout
        self._dev_term_sel = None
        self._dev_quota = self._quota_verdict = None
        self._all_rows_n = -1
        # row ids were remapped: the delta base is meaningless now, and so
        # is any result view still pointing at the old row layout
        self._reset_dense()
        self._unmap_batch()
        self._result_gen += 1
        return True

    def _reset_dense(self) -> None:
        """Invalidate the residents (row remap / growth). The next pass
        reallocates zeroed residents and a zeroed host meta mirror — a
        consistent pair, so every row whose current result is nonzero
        re-reports as changed and refills the mirrors. The host ENTRY
        mirror must reset with them: after a row remap its runs belong to
        other bindings, and the cell-delta fold MERGES into existing runs
        (a full-row phase-B fold rewrites rows wholesale and would mask
        the staleness, but a delta-carried pass diffing against zeroed
        residents emits insert-only deltas — merged into a stale run,
        stale sites would survive)."""
        self._res_dense = None
        self._res_meta = None
        self._host_meta = None
        self._host_entries = None
        self._mirror_epoch += 1

    def _grow(self, need: int) -> None:
        new_cap = max(self.chunk, _pow2(need))
        # an input check, not a fallback: refused before anything is
        # allocated at the new cap (the table keeps its rows and its cap)
        c = self.engine.snapshot.num_clusters
        if new_cap * c > DENSE_RESIDENT_MAX_BYTES:
            raise FleetTableTooLarge(
                f"fleet table of {new_cap} rows x {c} clusters needs a "
                f"dense resident of {new_cap * c} bytes; the bound is "
                f"{DENSE_RESIDENT_MAX_BYTES} (DENSE_RESIDENT_MAX_BYTES)"
            )
        st = {
            "cp_idx": np.zeros(new_cap, np.int32),
            "gvk_idx": np.zeros(new_cap, np.int32),
            "prof_idx": np.zeros(new_cap, np.int32),
            "replicas": np.zeros(new_cap, np.int32),
            "strategy": np.zeros(new_cap, np.int32),
            "fresh": np.zeros(new_cap, bool),
            "prev_sites": np.zeros((new_cap, K_PREV), np.int32),
            "prev_counts": np.zeros((new_cap, K_PREV), np.int32),
            # a row's graceful-eviction tasks as cluster indices and its
            # ordered term slots (host staging of the term kernel's input;
            # uploaded beside the state, never read by the pass)
            "evict_sites": np.full((new_cap, K_EVICT), -1, np.int32),
            "term_slots": np.full((new_cap, T_CAP), -1, np.int32),
            # a row's spread selection, bitpacked as the cp planes are;
            # all ones = no selection narrows this row
            "sel_bits": np.full((new_cap, (c + 7) // 8), 0xFF, np.uint8),
            # rows whose resident sel_bits is _fleet_select's, not this
            # mirror's (host bookkeeping, never uploaded)
            "sel_on_dev": np.zeros(new_cap, bool),
            # the last pass that walked the row (host bookkeeping too:
            # what _compact reads)
            "last_used": np.zeros(new_cap, np.int64),
            # quota admission's row state, uploaded BESIDE the state the
            # pass reads (_admit_on_device): the row's namespace index in
            # the quota snapshot (-1 = not quota'd) and the replicas it
            # holds that prev_counts does not keep: on members the snapshot
            # does not name, and a wide row's whole previous result (the
            # demand counts them as held)
            "ns_idx": np.full(new_cap, -1, np.int32),
            "prev_rest": np.zeros(new_cap, np.int32),
        }
        for k, a in self._st.items():
            st[k][: self.cap] = a
        self._st = st
        self.cap = new_cap
        self._dev_state = None  # full re-upload
        self._dev_term_sel = None
        self._dev_quota = self._quota_verdict = None
        self._reset_dense()  # cap changed: residents reallocate zeroed
        self._unmap_batch()

    def _unmap_batch(self) -> None:
        """The rows were remapped (a compaction, a growth): the resident
        batch keeps its lists and armed state, and the next pass walks it."""
        if self.batch is not None:
            self.batch.unmap()

    def upsert(
        self, problems: Sequence, compiled: Sequence,
        moved: Optional[np.ndarray] = None,
    ) -> tuple:
        """The batch's rows (int32, position by position), its rows brought
        to the batch's content, and whether no row comes twice in it.

        ``moved`` (sorted positions, from the engine's diff against the
        resident batch, ResidentBatch.diff) says which positions hold
        another object than the resident batch does: only those are
        visited, where the resident batch has rows of the same length and
        each moved position's key sits at the row its position had. Every
        other batch is walked position by position. ``_visited_this_pass``
        counts the positions looked at. A visited position whose row holds
        the same object, or one of equal content (compared field by field
        with the object the row holds, which then gives way to the
        newcomer), keeps its row state; a new key takes a row; the rest are
        packed together (_pack_rows). Problem objects are not mutated in
        place between passes: the identity paths here and in the engine
        rest on that."""
        n = len(problems)
        rec = self.batch
        positions = None
        if (
            moved is not None
            and rec is not None
            and rec.rows_np is not None
            and rec.unique
            and len(rec.rows_np) == n
        ):
            positions = moved.tolist()
            key_row = self._key_row
            if [key_row.get(problems[i].key) for i in positions] != (
                rec.rows_np[moved].tolist()
            ):
                positions = None
        if positions is None:
            if rec is not None and rec.rows_np is not None:
                # another batch takes the table: the resident one's rows
                # keep the pass they were last live at
                self._st["last_used"][rec.rows_np] = rec.live
            # reclaim rows of deleted/idle bindings before the table would
            # grow (compaction reindexes rows, so it must run before the
            # walk hands out indices). Gated on ACTUAL new keys so the
            # steady all-rows storm pays one dict sweep at capacity
            # pressure, not an O(n_rows) compaction scan per pass.
            if self.n_rows + n > self.cap:
                new_keys = sum(
                    1 for p in problems if p.key not in self._key_row
                )
                if self.n_rows + new_keys > self.cap:
                    self._compact()
        # the rows move under the record from here: none stands until the
        # pass holds the new one
        self.batch = None
        key_row, probs, terms = self._key_row, self._problems, self._terms
        rows: list = []
        pack_rows: list = []
        pack_p: list = []
        pack_c: list = []
        same = equal = 0
        try:
            for i in range(n) if positions is None else positions:
                p = problems[i]
                row = key_row.get(p.key)
                if row is None:
                    row = self.n_rows
                    if row + 1 > self.cap:
                        self._grow(row + 1)
                    key_row[p.key] = row
                    self.n_rows = row + 1
                    probs.append(p)
                    terms.append("")
                    q = None
                else:
                    # the newcomer is pinned whatever its content: a later
                    # pass is diffed against it
                    q = probs[row]
                    probs[row] = p
                rows.append(row)
                if q is p:
                    same += 1
                elif (
                    q is not None
                    and q.placement is p.placement
                    and q.replicas == p.replicas
                    and q.gvk == p.gvk
                    and q.fresh == p.fresh
                    and q.requests == p.requests
                    and q.prev == p.prev
                    and q.evict_clusters == p.evict_clusters
                    and q.namespace == p.namespace
                ):
                    equal += 1
                else:
                    pack_rows.append(row)
                    pack_p.append(p)
                    pack_c.append(compiled[i])
        finally:
            # also when the table refuses to grow (FleetTableTooLarge): the
            # rows it took before that hold their bindings' state
            self._pack_rows(pack_rows, pack_p, pack_c)
        self._visited_this_pass = len(rows)
        t_same, t_equal, t_packed = self._upsert_tally
        t_same.inc(n - len(rows) + same)
        t_equal.inc(equal)
        t_packed.inc(len(pack_rows))
        if positions is None:
            rows_np = np.array(rows, np.int32)
            if rows:
                self._st["last_used"][rows_np] = self._pass
            return rows_np, _distinct(rows_np, self.n_rows)
        if rows:
            # the same rows in a fresh array: what hangs off the record by
            # its row vector starts anew as after a walk
            return rec.rows_np.copy(), True
        return rec.rows_np, True  # no position moved: the batch stands

    @staticmethod
    def _slot_key(placement, term: int) -> tuple:
        """The interning key of a (placement, affinity term) slot: the
        Placement OBJECT (its slot pins it) and the term's index."""
        return (id(placement) if placement is not None else 0, term)

    def _term_slots(self, placement, compiled) -> tuple:
        """The ordered term slots of a placement, padded to T_CAP with -1,
        each interned at its first row (the engine sends no placement with
        more than T_CAP terms)."""
        terms = compiled.terms
        if len(terms) > T_CAP:
            raise IndexError(
                f"a placement of {len(terms)} affinity terms has no room in "
                f"a row's {T_CAP} term slots"
            )
        slots = []
        for t in range(len(terms)):
            key = self._slot_key(placement, t)
            slot = self._cp_slot.get(key)
            if slot is None:
                slot = len(self._cp_pl)
                self._cp_slot[key] = slot
                self._cp_pl.append((placement, compiled, t))
                self._slots_minted_this_pass += 1
                self._static_max = max(
                    self._static_max,
                    int(compiled.static_weights.max(initial=0)),
                )
                self._tables_dirty = True
            slots.append(slot)
        return (*slots, *(-1,) * (T_CAP - len(slots)))

    def _profile_slot(self, problem, qns: int) -> int:
        """The request-profile slot of a binding (pods-dim adjustment
        applied BEFORE interning, mirroring _pack_chunk: each replica
        occupies a pod), interned per (request vector, cap namespace)."""
        snap = self.engine.snapshot
        vec = np.zeros(len(snap.dims), np.int64)
        for d, q in problem.requests.items():
            j = snap.dim_index(d)
            if j is not None:
                vec[j] = q
        pods = snap.dim_index("pods")
        if pods is not None and problem.replicas > 0:
            vec[pods] = max(vec[pods], 1)
        pkey = vec.tobytes() + qns.to_bytes(4, "little", signed=True)
        pslot = self._prof_slot.get(pkey)
        if pslot is None:
            pslot = len(self._profiles)
            self._prof_slot[pkey] = pslot
            self._profiles.append(vec)
            self._prof_ns.append(qns)
            self._prof_capped = self._prof_capped or qns >= 0
            self._tables_dirty = True
        return pslot

    def _pack_rows(self, rows: list, problems: list, compiled: list) -> None:
        """Row state of ``rows`` from their bindings, by columns: one walk
        gathers the fields into flat lists (a placement's term slots looked
        up once a pass; slots, gvks and profiles interned in the order the
        rows come, so their numbers do not depend on how many rows a call
        packs), then each field of the staging takes ONE fancy-index
        assignment. The ONE pack path: a first pass of every key, a delta's
        sub-batch and a swapped batch's moved rows all come here. A row
        that comes twice keeps its last binding's state."""
        k = len(rows)
        if not k:
            return
        self._packed_this_pass += k
        snap = self.engine.snapshot
        # the identity check (not ==) on the memo's snapshot pins the dims
        # mapping the cached slots were built under AND keeps the object
        # alive, so a recycled id can never alias a stale entry
        if self._req_slot_snap is not snap:
            self._req_slot = {}
            self._req_slot_snap = snap
        req_slot, gvk_slot, names = self._req_slot, self._gvk_slot, self._terms
        quota = getattr(self.engine, "quota", None)
        cap_index = (
            quota.cap_index if quota is not None and quota.cap_index else None
        )
        # id(placement) -> (compiled, its index in pl_slots / pl_strategy,
        # the row's term name(s)): rows of one placement share the entry
        by_pl: dict = {}
        pl_slots: list = []
        pl_strategy: list = []
        pl_of: list = []
        gvks: list = []
        profs: list = []
        reps: list = []
        fresh: list = []
        # previous sites and eviction tasks are ragged: (flat cell, value)
        prev_at: list = []
        prev_site: list = []
        prev_count: list = []
        evict_at: list = []
        evict_site: list = []
        # rows holding replicas their columns do not: (i, count)
        rest_at: list = []
        rest_n: list = []
        # the wide rows: row -> its slot, and (slot, sites, counts) of each
        slot_of: dict = {}
        wide: list = []
        widen = False
        site_of = snap.index.get
        full = False
        for i, (row, p, cp) in enumerate(zip(rows, problems, compiled)):
            pl = p.placement
            ent = by_pl.get(id(pl))
            if ent is None or ent[0] is not cp:
                terms = cp.terms
                ent = by_pl[id(pl)] = (
                    cp, len(pl_slots),
                    terms[0][0] if len(terms) == 1
                    else tuple(name for name, _ in terms),
                )
                pl_slots.append(self._term_slots(pl, cp))
                pl_strategy.append(cp.strategy)
            pl_of.append(ent[1])
            names[row] = ent[2]
            gslot = gvk_slot.get(p.gvk)
            if gslot is None:
                gslot = gvk_slot[p.gvk] = len(self._gvk_list)
                self._gvk_list.append(p.gvk)
                self._tables_dirty = True
            gvks.append(gslot)
            qns = cap_index.get(p.namespace, -1) if cap_index else -1
            rkey = (tuple(p.requests.items()), p.replicas > 0, qns)
            pslot = req_slot.get(rkey)
            if pslot is None:
                pslot = req_slot[rkey] = self._profile_slot(p, qns)
            profs.append(pslot)
            reps.append(p.replicas)
            fresh.append(p.fresh)
            widen = widen or (
                cp.strategy != S_DUPLICATED and p.replicas > NARROW_CELL_MAX
            )
            if p.prev:
                sites = list(map(site_of, p.prev))
                counts = p.prev.values()
                rest = 0
                if None in sites:  # a site that left the snapshot
                    held = sum(counts)
                    counts = [
                        c for j, c in zip(sites, counts) if j is not None
                    ]
                    sites = [j for j in sites if j is not None]
                    rest = held - sum(counts)
                if len(sites) > K_PREV:
                    # more sites than the row's columns: the whole previous
                    # result in a slot of the wide table, which the first
                    # column names; the columns hold none of it
                    slot = slot_of.get(row)
                    if slot is None:
                        slot = slot_of[row] = self._wide_slot(row)
                    wide.append((slot, sites, counts))
                    rest += sum(counts)
                    sites, counts = (-1 - slot,), (0,)
                if rest:
                    rest_at.append(i)
                    rest_n.append(rest)
                prev_at.extend(range(i * K_PREV, i * K_PREV + len(sites)))
                prev_site.extend(sites)
                prev_count.extend(counts)
            if p.evict_clusters:
                sites = [
                    j for j in map(site_of, p.evict_clusters) if j is not None
                ]
                full = full or len(sites) > K_EVICT
                evict_at.extend(range(i * K_EVICT, i * K_EVICT + len(sites)))
                evict_site.extend(sites)
        if full:
            # a row's cells would run into the next row's (row_rides keeps
            # such a binding off the fleet)
            raise IndexError(
                f"a binding with more than {K_EVICT} eviction tasks on the "
                "snapshot's members has no room in a row"
            )
        st = self._st
        at = np.array(rows, np.int64)
        # the slots the rows held before this call (their first column)
        first = st["prev_sites"][at, 0]
        of = np.array(pl_of, np.int64)
        term_slots = np.array(pl_slots, np.int32)[of]
        st["term_slots"][at] = term_slots
        # cp_idx starts at the first term's slot and is the term kernel's
        # to rewrite for a multi-term row
        st["cp_idx"][at] = term_slots[:, 0]
        st["strategy"][at] = np.array(pl_strategy, np.int32)[of]
        # np.array(list, int32) refuses a number int32 does not hold, as
        # an element assignment does
        st["gvk_idx"][at] = np.array(gvks, np.int32)
        st["prof_idx"][at] = np.array(profs, np.int32)
        st["replicas"][at] = np.array(reps, np.int32)
        st["fresh"][at] = np.array(fresh, bool)
        sites = np.zeros(k * K_PREV, np.int32)
        counts = np.zeros(k * K_PREV, np.int32)
        cells = np.array(prev_at, np.int64)
        sites[cells] = np.array(prev_site, np.int32)
        counts[cells] = np.array(prev_count, np.int32)
        st["prev_sites"][at] = sites.reshape(k, K_PREV)
        st["prev_counts"][at] = counts.reshape(k, K_PREV)
        evict = np.full(k * K_EVICT, -1, np.int32)
        evict[np.array(evict_at, np.int64)] = np.array(evict_site, np.int32)
        st["evict_sites"][at] = evict.reshape(k, K_EVICT)
        # a (re)packed row starts unselected; the pass's selection, if the
        # row has one, lands after the uploads (_fleet_select, or
        # _apply_selections for a row the host selected)
        st["sel_bits"][at] = 0xFF
        st["sel_on_dev"][at] = False
        rest = np.zeros(k, np.int32)
        rest[rest_at] = rest_n
        st["prev_rest"][at] = rest
        if self._ns_src is not None:
            ns_of = self._ns_src.get
            st["ns_idx"][at] = [ns_of(p.namespace, -1) for p in problems]
        if self._dev_quota is not None:
            self._quota_dirty.update(rows)
        if slot_of or (first < 0).any():
            self._settle_wide(at, first, slot_of, wide)
        if widen and self._cell_bytes == 1:
            # the first Divided row past a one-byte cell: the residents
            # and the mirrors start again at two bytes a cell
            self._cell_bytes = 2
            self._reset_dense()
        self._dirty.update(rows)

    def _wide_slot(self, row: int) -> int:
        """The wide-table slot for ``row``: the one it holds (its first
        previous site names it), else a free one, else a new one (the host
        table grows to the next power of two, at least 16 rows)."""
        first = int(self._st["prev_sites"][row, 0])
        if first < 0:
            return -1 - first
        if self._wide_free:
            return self._wide_free.pop()
        slot = self._wide_n
        self._wide_n += 1
        if self._wide_prev is None or slot >= len(self._wide_prev):
            table = np.zeros(
                (_pow2(max(16, slot + 1)), self.engine.snapshot.num_clusters),
                np.int32,
            )
            if self._wide_prev is not None:
                table[: len(self._wide_prev)] = self._wide_prev
            self._wide_prev = table
            self._dev_wide = None  # another shape: a whole upload
        return slot

    def _settle_wide(self, at, first, slot_of: dict, wide: list) -> None:
        """After a pack: each written wide row's previous result into its
        slot (marked for upload where it moved), and the slot of every row
        whose last binding in the pack is narrow freed."""
        for slot, sites, counts in wide:
            vec = np.zeros(self._wide_prev.shape[1], np.int32)
            vec[list(sites)] = list(counts)
            if not np.array_equal(self._wide_prev[slot], vec):
                self._wide_prev[slot] = vec
                self._wide_dirty.add(slot)
        had = first < 0
        held = dict(zip(at[had].tolist(), (-1 - first[had]).tolist()))
        held.update(slot_of)
        now = self._st["prev_sites"][list(held), 0]
        self._wide_free.extend(
            s for s, f in zip(held.values(), now.tolist()) if f >= 0
        )

    def _compact_slots(self) -> None:
        """Drop placement slots no live row references: create/delete
        churn over a heterogeneous fleet retires placements whose rows
        compaction already reclaimed, and re-interning a returning
        placement is one cached compile + one slot append. Triggers a full
        table rebuild + state re-upload, so it runs only under cap
        pressure (slots_exhausted)."""
        ts = self._st["term_slots"][: self.n_rows]
        used = set(int(s) for s in np.unique(ts[ts >= 0]))
        keep = [i for i in range(len(self._cp_pl)) if i in used]
        if len(keep) == len(self._cp_pl):
            return
        remap = np.full(len(self._cp_pl), -1, np.int32)
        for new_i, old_i in enumerate(keep):
            remap[old_i] = new_i
        self._cp_pl = [self._cp_pl[i] for i in keep]
        self._cp_slot = {
            self._slot_key(pl, t): i
            for i, (pl, _, t) in enumerate(self._cp_pl)
        }
        self._static_max = max(
            (int(cp.static_weights.max(initial=0))
             for _, cp, _ in self._cp_pl),
            default=0,
        )
        ts[:] = np.where(ts >= 0, remap[np.maximum(ts, 0)], -1)
        self._st["cp_idx"][: self.n_rows] = ts[:, 0]
        self._tables_dirty = True
        self._cp_remapped = True  # device cp rows are stale: full upload
        self._dev_state = None  # cp_idx remapped: full re-upload

    def _max_slots(self) -> int:
        """Effective unique-placement cap: MAX_SLOTS floor, scaled up to
        the CP_TABLE_MAX_BYTES device budget. Per-slot bytes under the
        bitpacked layout: two packed mask planes (2*ceil(C/8) uint8) plus
        the int32 static-weight row (4C) — the pre-bitpack formula (12C)
        understated capacity ~2.8x. Snapped DOWN to _slot_cap's own
        quantization grid so the device capacity the cap implies actually
        fits the budget (a raw quotient would let the allocated table
        overshoot its quantum)."""
        c = max(1, self.engine.snapshot.num_clusters)
        per_slot = 2 * ((c + 7) // 8) + 4 * c
        by_budget = max(1, CP_TABLE_MAX_BYTES // per_slot)
        if by_budget > 8192:
            # _slot_cap quantizes device capacity in 4096-slot multiples
            # above 8192 — snap to ITS grid (a pow2 floor here forfeited
            # up to ~2x of the budgeted slots just above a power of two)
            snapped = by_budget // 4096 * 4096
        else:
            snapped = 1 << (by_budget.bit_length() - 1)
        return min(MAX_SLOTS_HARD, max(MAX_SLOTS, snapped))

    @property
    def slots_exhausted(self) -> bool:
        mx = self._max_slots()
        if len(self._cp_pl) > mx:
            # retired placements stay pinned by their AGED rows: reclaim
            # idle rows first, then sweep every unreferenced slot — a
            # generational churn workload (new unique placements per wave)
            # keeps one table alive instead of rebuilding per call
            self._compact()
            self._compact_slots()
        return (
            len(self._cp_pl) > mx
            or len(self._gvk_list) > mx
            or len(self._profiles) > mx
        )

    # -- device sync -------------------------------------------------------

    def _rebuild_tables(self) -> None:
        import os as _os
        import time as _t
        _dbg = _os.environ.get("KARMADA_SYNC_DEBUG") == "1"
        _t0 = _t.perf_counter()

        def _mark(tag):
            nonlocal _t0
            if _dbg:
                now = _t.perf_counter()
                print(f"# rebuild {tag}: {(now - _t0) * 1e3:.1f}ms", flush=True)
                _t0 = now

        snap = self.engine.snapshot
        gen = getattr(self.engine, "_snapshot_gen", 0)
        slots_changed = self._tables_dirty
        if gen != self._snapshot_gen and snap.mask_token == getattr(
            self, "_mask_token", None
        ):
            # availability-only swap: masks are pure functions of the
            # FILTER fields (mask_token), so every compiled slot is still
            # valid — recompiling 9k heterogeneous selectors through the
            # engine's LRU was ~6s per churn pass for identical results
            self._snapshot_gen = gen
        elif gen != self._snapshot_gen:
            # snapshot swapped in place (same cluster set): recompile each
            # slot's placement against the new snapshot, order-preserving so
            # row cp_idx values stay valid
            self._snapshot_gen = gen
            self._static_max = 0
            for i, (pl, _, t) in enumerate(self._cp_pl):
                cp = self.engine._compiled(pl)
                self._cp_pl[i] = (pl, cp, t)
                self._static_max = max(
                    self._static_max, int(cp.static_weights.max(initial=0))
                )
            # NOTE: device cp rows stay valid here — masks are functions
            # of the FILTER fields only, and a swap that changed those
            # fields changed mask_token, which the `full` check below
            # already catches (resetting _cp_uploaded on every gen bump
            # would re-upload the whole [U, 3C] table each churn pass)
        _mark("recompile")
        c = snap.num_clusters

        def cp_bits_np(slots) -> np.ndarray:
            """Bitpacked [aff&spread_field | taint] planes: uint8[k, 2*W8]
            (little bit order — _unpack_bits is the device inverse)."""
            aff = np.stack(
                [(cp.terms[t][1] & cp.spread_field_ok) for _, cp, t in slots]
            )
            taint = np.stack([cp.taint_ok for _, cp, _ in slots])
            return np.concatenate(
                [
                    np.packbits(aff, axis=1, bitorder="little"),
                    np.packbits(taint, axis=1, bitorder="little"),
                ],
                axis=1,
            )

        def cp_static_np(slots) -> np.ndarray:
            return np.stack(
                [cp.static_weights.astype(np.int32) for _, cp, _ in slots]
            )  # [k, C]

        # the mask tables are functions of the snapshot's FILTER fields only
        # (labels/taints/enablements/topology — snapshot.mask_token) and the
        # interned slot lists. An availability-only swap (churn) leaves both
        # unchanged, so the resident device tables stay valid. New interned
        # slots APPEND to a pow2-capacity device table (one small scatter —
        # re-uploading the full [U, 3C] table per new placement moves the
        # whole table at heterogeneous U, and an exact-U shape retraced
        # the whole solve per slot); mask-token changes and
        # slot remaps rebuild in full.
        token = snap.mask_token
        n_slots = len(self._cp_pl)
        full = (
            self._dev_tables is None
            or token != getattr(self, "_mask_token", None)
            or self._cp_remapped
            or self._cp_uploaded == 0
        )
        w8 = (c + 7) // 8
        if full:
            # quantized capacity, padded with on-device zeros via concat
            # (a functional .at[:n].set on a zeros table would hold TWO
            # full-size buffers transiently); only live rows ship the wire
            cap_s = _slot_cap(n_slots)
            bits_live = jnp.asarray(cp_bits_np(self._cp_pl))
            static_live = jnp.asarray(cp_static_np(self._cp_pl))
            if cap_s > n_slots:
                pad = cap_s - n_slots
                cp_bits_dev = jnp.concatenate(
                    [bits_live, jnp.zeros((pad, 2 * w8), jnp.uint8)]
                )
                cp_static_dev = jnp.concatenate(
                    [static_live, jnp.zeros((pad, c), jnp.int32)]
                )
            else:
                cp_bits_dev = bits_live
                cp_static_dev = static_live
            self._cp_uploaded = n_slots
            self._cp_remapped = False
        else:
            cp_bits_dev = self._dev_tables[0]
            cp_static_dev = self._dev_tables[1]
            if n_slots > self._cp_uploaded:
                if n_slots > cp_bits_dev.shape[0]:  # grow device capacity
                    grow = _slot_cap(n_slots) - cp_bits_dev.shape[0]
                    cp_bits_dev = jnp.concatenate(
                        [cp_bits_dev, jnp.zeros((grow, 2 * w8), jnp.uint8)]
                    )
                    cp_static_dev = jnp.concatenate(
                        [cp_static_dev, jnp.zeros((grow, c), jnp.int32)]
                    )
                new_slots = self._cp_pl[self._cp_uploaded :]
                idx = jnp.arange(self._cp_uploaded, n_slots)
                cp_bits_dev = cp_bits_dev.at[idx].set(
                    jnp.asarray(cp_bits_np(new_slots))
                )
                cp_static_dev = cp_static_dev.at[idx].set(
                    jnp.asarray(cp_static_np(new_slots))
                )
                self._cp_uploaded = n_slots
        if full or slots_changed:
            gvk_rows = []
            for g in self._gvk_list:
                gid = snap.gvk_vocab.get(g) if g else None
                if gid is None:
                    mask = (
                        np.zeros(c, bool)
                        if g and len(snap.gvk_vocab) > 0
                        else np.ones(c, bool)
                    )
                else:
                    word, bit = gid // 32, gid % 32
                    mask = (snap.gvk_bits[:, word] >> np.uint32(bit)) & 1 != 0
                gvk_rows.append(mask)
            gvk_packed = np.packbits(
                np.stack(gvk_rows), axis=1, bitorder="little"
            )
            gvk_dev = (
                jnp.zeros((_pow2(max(len(gvk_rows), 4)), w8), jnp.uint8)
                .at[: len(gvk_rows)]
                .set(jnp.asarray(gvk_packed))
            )
            inc_dev = jnp.asarray(~snap.complete_enablements)
            # the Select stage's small tables move with the slot tables:
            # each slot's constraint parameters (zero rows past the live
            # slots: no constraint) and the snapshot's region table
            regions = region_table(snap)
            self._dev_spread = None
            if regions is not None:
                sp = np.zeros((cp_bits_dev.shape[0], N_PARAMS), np.int32)
                sp[:n_slots] = [
                    constraint_params(cp) for _, cp, _ in self._cp_pl
                ]
                self._dev_spread = (jnp.asarray(sp), jnp.asarray(regions))
        else:
            _, _, gvk_dev, _, inc_dev = self._dev_tables
        _mark("masks")
        profs = np.stack(self._profiles)
        # pow2 row padding keeps the solve trace stable as profiles intern
        # (zero-request pad rows estimate to the untouched sentinel and are
        # never gathered — prof_idx stays below the live count)
        pad_p = _pow2(max(len(profs), 4))
        profs_dev = profs
        prof_ns = np.asarray(self._prof_ns, np.int32)
        if pad_p > len(profs):
            profs_dev = np.zeros((pad_p, profs.shape[1]), profs.dtype)
            profs_dev[: len(profs)] = profs
            prof_ns = np.concatenate(
                [prof_ns, np.full(pad_p - len(profs), -1, np.int32)]
            )
        # quota-aware table: cap-namespace profile slots get the static-
        # assignment ceiling min-folded into their availability row
        prof_table = self.engine._profile_table_quota(profs_dev, prof_ns)
        if self._prof_capped:
            # the cap kernel ran: (profile slots, those of a capped
            # namespace), stamped on the pass's sync stretch
            self._caps_fold = (len(profs), int((prof_ns >= 0).sum()))
        _mark("prof_table")
        # host mirror of the estimator max (general + models): the device
        # form is a blocking scalar fetch (one more round-trip) and this
        # rebuild runs EVERY churn pass (snapshot gen bumps per drift)
        self._avail_max = self._host_avail_max(profs)
        _mark("avail_max")
        # what the estimator fold (_fold_extra_estimates) starts from: the
        # table before any extra estimator's answer, its request vectors
        # (padding included) and its bound
        self._prof_base = (prof_table, profs_dev, len(profs), self._avail_max)
        self._folded_ests, self._folded_tokens = (), None
        # under a mesh the slot tables replicate explicitly (empty-spec
        # NamedSharding): they are gathered per row by slot index inside
        # the sharded solve, and a one-time replicated upload beats a
        # per-pass broadcast from device 0. device_put is a no-op for
        # arrays already committed to the target sharding (the
        # incremental append path mutates replicated arrays in place).
        tables = (cp_bits_dev, cp_static_dev, gvk_dev, prof_table, inc_dev)
        if self._mesh is not None:
            repl = NamedSharding(self._mesh, P())
            tables = tuple(jax.device_put(a, repl) for a in tables)
            if self._dev_spread is not None:
                self._dev_spread = tuple(
                    jax.device_put(a, repl) for a in self._dev_spread
                )
        self._dev_tables = tables
        self._mask_token = token
        self._tables_dirty = False

    def _host_avail_max(self, profs: np.ndarray) -> int:
        """Sentinel-excluded max over the shared host mirror of the
        estimator profile table (core.host_profile_table, general +
        resource models). The device form was a blocking scalar fetch
        running every churn pass."""
        from .core import host_profile_table

        mi = 2**31 - 1
        table = host_profile_table(
            self.engine.snapshot, profs,
            models_active=self.engine._models_active(),
        )
        valid = table != mi
        return int(table[valid].max()) if valid.any() else 0

    def _estimates_moved(self) -> bool:
        """Whether the resident profile table no longer holds what the
        engine's extra estimators answer: the estimators themselves were
        re-pointed, an estimator's ``refresh_token`` moved since the fold,
        or it gives none (a degraded pass: a registered member answered -1
        transiently, so nothing folded from it may be trusted, replayed or
        kept)."""
        ests = tuple(self.engine.extra_estimators)
        if ests != self._folded_ests:
            return True
        if not ests:
            return False
        tokens = self.engine._est_tokens()
        return None in tokens or tokens != self._folded_tokens

    def _fold_extra_estimates(self) -> None:
        """Min-merge every extra estimator's by-profile answer into the
        resident profile table (``_fold_estimates``), one more merge input
        exactly as the quota caps are. Re-asks the estimators and re-runs
        the fold, nothing else: no row is packed again, no mask table
        touched. The stretch is kept in ``_est_window`` so the pass's
        ``sync`` phase can leave it to the ``estimator.*`` spans."""
        from ..utils.tracing import tracer

        t_a = time.perf_counter()
        base, profs_pad, n_live, base_max = self._prof_base
        ests = self.engine.extra_estimators
        answers = tuple(est.profile_table(profs_pad, n_live) for est in ests)
        c = self.engine.snapshot.num_clusters
        with tracer.span("estimator.fold", profiles=n_live, clusters=c):
            self._mark_trace(
                "F", len(profs_pad), c, len(answers), self._mesh is not None
            )
            folded = _fold_estimates(base, answers, np.int32(n_live))
            if self._mesh is not None:
                folded = jax.device_put(folded, NamedSharding(self._mesh, P()))
            t = self._dev_tables
            self._dev_tables = (t[0], t[1], t[2], folded, t[4])
            # a stated upper bound, not the fold's own max: a cell the
            # general estimator left unanswered takes the estimator's value
            self._avail_max = max(
                [base_max] + [est.profile_bound(profs_pad[:n_live]) for est in ests]
            )
        # whose answers were just folded (the objects are pinned: an id()
        # could be recycled), and their tokens (None after a degraded one)
        self._folded_ests = tuple(ests)
        self._folded_tokens = self.engine._est_tokens()
        self._est_window = (t_a, time.perf_counter())

    def _upload_state(self) -> tuple:
        """Full packed-state upload. Under a mesh the state replicates
        EXPLICITLY across every device (NamedSharding with an empty spec):
        the solve gathers per-row state by arbitrary row index, so a
        replica-local gather beats a per-pass broadcast of the whole
        grid from device 0."""
        put = (
            (lambda a: a) if self._mesh is None
            else partial(jax.device_put, device=NamedSharding(self._mesh, P()))
        )
        self._last_upload_bytes += sum(
            self._st[k].nbytes for k in (*_STATE_FIELDS, "term_slots")
        )
        # the term kernel's input rides with the state; what it last chose
        # stays (a row's choice is made again in every pass that holds it)
        self._dev_term_slots = put(jnp.asarray(self._st["term_slots"]))
        if self._dev_term_sel is None:
            self._dev_term_sel = put(jnp.zeros(self.cap, jnp.uint8))
        return tuple(put(jnp.asarray(self._st[k])) for k in _STATE_FIELDS)

    def _sync_device(self) -> None:
        self._last_upload_bytes = 0
        if self._tables_dirty or (
            getattr(self.engine, "_snapshot_gen", 0) != self._snapshot_gen
        ):
            self._rebuild_tables()
        if self._estimates_moved():
            self._fold_extra_estimates()
        if self._dev_state is None:
            self._dev_state = self._upload_state()
            self._dirty.clear()
        elif self._dirty:
            rows = np.fromiter(self._dirty, np.int64, len(self._dirty))
            if len(rows) > self.cap // 2:
                self._dev_state = self._upload_state()
            else:
                # pow2-pad the scatter (repeating the first row: duplicate
                # writes of identical values are idempotent) so distinct
                # dirty-row counts yield log-many traces, and ledger the
                # signature — an unmarked compile here would break the
                # warm-loop contract new_trace_last_pass carries
                pad = _pow2(len(rows))
                rows_p = np.concatenate(
                    [rows, np.full(pad - len(rows), rows[0], np.int64)]
                )
                vals = tuple(
                    self._st[k][rows_p] for k in (*_STATE_FIELDS, "term_slots")
                )
                self._last_upload_bytes += rows_p.nbytes + sum(
                    v.nbytes for v in vals
                )
                self._mark_trace(
                    "S", self.cap, pad, self._mesh is not None
                )
                *state, self._dev_term_slots = _scatter_rows(
                    (*self._dev_state, self._dev_term_slots),
                    jnp.asarray(rows_p), vals,
                )
                self._dev_state = tuple(state)
            self._dirty.clear()
        if self._wide_prev is not None and (
            self._dev_wide is None or self._wide_dirty
        ):
            self._sync_wide()

    def _sync_wide(self) -> None:
        """The wide table to the device: whole where it is new or took
        another shape (or most of it moved), else the slots written since,
        by one scatter padded to a power of two of slots."""
        table, dirty = self._wide_prev, self._wide_dirty
        if self._dev_wide is None or len(dirty) * 2 > len(table):
            self._dev_wide = self._replicated(table)
            self._last_upload_bytes += table.nbytes
        else:
            slots = np.fromiter(dirty, np.int64, len(dirty))
            pad = _pow2(len(slots))
            slots = np.concatenate(
                [slots, np.full(pad - len(slots), slots[0], np.int64)]
            )
            self._mark_trace("S", table.shape, pad, "wide")
            (self._dev_wide,) = _scatter_rows(
                (self._dev_wide,), jnp.asarray(slots), (table[slots],)
            )
            self._last_upload_bytes += slots.nbytes + table[slots].nbytes
        dirty.clear()

    # -- scheduling --------------------------------------------------------

    def schedule(
        self, problems: Sequence, compiled: Sequence, moved=None,
        selections=None, select=None, host_rows: int = 0, quota=None,
    ) -> list:
        """One fleet pass, wrapped in a ``scheduler.solve`` wave span with
        per-phase kernel child spans (host pack / dispatch / fenced device
        execute / fetch+fold) emitted from the pass breakdown — the
        device/host attribution surface of ISSUE 6 (b). The span carries
        the pass's packed-vs-replayed row split (the churn-attribution
        series the history ring records per wave, ISSUE 12) and
        ``rows_visited``, the positions of the batch its upsert phase
        looked at: 0 when the same list objects come again, the positions
        that hold another object when a list of the resident batch's
        length comes (a swapped batch costs its swapped rows), every
        position otherwise (``upsert``); the ``kernel.host`` span of that
        phase carries it too, beside ``rows_packed``. The device-byte
        ledger publishes after every pass.

        ``moved`` (optional) is the sorted POSITIONS of ``problems`` that
        the engine's diff against the resident batch found moved (another
        object, or a key the caller named dirty); every other position
        holds the object the resident batch does. The upsert phase visits
        those positions alone. Whether the untouched rows REPLAY from the
        host mirrors (only the moved positions packed and dispatched) or
        every row is dispatched is decided here, once (_replays).

        ``select`` (optional) is the POSITIONS of the spread-constrained
        rows whose SelectClusters stage runs on the device: _fleet_select
        computes each one's selection from the resident row state and
        writes it into the resident ``sel_bits``, in every pass over these
        rows (the batch-identity fast path brings the same list objects and
        no ``select``: the table keeps the positions with the batch).

        ``selections`` (optional) is ``(positions, bits)``: rows the HOST
        selected (a snapshot with more regions than R_CAP) and each one's
        SelectClusters result under the current snapshot, bitpacked
        (uint8[k, W8], little bit order). Such a selection is row state: it
        stays with the row until a later pass brings another, so a pass
        over the same batch at the same snapshot generation need not bring
        any. A batch brings one or the other (the snapshot's region count
        decides for all its spread rows), never both.

        ``host_rows`` is how many rows of the caller's batch left the fleet
        for the host path (stamped on the span; 0 on the fast paths).

        ``quota`` (optional) is the QuotaSnapshot the batch is admitted
        against, from a caller whose whole batch rides the table: the pass
        admits it from the row state (_admit_on_device), the result list
        answers a denied row QUOTA_EXCEEDED_ERROR, and ``quota_debit``
        holds the admitted demand for the caller to commit (None where the
        verdict of the same rows at the same generation was replayed)."""
        from ..utils.metrics import (
            affinity_term_choices,
            eviction_masked_rows,
            fleet_placement_slots,
            fleet_slots_minted,
            spread_selections,
        )
        from ..utils.tracing import tracer

        with tracer.span("scheduler.solve") as sp:
            self._phase_marks = []
            self._entry_marks = []
            self._select_mark = self._terms_mark = self._quota_mark = None
            self._quota_pass, self.quota_debit = quota, None
            self._derived_outcome = "kept"
            self._sync_ns()
            res = self._schedule_pass(
                problems, compiled, moved, selections, select
            )
            if quota is not None:
                self._record_admission(res, quota)
            tmr = self.last_breakdown
            sp.attrs["rows"] = len(problems)
            sp.attrs["rows_visited"] = int(tmr.get("rows_visited", 0))
            sp.attrs["rows_packed"] = int(tmr.get("rows_packed", 0))
            sp.attrs["rows_replayed"] = int(tmr.get("rows_replayed", 0))
            sp.attrs["dirty_rows"] = int(tmr.get("dirty_rows", 0))
            minted, self._slots_minted_this_pass = (
                self._slots_minted_this_pass, 0
            )
            sp.attrs["slots"] = len(self._cp_pl)
            sp.attrs["slots_minted"] = minted
            sp.attrs["host_rows"] = int(host_rows)
            sp.attrs["derived"] = self._derived_outcome
            self._derived_tally[self._derived_outcome].inc()
            sp.attrs["wide_rows"] = wide = self.batch.derived.wide_rows
            sp.attrs["cell_bytes"] = self._cell_bytes
            self._wide_tally.inc(wide)
            self._emit_phase_spans()
            mark, self._terms_mark = self._terms_mark, None
            if mark is not None:
                # the term kernel's span: the host's share (the row vector
                # and the dispatch), with what the kernel counted (on the
                # host since the dispatch, read after the pass's fence)
                t_a, t_b, n_multi, counts, evicted = mark
                if n_multi:
                    fallback, unfit = (int(v) for v in np.asarray(counts))
                    tracer.record(
                        "scheduler.terms", t_b - t_a, start=t_a,
                        rows=n_multi, fallback=fallback, unfit=unfit,
                        evicted_rows=evicted,
                    )
                    for outcome, k in (
                        ("first", n_multi - fallback - unfit),
                        ("fallback", fallback), ("unfit", unfit),
                    ):
                        if k:
                            affinity_term_choices.inc(k, outcome=outcome)
                if evicted:
                    eviction_masked_rows.inc(evicted)
            mark, self._select_mark = self._select_mark, None
            if mark is not None:
                # the Select stage's span: the host's share of it (the row
                # vector and the dispatch), at its interval, with what the
                # kernel counted (copied to the host since the dispatch and
                # read after the pass's fence: no device round trip)
                t_a, t_b, n_sel, counts = mark
                fit_errors, moved = (int(v) for v in np.asarray(counts))
                tracer.record(
                    "scheduler.select", t_b - t_a, start=t_a, rows=n_sel,
                    device=n_sel, computed=0, hits=0,
                    fit_errors=fit_errors, moved=moved,
                )
                spread_selections.inc(n_sel - fit_errors, outcome="device")
                if fit_errors:
                    spread_selections.inc(fit_errors, outcome="fit_error")
        if minted:
            fleet_slots_minted.inc(minted)
        fleet_placement_slots.set(len(self._cp_pl))
        self._publish_device_bytes()
        return res

    def _apply_selections(self, rows_np: np.ndarray, selections) -> int:
        """Write the pass's spread selections into the row state and mark
        the rows whose selection moved for upload. Returns how many moved.
        Rows the pass brings no selection for keep theirs (all ones since
        they were packed, for a row without constraints)."""
        pos, bits = selections
        if not len(pos):
            return 0
        rows = rows_np[pos]
        st = self._st["sel_bits"]
        # a row the device selected last holds the kernel's bits there,
        # whatever this mirror says
        on_dev = self._st["sel_on_dev"]
        moved = (st[rows] != bits).any(axis=1) | on_dev[rows]
        if moved.any():
            st[rows[moved]] = bits[moved]
            on_dev[rows[moved]] = False
            self._dirty.update(rows[moved].tolist())
        return int(moved.sum())

    def _select_on_device(self, rows_np: np.ndarray, select) -> None:
        """Dispatch _fleet_select over the positions ``select`` of the
        pass's rows (no host wait: the pass's fence is the only fence).
        The device row vector is padded to whole chunks and kept while the
        same positions of the same rows come again, so one trace and one
        upload serve every wave of a batch."""
        t_a = time.perf_counter()
        if self._dev_spread is None:
            raise RuntimeError(
                f"the snapshot holds more than {R_CAP} regions: its spread "
                "rows select on the host (TensorScheduler decides by "
                "scheduler.select.region_table, as this table does)"
            )
        c = self.engine.snapshot.num_clusters
        cache = self.batch.select_rows
        if not (
            cache is not None
            and cache.rows_np is rows_np
            and np.array_equal(cache.select, select)
        ):
            rows = rows_np[select]
            n = len(rows)
            chunk = _select_chunk(n, c)
            n_chunks = -(-n // chunk)
            ar = np.full(chunk * n_chunks, -1, np.int32)
            ar[:n] = rows
            self._st["sel_on_dev"][rows] = True
            self._last_upload_bytes += ar.nbytes
            cache = _SelectRows(
                select, rows_np, jnp.asarray(ar), n, chunk, n_chunks
            )
            self.batch.select_rows = cache
        chunk, n_chunks = cache.chunk, cache.n_chunks
        if self._dev_subsets is None:
            self._dev_subsets = tuple(
                jnp.asarray(a) for a in subset_table(R_CAP)
            )
        state = self._dev_state
        args = (
            *self._dev_tables, *self._dev_spread, *self._dev_subsets,
            cache.rows_dev, *(state[k] for k in _SELECT_STATE),
            *self._wide_args(),
        )
        from ..parallel.mesh import mesh_shape as _mesh_shape

        key = (
            "T", self.cap, c, self._dev_tables[0].shape, chunk, n_chunks,
            _mesh_shape(self._mesh), self._wide_shape(),
        )
        if self._mark_trace(*key) and self._mesh is None:
            # meshed dispatches stay manifest-unrecorded, as _fleet_bits'
            self._record_trace(
                "fleet_select", key, args, chunk=chunk, n_chunks=n_chunks
            )
        sel_bits, counts = _fleet_select(
            *args, chunk=chunk, n_chunks=n_chunks
        )
        self._dev_state = (*state[:-1], sel_bits)
        # the two counts start for the host now and are there by the pass's
        # fence: reading them for the span waits on nothing
        counts.copy_to_host_async()
        self._select_mark = (t_a, time.perf_counter(), cache.n, counts)

    def _term_rows(self, rows_np: np.ndarray) -> _TermRows:
        """The pass's multi-term rows as the term kernel takes them: an
        int32 row vector padded to whole chunks, kept while the same rows
        come again unpacked (the identity fast path uploads nothing)."""
        cache = self.batch.terms
        if cache is not None and cache.rows_np is rows_np:
            return cache
        st = self._st
        multi = rows_np[st["term_slots"][rows_np, 1] >= 0]
        n = len(multi)
        chunk = _terms_chunk(n, self.engine.snapshot.num_clusters)
        n_chunks = max(1, -(-n // chunk))
        rows_dev = None
        if n:
            ar = np.full(chunk * n_chunks, -1, np.int32)
            ar[:n] = multi
            rows_dev = jnp.asarray(ar)
            self._last_upload_bytes += ar.nbytes
        cache = _TermRows(
            rows_np, rows_dev, n, chunk, n_chunks,
            int((st["evict_sites"][rows_np, 0] >= 0).sum()),
        )
        self.batch.terms = cache
        return cache

    def _terms_on_device(self, rows_np: np.ndarray) -> None:
        """Dispatch _fleet_terms over the pass's multi-term rows, if it has
        any (no host wait: the pass's fence is the only fence). Leaves the
        chosen slots in the resident ``cp_idx`` and ``term_sel``, and in
        ``_terms_mark`` what schedule() records of it."""
        t_a = time.perf_counter()
        tr = self._term_rows(rows_np)
        if not tr.n:
            self._terms_mark = (t_a, t_a, 0, None, tr.evicted)
            return
        c = self.engine.snapshot.num_clusters
        state = self._dev_state
        args = (
            *self._dev_tables, tr.rows_dev, self._dev_term_slots,
            self._dev_term_sel, *state[:-1],  # all but sel_bits
            *self._wide_args(),
        )
        from ..parallel.mesh import mesh_shape as _mesh_shape

        key = (
            "R", self.cap, c, self._dev_tables[0].shape, tr.chunk,
            tr.n_chunks, _mesh_shape(self._mesh), self._wide_shape(),
        )
        if self._mark_trace(*key) and self._mesh is None:
            # meshed dispatches stay manifest-unrecorded, as _fleet_bits'
            self._record_trace(
                "fleet_terms", key, args, chunk=tr.chunk,
                n_chunks=tr.n_chunks,
            )
        cp_idx, term_sel, counts = _fleet_terms(
            *args, chunk=tr.chunk, n_chunks=tr.n_chunks
        )
        self._dev_state = (cp_idx, *state[1:])
        self._dev_term_sel = term_sel
        # both start for the host now and are there by the pass's fence
        counts.copy_to_host_async()
        term_sel.copy_to_host_async()
        self._terms_mark = (
            t_a, time.perf_counter(), tr.n, counts, tr.evicted
        )

    def _sync_ns(self) -> None:
        """Keep the staging's ``ns_idx`` column derived from the engine's
        current QuotaSnapshot.ns_index. A quota generation that moves
        ``remaining`` alone brings the same namespaces (the same dict, or
        an equal one) and touches no row; another namespace SET re-derives
        the column from the rows' pinned bindings, once; with no quota set
        the column is not kept up (the next quota re-derives it)."""
        q = getattr(self.engine, "quota", None)
        src = q.ns_index if q is not None and q.ns_index else None
        cur = self._ns_src
        if src is cur:
            return
        self._ns_src = src
        if src is None or (cur is not None and src == cur) or not self.n_rows:
            return
        ns_of = src.get
        self._st["ns_idx"][: self.n_rows] = [
            ns_of(p.namespace, -1) for p in self._problems
        ]
        self._dev_quota = self._quota_verdict = None

    def _wide_args(self) -> tuple:
        """The wide table as a kernel's trailing argument: (table,) where
        the table holds one, else () (the kernel then reads none)."""
        return () if self._dev_wide is None else (self._dev_wide,)

    def _wide_shape(self) -> Optional[tuple]:
        """The wide table's shape, as the trace keys carry it (None where
        the table holds none)."""
        return None if self._dev_wide is None else self._dev_wide.shape

    def _replicated(self, a):
        """``a`` on the device; under a mesh on every device of it, as the
        state the pass gathers from is."""
        a = jnp.asarray(a)
        if self._mesh is None:
            return a
        return jax.device_put(a, NamedSharding(self._mesh, P()))

    def _rows_padded(self, rows_np: np.ndarray) -> np.ndarray:
        """A batch's rows padded with -1 to whole chunks, as a pass
        dispatches them."""
        n = len(rows_np)
        eff = min(self.chunk, _pow2(max(n, 256)))
        ar = np.full(max(eff, -(-n // eff) * eff), -1, np.int32)
        ar[:n] = rows_np
        return ar

    def _dispatch_quota(self, quota, rows_dev) -> tuple:
        """Admit the rows ``rows_dev`` (a batch's table rows in presented
        order, -1 padded) against ``quota.remaining``: _fleet_quota derives
        each position's namespace and demand from the row state, and
        ops.quota.quota_admit, the kernel the engine's host partition
        dispatches, admits them; its trace rides the ENGINE'S ledger under
        the engine's key, so one family counts both routes' compiles.
        First the inputs come to the device: the two quota columns (whole
        after a growth, a compaction or a namespace-set move, else the
        rows packed since), the profile slots' request vectors (when a
        profile was interned) and ``remaining``, padded to a power of two
        of namespaces with UNLIMITED rows (a few KB a generation). No host
        wait. Returns (admitted bool[n_pad], int64[N, R] the admitted
        demand a namespace, the batch's quota'd rows), their copies to the
        host started."""
        st = self._st
        if self._dev_quota is None:
            self._dev_quota = tuple(
                self._replicated(st[k]) for k in ("ns_idx", "prev_rest")
            )
            self._last_upload_bytes += 8 * self.cap
        elif self._quota_dirty:
            dirty = np.fromiter(
                self._quota_dirty, np.int64, len(self._quota_dirty)
            )
            pad = _pow2(len(dirty))
            rows_p = np.concatenate(
                [dirty, np.full(pad - len(dirty), dirty[0], np.int64)]
            )
            self._mark_trace("S", self.cap, pad, "quota")
            self._dev_quota = _scatter_rows(
                self._dev_quota, jnp.asarray(rows_p),
                (st["ns_idx"][rows_p], st["prev_rest"][rows_p]),
            )
            self._last_upload_bytes += 16 * pad
        self._quota_dirty.clear()
        n_prof = len(self._profiles)
        if self._dev_prof_reqs is None or self._dev_prof_reqs[0] != n_prof:
            profs = np.zeros(
                (_pow2(max(n_prof, 4)), len(quota.dims)), np.int64
            )
            profs[:n_prof] = np.stack(self._profiles)
            self._dev_prof_reqs = (n_prof, self._replicated(profs))
        args = (
            self._dev_prof_reqs[1], rows_dev, *self._dev_quota,
            *(self._dev_state[k] for k in _QUOTA_STATE),
        )
        from ..parallel.mesh import mesh_shape as _mesh_shape

        b_pad = int(rows_dev.shape[0])
        mesh_el = _mesh_shape(self._mesh)
        key = ("Q", self.cap, b_pad, args[0].shape, mesh_el)
        if self._mark_trace(*key) and self._mesh is None:
            # meshed dispatches stay manifest-unrecorded, as _fleet_bits'
            self._record_trace("fleet_quota", key, args)
        ns, demand, quota_rows = _fleet_quota(*args)
        remaining = quota.remaining
        n_ns = _pow2(max(remaining.shape[0], 4))
        if n_ns > remaining.shape[0]:
            remaining = np.pad(
                remaining, ((0, n_ns - remaining.shape[0]), (0, 0)),
                constant_values=UNLIMITED,
            )
        admit_args = (ns, demand, jnp.asarray(remaining))
        engine = self.engine
        key = ("Q", b_pad, n_ns, int(remaining.shape[1]), mesh_el)
        if engine._mark_trace(*key) and self._mesh is None:
            engine._record_trace("quota_admit", key, admit_args)
        admitted, used = _quota_admit(*admit_args)
        for a in (admitted, used, quota_rows):
            a.copy_to_host_async()
        return admitted, used, quota_rows

    def _admit_on_device(self, rows_np, rows_dev, changed=None) -> None:
        """The quota admission of the batch whose rows are ``rows_np``
        (``rows_dev``: the same on the device, -1 padded, or None to have
        it made here), against the pass's quota: one admission over the
        batch in presented order (_dispatch_quota), or none where the last
        verdict is of these rows at this quota generation (it is replayed:
        within a generation ``remaining`` only falls, by what was
        admitted).

        ``changed`` = (the row vector the last verdict was given, the
        positions that moved since): at an unmoved generation the moved
        positions alone are admitted, as a batch of their own against the
        working ``remaining``, which already carries what the others were
        admitted with; every other position keeps its verdict and is not
        charged again (TensorScheduler._quota_admission_delta's contract).

        Leaves in ``_quota_mark`` what schedule() records: the stretch, the
        verdict, and the kernels' count and admitted demand still on their
        way to the host (None for a replay)."""
        t_a = time.perf_counter()
        quota = self._quota_pass
        last = self._quota_verdict
        n = len(rows_np)
        stands = last is not None and last.generation == quota.generation
        if stands and changed is None and last.rows_np is rows_np:
            self._quota_mark = (t_a, time.perf_counter(), last, None, None)
            return
        if stands and changed is not None and last.rows_np is changed[0]:
            pos = changed[1]
            sub = np.full(_pow2(len(pos)), -1, np.int32)
            sub[: len(pos)] = rows_np[pos]
            admitted, used, _ = self._dispatch_quota(quota, jnp.asarray(sub))
            denied = last.denied().copy()
            denied[pos] = ~np.asarray(admitted)[: len(pos)]
            verdict = _QuotaVerdict(rows_np, quota.generation, n, denied)
            # the unmoved positions' quota'd rows + these
            quota_rows = int((self._st["ns_idx"][rows_np] >= 0).sum())
        else:
            if rows_dev is None:
                rows_dev = jnp.asarray(self._rows_padded(rows_np))
            admitted, used, quota_rows = self._dispatch_quota(quota, rows_dev)
            verdict = _QuotaVerdict(rows_np, quota.generation, n, admitted)
        self._quota_verdict = verdict
        self._quota_mark = (
            t_a, time.perf_counter(), verdict, quota_rows, used
        )

    def _record_admission(self, res, quota) -> None:
        """After the pass: hand the verdict to the result list, keep the
        admitted demand for the engine to debit, and record the admission
        (the ``scheduler.quota`` span at its stretch, the two counters).
        The kernels' outputs were on their way to the host since the
        dispatch, which lay before the pass's fetch and decode: reading
        them here waits on little."""
        from ..utils.tracing import tracer

        t_a, t_b, verdict, quota_rows, used = self._quota_mark
        self._quota_mark = None
        res.quota = verdict
        if used is not None:
            verdict.quota_rows = int(quota_rows)
            verdict.denied_rows = int(verdict.denied().sum())
            wu = np.asarray(used)[: quota.remaining.shape[0]]
            self.quota_debit = wu if wu.any() else None
        n, q_rows, denied = verdict.n, verdict.quota_rows, verdict.denied_rows
        tracer.record(
            "scheduler.quota", t_b - t_a, start=t_a, rows=n,
            quota_rows=q_rows, denied=denied,
            # rows whose demand or partition the host derived: none on
            # this route (a packed row's namespace lookup is the upsert
            # phase's, counted in its rows_packed)
            host_rows=0,
            dispatched=int(used is not None), generation=quota.generation,
        )
        self.engine._quota_tally.add(
            "resident" if used is not None else "replayed", n, q_rows, denied
        )

    def device_bytes(self) -> dict[str, int]:
        """Resident device bytes by ledger kind — the EXACT ``nbytes`` of
        the arrays this table holds right now (ISSUE 12 b): the packed
        state grid, the interned slot tables, the donated result
        residents (the dense pair), and the cached
        all-rows index. The accounting the 1M-on-16GB-HBM question needs
        before anyone puts the resident grid on a real part."""

        def nb(x) -> int:
            if x is None:
                return 0
            if isinstance(x, tuple):
                return sum(nb(v) for v in x)
            return int(getattr(x, "nbytes", 0))

        rec = self.batch
        return {
            "packed_grid": nb(self._dev_state) + nb(self._dev_term_slots)
            + nb(self._dev_term_sel) + nb(self._dev_quota) + nb(self._dev_wide)
            + (nb(self._dev_prof_reqs[1]) if self._dev_prof_reqs else 0),
            "slot_tables": nb(self._dev_tables) + nb(self._dev_spread)
            + nb(self._dev_subsets),
            "donated_residents": nb(self._res_dense) + nb(self._res_meta),
            "rows_index": nb(self._all_rows_dev) + (
                nb(rec.select_rows and rec.select_rows.rows_dev)
                + nb(rec.terms and rec.terms.rows_dev)
                if rec is not None else 0
            ),
        }

    def _buffer_platform(self) -> str:
        """Platform of the buffers the ledger counts (PR 9's honesty
        rule carried to the gauge: forced-host bytes must never read as
        HBM — the label says whose memory it is)."""
        for x in (self._dev_state, self._dev_tables, self._res_dense):
            arr = x[0] if isinstance(x, tuple) and x else x
            try:
                if arr is not None:
                    return next(iter(arr.devices())).platform
            except Exception:  # noqa: BLE001 — label is best-effort
                continue
        return "none"

    def _publish_device_bytes(self) -> None:
        """Refresh ``karmada_tpu_device_bytes{kind,bucket,platform}``
        from the live ledger: a clear-then-set sweep per kind so a cap
        regrow (bucket change) retires its stale sample instead of
        double-counting. With several engines in one process the gauge
        reflects the most recently dispatched table — the bucket label
        says which."""
        from ..utils.metrics import device_bytes as device_bytes_gauge

        bucket = f"{self.cap}x{self.engine.snapshot.num_clusters}"
        platform = self._buffer_platform()
        for kind, nbytes in self.device_bytes().items():
            device_bytes_gauge.remove_matching(kind=kind)
            device_bytes_gauge.set(
                nbytes, kind=kind, bucket=bucket, platform=platform
            )

    #: breakdown keys that are pure host work outside the dispatch/fetch
    #: windows (pack, delta scatter, result decode)
    _HOST_PHASE_KEYS = ("upsert", "sync", "prep", "post")
    #: breakdown key of a timed phase -> the span it is recorded as
    _PHASE_SPANS = {
        **dict.fromkeys(_HOST_PHASE_KEYS, "kernel.host"),
        "dispatch": "kernel.dispatch",
        "device": "kernel.device",
        "fetch": "kernel.fetch",
    }

    def _phase(
        self, tmr: dict, key: str, t0: float, end: Optional[float] = None
    ) -> float:
        """Close the pass phase ``key`` that began at ``t0``: its seconds
        go into the breakdown (summed where a phase has two stretches, as
        a delta pass's ``post``) and its interval is kept for the phase
        spans. Returns the closing stamp — the next phase's start, so the
        phases of a pass tile it without gaps."""
        now = time.perf_counter() if end is None else end
        tmr[key] = tmr.get(key, 0.0) + (now - t0)
        self._phase_marks.append((key, t0, now))
        return now

    def _emit_phase_spans(self) -> None:
        """Kernel phase spans + karmada_tpu_kernel_phase_seconds from the
        pass's phase stamps, each span at its TRUE interval: one
        ``kernel.host`` per host stretch (``phase`` = upsert | sync | prep
        before the dispatch, post after the fetch), then
        ``kernel.dispatch``, ``kernel.device``, ``kernel.fetch``. Children
        of one ``scheduler.solve`` are therefore disjoint and ordered, and
        a device-idle gap is charged to the phase that really held the
        host (ISSUE 25). Components are DISJOINT: ``fetch`` is the whole
        post-device window (wire transfer + decode + entry folds — each
        phase-B stretch inside it is a ``kernel.entries`` child, at its
        interval), and the fenced ``device`` window carries the compile
        attribution flag when this pass minted a fresh XLA trace. The
        histogram is observed once a phase a pass (``host`` with the
        stretches' sum)."""
        from ..utils.metrics import kernel_phase_seconds
        from ..utils.tracing import tracer

        tmr = self.last_breakdown
        # compile attribution: the compile of a fresh trace runs inside
        # the dispatch call or surfaces at the device fence — on a
        # fresh-trace pass both windows carry the flag, so the summary's
        # compile_s covers either
        fresh = bool(self.new_trace_last_pass)
        attrs = {
            "upsert": {
                "rows_visited": int(tmr.get("rows_visited", 0)),
                "rows_packed": int(tmr.get("rows_packed", 0)),
            },
            # the pass's host->device bytes ride the stretch that uploads,
            # so the history sampler (and a dumped wave) can read transfer
            # volume without reaching into the engine
            "sync": {
                "upload_mb": tmr.get("upload_mb", 0.0),
                **{k: int(tmr[k]) for k in ("quota_profiles", "quota_cap_rows")
                   if k in tmr},
            },
            "prep": {
                "derived": self._derived_outcome,
                "wide_rows": self.batch.derived.wide_rows,
                "cell_bytes": self._cell_bytes,
            },
            "dispatch": {"compile": fresh} if fresh else {},
            "device": {"compile": fresh},
            "fetch": {
                "fetch_mb": tmr.get("fetch_mb", 0.0),
                "changed_rows": tmr.get("changed_rows", 0.0),
                **{k: int(tmr.get(k, 0))
                   for k in ("delta_rows", "delta_cells", "entry_rows")},
            },
        }
        seconds: dict[str, float] = {}
        for key, t0, t1 in self._phase_marks:
            if t1 <= t0:
                continue
            name = self._PHASE_SPANS[key]
            sp = tracer.record(
                name, t1 - t0, start=t0,
                kind="device" if key == "device" else "host",
                **({"phase": key} if name == "kernel.host" else {}),
                **attrs.get(key, {}),
            )
            seconds[name] = seconds.get(name, 0.0) + (t1 - t0)
            if key != "fetch":
                continue
            # phase B's stretches inside this fetch window, its children
            for route, e0, e1, rows, entries, nbytes in self._entry_marks:
                if t0 <= e0 <= t1:
                    tracer.record(
                        "kernel.entries", e1 - e0, start=e0, parent=sp,
                        kind="host", route=route, rows=rows,
                        entries=entries, fetch_mb=nbytes / 1e6,
                    )
        for name, total in seconds.items():
            kernel_phase_seconds.observe(total, phase=name.split(".")[1])

    def _batch_derived(self) -> _BatchDerived:
        """What a pass derives from the row state of the resident batch's
        rows: kept with the record while the same row vector comes again
        (only _pack_rows writes the columns read here, and a pass that
        packs a row of the batch holds a new record), built anew
        otherwise."""
        rec = self.batch
        if rec.derived is not None:
            return rec.derived
        rows_np = rec.rows_np
        st = self._st
        n = len(rows_np)
        reps_sel = st["replicas"][rows_np]
        strat_sel = st["strategy"][rows_np]
        is_dup = strat_sel == S_DUPLICATED
        max_prev = int(st["prev_counts"][rows_np].max(initial=0))
        wide_rows = 0
        if self._wide_prev is not None or self._cell_bytes > 1:
            first = st["prev_sites"][rows_np, 0]
            slots = -1 - first[first < 0]
            if slots.size:
                max_prev = max(max_prev, int(self._wide_prev[slots].max()))
            wide_rows = int(
                ((first < 0) | (~is_dup & (reps_sel > NARROW_CELL_MAX))).sum()
            )
        d = rec.derived = _BatchDerived(
            rows_np,
            list(map(self._terms.__getitem__, rows_np.tolist())),
            int(reps_sel.max(initial=0)),
            max_prev,
            wide_rows,
            bool((strat_sel == AGGREGATED).any()),
            is_dup,
            bool(is_dup.any() or (reps_sel == 0).any()),
            n == self.n_rows
            and np.array_equal(rows_np, np.arange(n, dtype=np.int32)),
        )
        self._derived_outcome = "built"
        return d

    def _hold(
        self, old, problems, compiled, rows_np, select, unique, epoch,
    ) -> None:
        """Replace the resident batch's record ``old`` (the one the pass
        began with) by an unarmed one. What hangs off it by its row vector
        is kept where the rows are the same array (no row of the batch was
        packed since)."""
        rec = ResidentBatch(
            problems, compiled, rows_np, select, unique, self._pass, epoch,
        )
        if old is not None and old.rows_np is rows_np:
            rec.derived, rec.terms = old.derived, old.terms
            rec.select_rows = old.select_rows
        self.batch = rec

    def _replays(self, compiled, moved, selections) -> bool:
        """THE replay decision of a batch the engine diffed against the
        resident one: the untouched rows' answers are served from the host
        mirrors only while they are what a full pass would answer. That
        needs the resident rows, no row twice among them (a key twice in
        the batch: its positions depend on one another, which only a full
        pass keeps), mirrors that cover them (the record's epoch is the
        mirrors'), the snapshot generation the batch was armed at (a
        moved generation moves every answer), the estimators' folded
        answers, no preemption plane (preempt_select ranks victims across
        rows: a partial wave cannot see them), at most half of the rows
        moved (_few), and no moved position that is spread-constrained or
        host-selected (its selection is the full pass's to arrange)."""
        rec = self.batch
        engine = self.engine
        return (
            rec is not None
            and rec.rows_np is not None
            and rec.unique
            and len(rec.rows_np) == len(compiled)
            and _few(len(moved), len(compiled))
            and selections is None
            and self._host_entries is not None
            and self._host_meta is not None
            and rec.gen == getattr(engine, "_snapshot_gen", 0)
            and rec.epoch == self._mirror_epoch
            and getattr(engine, "preempt_source", None) is None
            and not self._estimates_moved()
            and not any(compiled[i].spread_single_term for i in moved.tolist())
        )

    def _schedule_pass(
        self, problems: Sequence, compiled: Sequence, moved=None,
        selections=None, select=None,
    ) -> list:
        self.replayed = False
        if moved is not None and self._replays(compiled, moved, selections):
            res = self._schedule_delta(problems, compiled, moved, select)
            if res is not None:
                return res
            # the sub pass moved the residents: the full pass below

        tmr: dict[str, float] = {}
        t0 = time.perf_counter()
        self._pass += 1
        self.new_trace_last_pass = False
        self._packed_this_pass = self._visited_this_pass = 0
        rec = self.batch
        if (
            rec is not None
            and rec.problems is problems
            and rec.compiled is compiled
        ):
            if rec.rows_np is None:
                # the rows were remapped under the same lists: walk them
                rec.rows_np, rec.unique = self.upsert(problems, compiled)
                self.batch = rec
            else:
                # same batch objects as last pass: rows are current
                # (upsert would skip every row anyway — this skips the
                # sweep)
                self._upsert_tally[0].inc(len(rec.rows_np))
            # ``live`` stands in for the last-used stamp of the rows no
            # pass visits; _compact honors it.
            rec.live = self._pass
            if select is None and selections is None:
                select = rec.select
            else:
                rec.select = select
        else:
            rows_np, unique = self.upsert(problems, compiled, moved)
            self._hold(
                rec, problems, compiled, rows_np, select, unique,
                -1,  # the pass's end sets the epoch that covers the rows
            )
        rows_np = self.batch.rows_np
        if selections is not None:
            tmr["sel_moved"] = self._apply_selections(rows_np, selections)
        t0 = self._phase(tmr, "upsert", t0)
        # packed-vs-replayed split of THIS pass: a replayed row kept its
        # state (the same object or one of equal content, or a position the
        # pass did not visit) without re-packing
        tmr["rows_visited"] = self._visited_this_pass
        tmr["rows_packed"] = self._packed_this_pass
        tmr["rows_replayed"] = max(
            len(problems) - self._packed_this_pass, 0
        )
        self._est_window = None
        self._caps_fold = None
        self._sync_device()
        if self._caps_fold is not None:
            # the static-assignment cap kernel ran for the profile table
            tmr["quota_profiles"], tmr["quota_cap_rows"] = self._caps_fold
        if self._est_window is not None:
            # the estimator stretch is the estimator.* spans' own: the
            # sync phase is what lies before and after it
            t_a, t0_after = self._est_window
            self._phase(tmr, "sync", t0, t_a)
            tmr["estimate"] = t0_after - t_a
            t0 = t0_after
        t0 = self._phase(tmr, "sync", t0)
        # the ordered affinity terms of the pass's multi-term rows, chosen
        # on the device after the uploads and before the Select stage and
        # the pass; the host stretch is the scheduler.terms span's
        self._terms_on_device(rows_np)
        if self._terms_mark[2]:
            tmr["terms_dispatch"] = self._terms_mark[1] - t0
            t0 = self._terms_mark[1]
        if select is not None and len(select):
            # the Select stage on the device, after the pass's uploads and
            # before the pass; its host stretch is the scheduler.select
            # span's, so the phases leave it out as they do the estimators'
            self._select_on_device(rows_np, select)
            tmr["select_dispatch"] = self._select_mark[1] - t0
            t0 = self._select_mark[1]
        n = len(rows_np)
        # adaptive chunk: a straggler batch of a few hundred rows should
        # not execute a full 4096-row chunk (pow2 snapping keeps the trace
        # count logarithmic)
        eff_chunk = min(self.chunk, _pow2(max(n, 256)))
        n_pad = max(eff_chunk, -(-n // eff_chunk) * eff_chunk)
        n_chunks = n_pad // eff_chunk
        d = self._batch_derived()
        # all-rows storm mode: the row-index upload is cached on device
        if d.is_all:
            if self._all_rows_n != n or self._all_rows_dev is None or (
                self._all_rows_dev.shape[0] != n_pad
            ):
                ar = np.full(n_pad, -1, np.int32)
                ar[:n] = np.arange(n, dtype=np.int32)
                self._all_rows_dev = jnp.asarray(ar)
                self._all_rows_n = n
            rows_dev = self._all_rows_dev
        else:
            ar = np.full(n_pad, -1, np.int32)
            ar[:n] = rows_np
            rows_dev = jnp.asarray(ar)
            self._last_upload_bytes += ar.nbytes

        c = self.engine.snapshot.num_clusters
        from .core import kernel_variant

        wide, fast = kernel_variant(
            max(self._avail_max, d.max_n), self._static_max, d.max_prev,
            d.max_n, c,
        )
        k_out = min(max(1, c), _pow2(max(d.max_n, 1)))
        bits_src = None
        if d.need_bits:
            bits_src = self._bits_src(lambda: rows_dev, eff_chunk, n_chunks)
        # table-validated mesh (see __init__): the row axis shards over
        # "b" on every pass — batches are padded to the pow2 chunk, so
        # the mesh-divisible bucket holds by construction. The cluster
        # axis additionally shards when the engine opted in AND c divides
        # the "c" extent. mesh_el is the mesh's canonical SHAPE: the
        # trace-key/manifest element (a Mesh object is process-local; its
        # shape is the compile identity across processes and boots).
        from ..parallel.mesh import mesh_shape as _mesh_shape

        mesh = self._mesh
        shard_c = False
        if mesh is not None:
            c_sz = mesh.shape.get("c", 1)
            shard_c = (
                getattr(self.engine, "shard_clusters", False)
                and c_sz > 1
                and c % c_sz == 0
            )
        mesh_el = _mesh_shape(mesh)
        # host->device transfer of THIS pass so far (state scatter/upload
        # + row indices): the multichip bench's steady-pass bound — a
        # steady storm must ship changed rows' bytes, never the grid
        tmr["upload_mb"] = self._last_upload_bytes / 1e6
        res = self._solve_dense(
            problems=problems, rows_np=rows_np, rows_dev=rows_dev, tmr=tmr,
            n=n, n_pad=n_pad, eff_chunk=eff_chunk, n_chunks=n_chunks,
            c=c, k_out=k_out, wide=wide, fast=fast, bits_src=bits_src,
            mesh=mesh, mesh_el=mesh_el, shard_c=shard_c,
            byte_wire=c <= 0xFFFF,
            # 21-bit entry packing: 2.625 B/entry when the site id fits
            # 13 bits and the count 8
            pack21=c <= (1 << 13) and self._cell_bytes == 1, t0=t0,
        )
        # this pass dispatched every row of the batch, so the mirrors now
        # cover them at the current epoch — the replay fence
        self.batch.epoch = self._mirror_epoch
        return res

    #: full-pass buffer-tuning attributes frozen across a delta sub-pass:
    #: a few-thousand-row delta must never shrink the caps the next full
    #: storm dispatches at (every distinct cap pair is an XLA trace)
    _TUNE_ATTRS = (
        "_last_total", "_m_cap_cur", "_shrink_desire", "_d_cap_cur",
        "_last_changed", "_last_dtotal", "_delta_live",
    )

    def _schedule_delta(self, problems, compiled, moved, select):
        """Partial pass: pack + dispatch ONLY the ``moved`` positions,
        replay every other row's result from the host mirrors (_replays
        decided it may). Returns None where the sub pass reallocated the
        residents or grew the table (the replay base is gone), and the
        caller runs the full pass."""
        rec = self.batch
        n = len(problems)
        t_all = time.perf_counter()
        rows_full = rec.rows_np
        n_sub = int(moved.size)
        quota = self._quota_pass
        if n_sub == 0:
            # pure replay: nothing changed — serve the whole batch from
            # the mirrors without touching the device (but for a quota
            # generation that moved: one admission over the batch's rows)
            if quota is not None:
                self._admit_on_device(rows_full, None)
            self._pass += 1
            self.new_trace_last_pass = False
            self._packed_this_pass = self._visited_this_pass = 0
            self._hold(
                rec, problems, compiled, rows_full, select, rec.unique,
                rec.epoch,
            )
            self._upsert_tally[0].inc(n)
            tmr: dict[str, float] = {
                "rows_visited": 0.0,
                "rows_packed": 0.0,
                "rows_replayed": float(n),
                "dirty_rows": 0.0,
            }
            res = self._replay_result(problems, tmr)
            self._phase(tmr, "post", t_all)
            self.last_breakdown = tmr
            self.replayed = True
            return res
        idx = moved.tolist()
        sub_p = [problems[i] for i in idx]
        sub_c = [compiled[i] for i in idx]
        epoch = self._mirror_epoch
        cap_before = self.cap
        tune = tuple(getattr(self, a) for a in self._TUNE_ATTRS)
        # virgin tuning state for the sub dispatch: demand-sized caps
        # keyed per pow2 sub-size bucket, so a settle train of equal-size
        # deltas converges to one trace instead of thrashing the tuned
        # full-pass caps
        self._last_total = None
        self._m_cap_cur = None
        self._shrink_desire = (None, 0)
        self._d_cap_cur = None
        self._last_changed = None
        self._last_dtotal = None
        self._delta_live = False
        # the sub pass admits nothing: admission is over the batch
        self._quota_pass = None
        try:
            self._schedule_pass(sub_p, sub_c)
        finally:
            self._quota_pass = quota
            for a, v in zip(self._TUNE_ATTRS, tune):
                setattr(self, a, v)
        if (
            self._mirror_epoch != epoch
            or self.cap != cap_before
            or self.batch is None
        ):
            # a resident/mirror realloc (or table growth) happened inside
            # the sub pass: the replay base for the untouched rows is
            # gone — hand back to the caller for a full pass
            return None
        sub_rows = self.batch.rows_np
        rows_new, unique = rows_full, rec.unique
        if not np.array_equal(sub_rows, rows_full[moved]):
            rows_new = rows_full.copy()
            rows_new[moved] = sub_rows
            unique = _distinct(rows_new, self.n_rows)
        tmr = self.last_breakdown  # the sub pass's phase breakdown
        tmr["rows_replayed"] = float(n - n_sub)
        tmr["dirty_rows"] = float(n_sub)
        # the moved rows are never spread-constrained (_replays), so the
        # batch's device-selected positions are the engine's
        self._hold(
            self.batch, problems, compiled, rows_new, select, unique,
            self._mirror_epoch,
        )
        self._upsert_tally[0].inc(n - n_sub)
        if quota is not None:
            self._admit_on_device(rows_new, None, changed=(rows_full, moved))
        t0 = time.perf_counter()
        res = self._replay_result(problems, tmr)
        self._phase(tmr, "post", t0)
        self.replayed = True
        return res

    def _replay_result(self, problems, tmr):
        """Batch result for the resident batch's rows built entirely from
        the host mirrors (entry runs + meta words) — the replay half of a
        delta pass. The mirrors cover every row of the batch by induction:
        each row was dispatched by the pass that established the mapping
        (or a later one), and the _mirror_epoch fence rejects any realloc
        in between."""
        n = len(problems)
        rows_full = self.batch.rows_np
        d = self._batch_derived()
        eff_chunk = min(self.chunk, _pow2(max(n, 256)))
        n_pad = max(eff_chunk, -(-n // eff_chunk) * eff_chunk)
        bits_src = None
        if d.need_bits:
            # over the FULL batch's rows (a replayed Duplicated row's
            # consumer needs the whole batch's bitsets, not the dirty
            # sub-batch's); the row-index upload waits for the first
            # access: most delta batches never decode a Duplicated row
            def rows_dev():
                ar = np.full(n_pad, -1, np.int32)
                ar[:n] = rows_full
                return jnp.asarray(ar)

            bits_src = self._bits_src(rows_dev, eff_chunk, n_pad // eff_chunk)
        return self._result_list(problems, d, bits_src, n_pad)

    def _result_list(
        self, problems, d: _BatchDerived, bits_src, n_pad: int
    ) -> "_FleetResultList":
        """The pass's answers over the host mirrors (meta words + entry
        runs) for the rows of ``d``."""
        meta_sel = self._host_meta[d.rows_np]
        self._result_gen += 1
        sb = 8 * self._cell_bytes
        batches = [
            _FleetBatch(
                self.engine.snapshot.names, self._host_entries, d.rows_np,
                bits_src, self, self._result_gen, self._dev_term_sel, sb,
            )
        ]
        return _FleetResultList(
            problems, d.terms, batches, n_pad,
            (meta_sel & ((1 << sb) - 1)).astype(np.int64),
            (meta_sel >> sb) & 1, (meta_sel >> (sb + 1)) & 1, d.is_dup,
        )

    def _bits_src(self, rows_dev, chunk: int, n_chunks: int):
        """Lazy feasibility-bitset thunk of a batch: it captures the
        PASS-TIME device arrays (immutable), so a consumer decoding a
        Duplicated result later gets this pass's sets even if the live
        tables have since been rebuilt. ``rows_dev`` is itself a thunk
        (the row indices on the device). Dispatched at most once per
        batch, on the first feasible/cluster access (_FleetBatch)."""
        _tables = self._dev_tables
        _state = (*self._dev_state, *self._wide_args())
        _wide_shape = self._wide_shape()

        def bits_src():
            from ..parallel.mesh import mesh_shape as _bits_mesh_shape

            rows = rows_dev()
            # the signature must carry every shape the trace closes over:
            # the cp-table capacity (slot growth re-traces), the
            # rows-buffer length, and the state cap
            key = (
                "B", chunk, n_chunks, _tables[0].shape,
                int(rows.shape[0]), int(_state[0].shape[0]),
                # the wide table, where the batch's table held one
                _wide_shape,
                # canonical mesh shape: the bits inputs commit to the
                # mesh (replicated), so each shape is a distinct
                # executable — a bool here let a mesh=2 manifest
                # fake-warm a mesh=8 boot
                _bits_mesh_shape(self._mesh),
            )
            if self._mark_trace(*key) and self._mesh is None:
                # meshed dispatches stay manifest-UNRECORDED: the kernel
                # has no mesh static, so a replay could only compile the
                # single-device form and would seed this key as falsely
                # warmed (see _quota_admission)
                self._record_trace(
                    "fleet_bits", key, (*_tables, rows, *_state),
                    chunk=chunk, n_chunks=n_chunks,
                )
            return _fleet_bits(
                *_tables, rows, *_state, chunk=chunk, n_chunks=n_chunks,
            )

        return bits_src

    def _alloc_resident(self, shape, dtype, mesh, *, c_axis=False):
        """Zeroed resident born on the solve's sharding layout (rows over
        mesh axis "b", optionally clusters over "c"): donation aliases
        input->output only when the shardings agree, so a resident must
        START on the layout the kernels pin their outputs to — otherwise
        the first meshed pass silently copies instead of aliasing."""
        if mesh is None:
            return jnp.zeros(shape, dtype)
        axes = ["b"] + [None] * (len(shape) - 1)
        if c_axis and len(shape) > 1:
            axes[1] = "c"
        return jnp.zeros(
            shape, dtype, device=NamedSharding(mesh, P(*axes))
        )

    def _e_key(
        self, chunk: int, n_chunks: int, k_out: int, e_cap: int,
        byte_wire: bool, pack21: bool,
    ) -> tuple:
        """THE ``_fleet_entries`` trace signature, shared by the exact
        phase-B fetch and the speculative dispatch in ``_solve_dense``.
        The two sites used to compose the cluster-count element
        differently (``self._res_dense.shape[1]`` vs the pass-local
        ``c``), so the same trace could be ledgered under two keys —
        spuriously flipping ``new_trace_last_pass`` (and double-entering
        the manifest). Keyed on the resident's OWN shape: that is the
        array the trace closes over. The resident's mesh layout rides
        along — a row-sharded dense resident compiles a different
        (gather-collective-bearing) executable than a single-device one."""
        return (
            "E", self._res_dense.shape[0], self._res_dense.shape[1],
            chunk, n_chunks, k_out, e_cap, byte_wire, pack21,
            self._resident_mesh, self._cell_bytes,
        )

    @property
    def _entries_mesh(self):
        """Mesh arg for a phase-B dispatch: the mesh the dense resident
        was allocated on (None when it was born single-device)."""
        return self._mesh if self._resident_mesh is not None else None

    def _mark_entries_trace(
        self, rows_dev, *, chunk, n_chunks, k_out, e_cap, byte_wire, pack21,
    ) -> None:
        """Ledger + manifest entry for a ``_fleet_entries`` dispatch."""
        key = self._e_key(chunk, n_chunks, k_out, e_cap, byte_wire, pack21)
        if self._mark_trace(*key):
            self._record_trace(
                "fleet_entries", key, (self._res_dense, rows_dev),
                chunk=chunk, n_chunks=n_chunks, k_out=k_out, e_cap=e_cap,
                byte_wire=byte_wire, pack21=pack21,
                mesh=self._entries_mesh, cell_bytes=self._cell_bytes,
            )

    def _fetch_fold_exact(
        self, rows, counts, *, route, eff_chunk, k_out, byte_wire, pack21,
    ) -> int:
        """Dispatch an exact phase B over ``rows``, fetch its entry wire,
        and fold the full runs into the host mirror. The entry cap is
        host-summed from ``counts`` so overflow is structurally
        impossible. The stretch, dispatch to fold, is kept for the
        ``kernel.entries`` span under ``route`` (overflow | exact).
        Returns the fetched byte count."""
        e_want = int(counts.sum())
        m_pad_b = max(2048, _pow2(len(rows)))
        b_chunk = min(eff_chunk, m_pad_b)
        rows_b = np.full(m_pad_b, -1, np.int32)
        rows_b[: len(rows)] = rows
        e_cap = _cap_round(max(e_want, 1))
        t_b = time.perf_counter()
        rows_b_dev = jnp.asarray(rows_b)
        self._mark_entries_trace(
            rows_b_dev, chunk=b_chunk, n_chunks=m_pad_b // b_chunk,
            k_out=k_out, e_cap=e_cap, byte_wire=byte_wire,
            pack21=pack21 and byte_wire,
        )
        flat2 = _fleet_entries(
            self._res_dense,
            rows_b_dev,
            chunk=b_chunk,
            n_chunks=m_pad_b // b_chunk,
            k_out=k_out,
            e_cap=e_cap,
            byte_wire=byte_wire,
            pack21=pack21 and byte_wire,
            mesh=self._entries_mesh,
            cell_bytes=self._cell_bytes,
        )
        raw2 = np.asarray(flat2)
        total2, stream = _decode_entry_wire(
            raw2, e_cap, byte_wire, pack21, self._cell_bytes
        )
        assert total2 == e_want, (total2, e_want)
        from .. import native

        native.fold_entries(
            self._host_entries, rows, counts, np.asarray(stream, np.int32)
        )
        self._entry_marks.append(
            (route, t_b, time.perf_counter(), len(rows), e_want, raw2.nbytes)
        )
        return raw2.nbytes

    def _solve_dense(
        self, *, problems, rows_np, rows_dev, tmr, n, n_pad, eff_chunk,
        n_chunks, c, k_out, wide, fast, bits_src, mesh, mesh_el, shard_c,
        byte_wire, pack21, t0,
    ) -> "_FleetResultList":
        """Two-phase solve: _fleet_pass (divide + dense diff, ~13 KB wire
        on a steady pass) and, only when rows changed, _fleet_entries over
        exactly those rows with an exactly-sized entry buffer (no
        overflow rerun by construction)."""
        d = self._batch_derived()
        has_agg, is_all = d.has_agg, d.is_all
        cb = self._cell_bytes
        sb = 8 * cb  # bits of a count in the meta and entry words
        marks0 = len(self._entry_marks)  # this pass's phase-B stretches
        if (
            self._res_dense is None
            or self._res_dense.shape != (self.cap, c)
            or self._resident_mesh != mesh_el
        ):
            self._res_dense = self._alloc_resident(
                (self.cap, c), jnp.uint8 if cb == 1 else jnp.uint16, mesh,
                c_axis=shard_c,
            )
            self._res_meta = self._alloc_resident(
                (self.cap,), jnp.int32, mesh
            )
            self._host_meta = np.zeros(self.cap, np.int32)
            self._resident_mesh = mesh_el
            self._mirror_epoch += 1
        # host entry mirror: width grows in place (no resident to reset —
        # the dense base is width-independent)
        k_res = max(self._k_res, k_out)
        if self._host_entries is None or self._host_entries.shape[0] != (
            self.cap
        ):
            self._host_entries = np.zeros((self.cap, k_res), np.int32)
        elif self._host_entries.shape[1] < k_res:
            self._host_entries = np.pad(
                self._host_entries,
                ((0, 0), (0, k_res - self._host_entries.shape[1])),
            )
        self._k_res = k_res

        # changed-meta buffer, tuned to the last pass's changed-row count;
        # an overflow costs one cheap _gather_meta round-trip
        def m_round(v: int) -> int:
            v = max(v, 1)
            q = -(-v // M_ROUND) * M_ROUND if v > 4096 else 4096
            return min(q, n_pad)

        def a_key(m: int, d: int) -> tuple:
            # mesh_el (the canonical mesh SHAPE, not a bool): partitioned
            # executables are distinct per mesh shape, and the manifest
            # key must never let a mesh=1 record seed a mesh=8 boot
            return (
                "A", self.cap, c, self._dev_tables[0].shape, eff_chunk,
                n_chunks, wide, fast, has_agg, is_all, m, d,
                mesh_el, shard_c, cb, self._wide_shape(),
            )

        # cap tuning, demand-based. Every distinct (m_cap, d_cap) pair is a
        # fresh XLA trace, so the policy is built around compile cost:
        # - GROW immediately when demand threatens a cap (overflow costs a
        #   round-trip or a full-row fold; growth normally lands in churn
        #   onset, which warm loops cover);
        # - SHRINK only on sustained desire: 2 consecutive passes when the
        #   smaller pair is already compiled (cheap switch), SHRINK_SUSTAIN
        #   when it would compile a new trace (a demand-regime shift like
        #   onset-overshoot -> steady churn; a wobble never qualifies, and
        #   warm loops that run past the window absorb the one compile —
        #   vote-delayed shrinks used to fire mid-storm: a 94s dispatch
        #   stall on the bench).
        # m demand: the changed-row count; d demand: the cell-delta count
        # with 1.5x headroom (dtotal wobbles a few percent pass to pass).
        needed_m = m_round(n)
        if self._last_changed is not None and (
            self._last_changed * 5 // 4 < n
        ):
            needed_m = min(needed_m, m_round(self._last_changed * 5 // 4))
        # the cell-delta word (cb + 2 bytes on the wire, an int32) holds
        # the site above its sb + 1 count bits
        d_on = byte_wire and c <= 1 << (min(8 * (cb + 2), 31) - (sb + 1))
        last = self._last_dtotal or 0
        d_need_min = (d_round(last * 9 // 8) if last else D_FLOOR) if d_on else 0
        d_need_tgt = (
            min(d_round(last * 3 // 2) if last else D_FLOOR,
                d_round(n_pad * 63))
            if d_on
            else 0
        )
        cur_m, cur_d = self._m_cap_cur, self._d_cap_cur
        if cur_m is None:
            m_cap, d_cap = needed_m, d_need_tgt
            self._shrink_desire = (None, 0)
        else:
            m_cap, d_cap = cur_m, (cur_d or 0) if d_on else 0
            grow_m = needed_m > cur_m
            grow_d = d_on and d_cap < d_need_min
            if grow_m:
                m_cap = needed_m
            if grow_d:
                d_cap = d_need_tgt
            if grow_m or grow_d:
                self._shrink_desire = (None, 0)
            else:
                want_m = min(needed_m, m_cap)
                want_d = (
                    d_need_tgt
                    if d_on and d_need_tgt * 2 <= d_cap
                    else d_cap
                )
                want = (want_m, want_d)
                if want != (m_cap, d_cap):
                    tgt, cnt = self._shrink_desire
                    cnt = cnt + 1 if tgt == want else 1
                    self._shrink_desire = (want, cnt)
                    sustain = (
                        2 if a_key(*want) in self._seen_traces
                        else SHRINK_SUSTAIN
                    )
                    if cnt >= sustain:
                        m_cap, d_cap = want
                        self._shrink_desire = (None, 0)
                else:
                    self._shrink_desire = (None, 0)
        self._m_cap_cur = m_cap
        self._d_cap_cur = d_cap if d_on else None

        t0 = self._phase(tmr, "prep", t0)
        wide_args = self._wide_args()
        if self._mark_trace(*a_key(m_cap, d_cap)):
            self._record_trace(
                "fleet_pass", a_key(m_cap, d_cap),
                (*self._dev_tables, rows_dev, *self._dev_state,
                 self._res_dense, self._res_meta, *wide_args),
                chunk=eff_chunk, n_chunks=n_chunks, wide=wide, fast=fast,
                has_aggregated=has_agg, all_rows=is_all, m_cap=m_cap,
                d_cap=d_cap, mesh=mesh, shard_c=shard_c, cell_bytes=cb,
            )
        # the dense residents are DONATED into the pass: detach the
        # attributes first so a dispatch that dies cannot leave deleted-
        # buffer references (the next pass reallocates a zeroed, mutually
        # consistent resident/mirror pair and re-reports every row)
        rd_in, self._res_dense = self._res_dense, None
        rm_in, self._res_meta = self._res_meta, None
        flat, rowbuf, rd, rm = _fleet_pass(
            *self._dev_tables,
            rows_dev,
            *self._dev_state,
            rd_in,
            rm_in,
            *wide_args,
            cell_bytes=cb,
            chunk=eff_chunk,
            n_chunks=n_chunks,
            wide=wide,
            fast=fast,
            has_aggregated=has_agg,
            all_rows=is_all,
            m_cap=m_cap,
            d_cap=d_cap,
            mesh=mesh,
            shard_c=shard_c,
        )
        self._res_dense, self._res_meta = rd, rm
        # speculative phase B: when the last pass saw churn AND could not
        # ride the delta wire, dispatch the entry compaction over A's
        # device-resident changed-row buffer BEFORE fetching A — B
        # executes back-to-back with A on device and its wire overlaps
        # A's decode, removing a round-trip from the churn critical path.
        # Steady passes (last_changed == 0) and delta-carried churn skip
        # it (the full-row sort + wire would be pure waste there).
        spec_flat = None
        spec_cap = 0
        spec_used = False
        # skip the speculation when the cell-delta wire is expected to carry
        # this pass (cap already grown past the last observed demand): the
        # full-row sort + wire would be pure waste — and an unfetched
        # speculative dispatch is WORSE than waste: its compile + execution
        # stay queued on device and surface in the NEXT pass's blocking
        # fetch.
        delta_expected = bool(
            d_cap and self._last_dtotal and self._last_dtotal <= d_cap
        )
        if (
            self._last_changed and self._last_total
            and not self._delta_live and not delta_expected
        ):
            spec_cap = _cap_round(self._last_total * 9 // 8)
            b_chunk = min(eff_chunk, m_cap)
            self._mark_entries_trace(
                rowbuf, chunk=b_chunk, n_chunks=m_cap // b_chunk,
                k_out=k_out, e_cap=spec_cap, byte_wire=byte_wire,
                pack21=pack21 and byte_wire,
            )
            spec_flat = _fleet_entries(
                self._res_dense,
                rowbuf,
                chunk=b_chunk,
                n_chunks=m_cap // b_chunk,
                k_out=k_out,
                e_cap=spec_cap,
                byte_wire=byte_wire,
                pack21=pack21 and byte_wire,
                mesh=self._entries_mesh,
                cell_bytes=cb,
            )
        t0 = self._phase(tmr, "dispatch", t0)
        if self._quota_pass is not None:
            # the batch's quota admission, behind the pass on the device
            # (it runs while the host fetches and decodes the pass's wire)
            # and outside the pass's phases: the stretch is the
            # scheduler.quota span's
            self._admit_on_device(rows_np, rows_dev)
            tmr["quota_dispatch"] = self._quota_mark[1] - t0
            t0 = self._quota_mark[1]
        # device fence at the span boundary: block_until_ready splits
        # phase A's on-device execute (+compile on a fresh trace) from the
        # wire/decode window — the fetch would block on the same event
        # anyway, so the fence costs nothing and buys the device/host
        # attribution.
        # The speculative B keeps running behind it — the fence waits on
        # A's output only, so the B-overlaps-A's-decode flow is preserved.
        flat.block_until_ready()
        t0 = self._phase(tmr, "device", t0)
        # A's wire and the speculative B's are fetched separately, so B's
        # transfer overlaps A's fetch+decode. Whether one fused fetch would
        # win on a chip local to the process is not measured on this
        # machine (ROADMAP Design 3).
        raw = np.asarray(flat)
        tmr["fetch_a"] = time.perf_counter() - t0
        fetched_bytes = raw.nbytes
        from .. import native

        total = native.le32(raw)
        nb = n_pad // 8
        changed_bits = np.unpackbits(
            raw[4 : 4 + nb], bitorder="little"
        )[:n_pad].astype(bool)
        ch_pos = np.flatnonzero(changed_bits)
        assert len(ch_pos) == total, (len(ch_pos), total)
        ch_rows = rows_np[ch_pos] if total else np.empty(0, np.int64)
        have_dcounts = total <= m_cap
        # a meta word is cb + 1 bytes on the wire, a cell delta cb + 2
        decode_meta = native.decode2 if cb == 1 else native.decode3
        decode_delta = native.decode3 if cb == 1 else native.decode4
        mb = cb + 1
        if have_dcounts:
            metas = decode_meta(raw[4 + nb : 4 + nb + mb * m_cap])[:total]
        else:
            # tuned buffer overflow (churn onset): one gather round-trip.
            # res_meta stores STATE only, so the per-row delta counts are
            # lost — this pass folds via the full-row phase B flow.
            m_pad_f = max(4096, _pow2(total))
            rows_f = np.full(m_pad_f, -1, np.int32)
            rows_f[:total] = ch_rows
            self._mark_trace(
                "G", self.cap, m_pad_f, self._resident_mesh, cb
            )
            mraw = np.asarray(
                _gather_meta(
                    self._res_meta, jnp.asarray(rows_f), cell_bytes=cb
                )
            )
            fetched_bytes += mraw.nbytes
            metas = decode_meta(mraw)[:total]
        self._last_changed = total
        # n_placed | unsched << sb | has_cand << sb + 1
        state = metas & ((1 << (sb + 2)) - 1)
        off_d = 4 + nb + mb * m_cap
        dtotal = native.le32(raw[off_d : off_d + 4]) if d_cap else None

        # fold: cell deltas when they fit, full-row phase B otherwise
        use_delta = False
        delta_rows = delta_cells = 0
        if total:
            self._host_meta[ch_rows] = state
            counts = (state & ((1 << sb) - 1)).astype(np.int64)
            e_total = int(counts.sum())
            self._last_total = e_total
            use_delta = bool(
                d_cap and have_dcounts and dtotal <= d_cap
            )
            if use_delta:
                t_b = time.perf_counter()
                # min(changed cells, 63) per changed row
                dch = metas >> (sb + 2)
                norm = dch <= 62
                nd_norm = dch[norm].astype(np.int64)
                assert int(nd_norm.sum()) == dtotal, (
                    int(nd_norm.sum()), dtotal,
                )
                if dtotal:
                    dstream = decode_delta(
                        raw[off_d + 4 : off_d + 4 + (cb + 2) * dtotal]
                    )
                    native.apply_deltas(
                        self._host_entries, ch_rows[norm], nd_norm, dstream,
                        cell_bits=sb,
                    )
                # decode+merge time only; an overflow-row fetch below
                # is a kernel.entries stretch of its own
                tmr["delta_fold"] = time.perf_counter() - t_b
                delta_rows = int(norm.sum())
                delta_cells = int(dtotal)
                rows_over = ch_rows[~norm]
                if rows_over.size:
                    # rows whose delta count overflowed the 6-bit meta
                    # field: fetch their full entry runs exactly
                    fetched_bytes += self._fetch_fold_exact(
                        rows_over, counts[~norm], route="overflow",
                        eff_chunk=eff_chunk, k_out=k_out,
                        byte_wire=byte_wire, pack21=pack21,
                    )
            elif not e_total:
                # every changed row lost its placements: clear the runs
                # (the fold below zero-fills rows it writes, covering the
                # mixed case without a second full sweep)
                self._host_entries[ch_rows] = 0
            if e_total and not use_delta:
                if (
                    spec_flat is not None
                    and total <= m_cap
                    and e_total <= spec_cap
                ):
                    # the speculative B covers exactly the changed rows
                    spec_used = True
                    t_b = time.perf_counter()
                    raw2 = np.asarray(spec_flat)
                    fetched_bytes += raw2.nbytes
                    total2, stream = _decode_entry_wire(
                        raw2, spec_cap, byte_wire, pack21, cb
                    )
                    assert total2 == e_total, (total2, e_total)
                    native.fold_entries(
                        self._host_entries, ch_rows, counts,
                        np.asarray(stream, np.int32),
                    )
                    self._entry_marks.append((
                        "speculative", t_b, time.perf_counter(), total,
                        e_total, raw2.nbytes,
                    ))
                else:
                    # exact fallback: churn onset (no speculation) or the
                    # speculative caps were too small
                    fetched_bytes += self._fetch_fold_exact(
                        ch_rows, counts, route="exact", eff_chunk=eff_chunk,
                        k_out=k_out, byte_wire=byte_wire, pack21=pack21,
                    )
        else:
            self._last_total = 0
        if spec_flat is not None and not spec_used:
            # speculation mispredicted (the pass folded another way): block
            # it out NOW and account the cost in this pass — an unfetched
            # dispatch would otherwise drain into the next pass's fetch
            t_b = time.perf_counter()
            spec_flat.block_until_ready()
            self._entry_marks.append(
                ("drained", t_b, time.perf_counter(), 0, 0, 0)
            )
        self._delta_live = use_delta
        if d_cap:
            self._last_dtotal = int(dtotal)
        t0 = self._phase(tmr, "fetch", t0)
        tmr["fetch_mb"] = fetched_bytes / 1e6
        tmr["changed_rows"] = float(total)
        # the wire home: rows on the cell-delta wire and the cells it
        # carried; rows phase B brought home (the entry wire)
        entry_rows = sum(m[3] for m in self._entry_marks[marks0:])
        tmr["delta_rows"] = float(delta_rows)
        tmr["delta_cells"] = float(delta_cells)
        tmr["entry_rows"] = float(entry_rows)
        t_delta, t_entries = self._wire_tally
        t_delta.inc(delta_rows)
        t_entries.inc(entry_rows)
        self._cells_tally.inc(delta_cells)
        if spec_flat is not None:
            self._spec_tally["used" if spec_used else "drained"].inc()

        res = self._result_list(problems, d, bits_src, n_pad)
        self._phase(tmr, "post", t0)
        self.last_breakdown = tmr
        return res
