"""Preemption kernels: plane-wide victim selection as ONE tensor op.

Ref: the reference schedules under sufficiency — priority exists on
PropagationPolicy (policy.go getHighestPriorityPropagationPolicy) but
orders only policy MATCHING; no reference deployment preempts at the
binding tier. The scarcity plane (ISSUE 14 / ROADMAP item 4) closes that
gap the repo way: when a high-priority wave cannot fit, the whole
plane's victim selection runs as one batched kernel — the
cohort-predicate style of ``ops.masks.first_fit_group`` — instead of a
per-binding host loop, and victims route through PR 7's graceful-
eviction machinery (condition -> taint -> NoExecute path).

THE selection rule (the numpy oracle ``refimpl/preempt_np.py``
implements it as the reference would — a sequential loop over victims
maintaining per-class unmet demand — sharing no code with this kernel):

- Demanders are bindings with ``priority > 0`` whose solve answered
  "available replicas are not enough"; each contributes
  ``shortfall x per-replica request`` of unmet demand to its priority
  class.
- Candidate victims are BOUND bindings; a victim may only serve demand
  from classes STRICTLY above its own priority (never equal-or-higher —
  a priority-10 binding is never displaced for another priority-10).
- Victims are taken lowest priority first; within a class, largest
  displacement weight (total assigned replicas) first — covering the
  demand with the FEWEST displacements — with arrival order (row index)
  as the final tiebreak. Whole bindings are displaced (the graceful-
  eviction unit), so freed capacity is the victim's full assignment.
- A victim is selected iff, at its place in that order, SOME resource
  dim it frees still has unmet demand from the classes above it. The
  batched form is a prefix cumsum: selected(v) iff
  ``exists r: freed[v,r] > 0 and cum_excl[v,r] < demand_gt(prio_v)[r]``
  where ``cum_excl`` sums freed capacity over ALL earlier victims in
  the sort order. The full prefix equals the selected-only prefix: an
  unselected victim only inflates dims whose demand the prefix already
  met, and met dims stay met (cumsum is nondecreasing) — the same
  holds-its-place-in-line algebra as ``quota_admit``'s FIFO prefix.

The kernel returns the victim mask plus the per-cluster freed-capacity
tensor ``[C, R]`` (victim assignment x per-replica request, summed over
selected victims) — the engine min-merges it back into availability and
re-solves the demanders IN THE SAME PASS, so a scarcity storm costs one
extra batched solve, not a settle round-trip.

Pure integer math (no float64, no host round-trips, no captured consts
— graftlint IR001-IR005 audit via the entry-point registry). ``mesh``
shards the binding axis over "b" exactly like the fleet kernels; the
mesh static is part of the compile identity. Demand/freed rows are
clamped by the packing layer (``ops.quota.DEMAND_CLAMP``) so a plane-
wide cumsum can never overflow int64 — ``preempt_select`` asserts the
same row bound ``quota_admit`` does.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .quota import MAX_ADMIT_ROWS

#: priority values must fit the packed sort key beside the displacement
#: weight and row index: prio in [0, 2^20), weight < 2^20, B <= 2^17
MAX_PRIORITY = (1 << 20) - 1
MAX_WEIGHT = (1 << 20) - 1


@partial(jax.jit, static_argnames=("mesh",))
def preempt_select(
    prio,  # int32[B]: per-binding priority class
    demand,  # int64[B, R]: unmet demand (0 for non-demanders; clamped)
    freed,  # int64[B, R]: capacity a victim would free (0 otherwise)
    victim_ok,  # bool[B]: eligible victim (bound, not itself a demander)
    weight,  # int32[B]: displacement weight (total assigned replicas)
    assigned,  # int32[B, C]: current per-cluster assignment
    requests,  # int64[B, R]: per-replica requests
    *,
    mesh=None,  # jax.sharding.Mesh with axes ("b", "c") — None = single
):
    """ONE plane-wide victim selection. Returns ``(victims bool[B],
    freed_caps int64[C, R])``. Rows that are neither demanders nor
    eligible victims (padding included: all-zero rows) select nothing
    and free nothing."""
    b, r = demand.shape
    assert b <= MAX_ADMIT_ROWS, (b, MAX_ADMIT_ROWS)

    def shard(a, *axes):
        if mesh is None:
            return a
        return lax.with_sharding_constraint(a, NamedSharding(mesh, P(*axes)))

    def repl(a):
        """Replicate a global-scan input: the sorts/cumsums below are
        plane-wide compactions, and the CPU SPMD partitioner miscompiles
        prefix scans whose inputs inherit row sharding (the PR 9 guard —
        fleet.py wire builds carry the same constraint)."""
        if mesh is None:
            return a
        return lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(*((None,) * a.ndim)))
        )

    prio = shard(prio, "b")
    demand = shard(demand, "b", None)
    freed = shard(freed, "b", None)
    victim_ok = shard(victim_ok, "b")
    weight = shard(weight, "b")
    assigned = shard(assigned, "b", None)
    requests = shard(requests, "b", None)

    # --- demand by priority class, as a descending-priority prefix sum:
    # demand_gt(q) = total demand of rows with prio > q. Sorting rows by
    # prio DESC and cumsumming demand gives, at each sorted position,
    # the demand of every strictly-higher class up to prio ties; the
    # per-victim lookup below binary-searches the first position whose
    # prio <= q, whose EXCLUSIVE cumsum is exactly demand_gt(q).
    p64 = prio.astype(jnp.int64)
    idx64 = jnp.arange(b, dtype=jnp.int64)
    d_order = jnp.argsort(repl(-(p64 * b) - (b - 1 - idx64)))
    d_prio = repl(p64[d_order])
    d_demand = repl(demand[d_order])
    d_cum = jnp.cumsum(d_demand, axis=0)
    d_cum_excl = d_cum - d_demand

    # --- victim sort: (prio asc, weight desc, index asc) packed into one
    # int64 key; ineligible rows sort to the far end via a prio above
    # every real class
    w64 = jnp.clip(weight.astype(jnp.int64), 0, MAX_WEIGHT)
    v_prio = jnp.where(victim_ok, p64, jnp.int64(MAX_PRIORITY + 1))
    v_key = (
        v_prio * ((MAX_WEIGHT + 1) * b)
        + (MAX_WEIGHT - w64) * b
        + idx64
    )
    v_order = jnp.argsort(repl(v_key))
    v_freed = repl(freed[v_order])
    v_cum = jnp.cumsum(v_freed, axis=0)
    v_cum_excl = v_cum - v_freed
    v_ok = victim_ok[v_order]
    v_p = p64[v_order]

    # demand_gt(prio_v): first descending-prio position with prio <= q is
    # found by searching the NEGATED (ascending) key space
    pos = jnp.searchsorted(-d_prio, -v_p, side="left")
    d_gt = d_cum_excl[jnp.minimum(pos, b - 1)]
    d_gt = jnp.where((pos < b)[:, None], d_gt, d_cum[b - 1])

    sel_sorted = v_ok & (
        (v_freed > 0) & (v_cum_excl < d_gt)
    ).any(axis=1)
    victims = jnp.zeros((b,), bool).at[v_order].set(sel_sorted)

    # freed capacity lands on the victims' clusters: the [B,C]x[B,R]
    # contraction over B in exact int64, as one multiply + row reduction
    # per resource dim (R is small and static; unrolled under jit). Not a
    # dot: the TPU compiler has no 64-bit integer dot and refuses one.
    sel_assigned = jnp.where(victims[:, None], assigned, 0).astype(jnp.int64)
    freed_caps = jnp.stack(
        [
            (sel_assigned * requests[:, d][:, None]).sum(axis=0)
            for d in range(r)
        ],
        axis=1,
    )
    if mesh is not None:
        freed_caps = lax.with_sharding_constraint(
            freed_caps, NamedSharding(mesh, P(None, None))
        )
    return victims, freed_caps


# row_coupled: the graftlint-dep delta-safety declaration — victim
# selection is cross-row by design (plane-wide priority sorts and
# cumulative freed-capacity scans over B, plus the row-contracting
# freed-caps einsum); never delta-replayable. IR006 verifies the
# coupling is still present, see tools/graftlint/dep.py
preempt_select.row_coupled = True
