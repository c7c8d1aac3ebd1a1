"""SelectClusters on the device: the Select stage of spread-constrained
rows as batched tensor math.

``scheduler/spread.py`` and ``scheduler/groups.py`` are the semantics (and
the oracle the tests hold this module to, bit for bit); this module is the
same selection for a whole batch of rows at once, with no per-row Python:

- the order (score desc, credited availability desc, index asc) is ONE
  multi-key sort of ``[rows, C]``; everything after it works in that sorted
  layout and one single-operand sort carries the answer back to cluster
  order;
- the cluster constraint (select_by_cluster_constraint) is a prefix of the
  order plus the swap-repair as a bounded loop over the ``update`` slot, the
  remainder kept in slot layout so "first maximum of the rest" is the
  minimum slot among the maxima;
- the region constraint (select_by_topology_groups): group membership from
  ``region_of``, calc_group_score as segmented sums along the order, and the
  group DFS as an ENUMERATED SUBSET TABLE over the groups sorted by
  (value asc, weight desc, name asc): a subset is a recorded path iff it is
  feasible and no proper position-prefix of it is; the winner is the
  maximum by (weight sum desc, value sum desc, discovery id asc), replaced
  by the shortest recorded path that is a proper prefix of the winner's
  groups in (weight desc, name asc) order (what _prioritize_paths' scan
  comes to). The table holds all subsets of ``R_CAP`` positions in DFS
  discovery order; a snapshot with more regions keeps the host selection.

Integer width: orderings compare exact 32-bit values (credited =
availability + previous replicas fits uint32); every running sum clamps its
addends at the threshold it is compared with (a sum of min(a, t) reaches t
exactly when the sum of a does), and a Divided row rides the fleet only with
replicas <= fleet.replicas_bound(C) (65,535 below 16,384 members, 255 from
there on), so no sum leaves int32.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..api.policy import SpreadConstraint
from .groups import WEIGHT_UNIT
from .spread import (
    LOCALITY_SCORE,
    should_ignore_available_resource,
    should_ignore_spread_constraint,
)

#: most regions a snapshot may hold for its spread rows to select on the
#: device: the subset table has 2**R_CAP rows
R_CAP = 8

# constraint kinds a placement slot compiles to (column 0 of its params)
MODE_NONE = 0  # no effective constraint: the selection is every cluster
MODE_REGION = 1  # region (+ optional cluster) constraint: the group search
MODE_CLUSTER = 2  # cluster constraint alone
MODE_UNSUPPORTED = 3  # zone-/provider-/label-only: FitError upstream
N_PARAMS = 6  # mode, ignore-availability, region min/max, cluster min/max


def constraint_params(cp) -> tuple:
    """The N_PARAMS integers the kernel reads for one compiled placement:
    select_clusters_batch's dispatch on the constraint fields, decided once
    a placement instead of once a row."""
    pl = cp.placement
    if (
        not cp.spread_constraints
        or pl is None
        or should_ignore_spread_constraint(pl)
    ):
        return (MODE_NONE, 0, 0, 0, 0, 0)
    by_field = {sc.spread_by_field: sc for sc in cp.spread_constraints}
    dup = int(should_ignore_available_resource(pl))
    if "region" in by_field:
        region = by_field["region"]
        cluster = by_field.get(
            "cluster", SpreadConstraint(min_groups=0, max_groups=0)
        )
        return (
            MODE_REGION, dup, region.min_groups, region.max_groups,
            cluster.min_groups, cluster.max_groups,
        )
    if "provider" in by_field or "zone" in by_field or "cluster" not in by_field:
        return (MODE_UNSUPPORTED, dup, 0, 0, 0, 0)
    cluster = by_field["cluster"]
    return (MODE_CLUSTER, dup, 0, 0, cluster.min_groups, cluster.max_groups)


def region_count(snap) -> int:
    """Regions the snapshot's clusters name."""
    return sum(1 for n in snap.region_vocab._ids if n)


def regions_fit(snap) -> bool:
    """Whether the snapshot's regions fit the kernel's subset table: what
    decides, for the engine and the fleet table alike, that its spread rows
    select on the device."""
    return region_count(snap) <= R_CAP


def region_table(snap) -> np.ndarray | None:
    """int32[C]: 0 for a cluster without a region, else 1 + the rank of its
    region's NAME among the snapshot's regions (group-name tie-breaks are
    lexicographic). None where the regions do not fit (regions_fit)."""
    if not regions_fit(snap):
        return None
    names = sorted(n for n in snap.region_vocab._ids if n)
    rank = {n: k + 1 for k, n in enumerate(names)}
    rank[""] = 0
    by_id = np.asarray(
        [rank[n] for n in snap.region_vocab._ids], np.int32
    )
    return by_id[snap.region_ids]


@lru_cache(maxsize=None)
def subset_table(r_cap: int = R_CAP) -> tuple:
    """(bits int32[S], prefix float32[S, S]) over all S = 2**r_cap subsets
    of the sorted group positions, in the DFS's discovery order (so a
    subset's row index IS its discovery id relative to any other subset):
    ``bits[s]`` has bit p set where position p is in the subset;
    ``prefix[t, s]`` is 1 where t is a proper position-prefix of s (the
    empty subset is everyone's)."""
    seqs: list[tuple] = []

    def dfs(stack: tuple, begin: int) -> None:
        seqs.append(stack)
        for i in range(begin, r_cap):
            dfs(stack + (i,), i + 1)

    dfs((), 0)
    index = {s: k for k, s in enumerate(seqs)}
    bits = np.asarray([sum(1 << p for p in s) for s in seqs], np.int32)
    prefix = np.zeros((len(seqs), len(seqs)), np.float32)
    for s, k in index.items():
        for cut in range(len(s)):
            prefix[index[s[:cut]], k] = 1.0
    return bits, prefix


def _i32(x):
    return x.astype(jnp.int32)


def select_rows(
    feasible,  # bool[B, C] the row's feasibility BEFORE any selection
    prev,  # int32[B, C] previous replicas (>= 0)
    avail,  # int32[B, C] merged availability (>= 0)
    replicas,  # int32[B]
    params,  # int32[B, N_PARAMS] the row's placement's constraint_params
    region_of,  # int32[C] region_table
    sub_bits,  # int32[S] subset_table bits
    sub_prefix,  # float32[S, S] subset_table prefix relation
):
    """bool[B, C]: each row's SelectClusters result (all False = FitError;
    all True for a row whose placement has no effective constraint)."""
    b, c = feasible.shape
    r_cap = int(sub_bits.shape[0]).bit_length() - 1
    i32 = jnp.int32
    mode, dup = params[:, 0], params[:, 1] != 0
    r_min, r_max = params[:, 2], params[:, 3]
    c_min, c_max = params[:, 4], params[:, 5]

    with jax.named_scope("select.order"):
        hi = prev > 0  # the locality score: LOCALITY_SCORE or 0
        credited = avail.astype(jnp.uint32) + prev.astype(jnp.uint32)
        k_class = jnp.where(feasible, jnp.where(hi, i32(0), i32(1)), i32(2))
        iota_c = jnp.broadcast_to(jnp.arange(c, dtype=i32), (b, c))
        k_class, k_cred, cid, reg = lax.sort(
            (k_class, ~credited, iota_c,
             jnp.broadcast_to(region_of.astype(i32), (b, c))),
            dimension=1, num_keys=3,
        )
        feas = k_class < 2  # the first ``total`` slots, in the order
        hi = k_class == 0
        cred = ~k_cred
        total = feas.sum(axis=1, dtype=i32)
        pos = iota_c

    with jax.named_scope("select.cluster"):
        # select_by_cluster_constraint, for every row (rows of another mode
        # drop the result below): the first need_cnt slots, then the
        # swap-repair while the capacity falls short of the replicas
        need = replicas
        need_u = need.astype(jnp.uint32)
        need_cnt = jnp.minimum(jnp.where(c_max > 0, c_max, total), total)
        check = (mode == MODE_CLUSTER) & ~dup
        clamped = _i32(jnp.minimum(cred, need_u[:, None]))
        sum_ret = jnp.where(
            feas & (pos < need_cnt[:, None]), clamped, 0
        ).sum(axis=1, dtype=i32)
        # the loop matters only where a remainder exists to swap from
        steps = jnp.max(
            jnp.where(check & (need_cnt < total) & (sum_ret < need),
                      need_cnt, 0)
        )
        big = i32(c)

        def repair(t, carry):
            slot, sum_ret = carry
            update = need_cnt - 1 - t
            active = check & (update >= 0) & (sum_ret < need)
            in_rest = feas & (slot >= need_cnt[:, None])
            best_val = jnp.max(jnp.where(in_rest, cred, 0), axis=1)
            best_slot = jnp.min(
                jnp.where(in_rest & (cred == best_val[:, None]), slot, big),
                axis=1,
            )
            at_update = feas & (slot == update[:, None])
            upd_val = jnp.max(jnp.where(at_update, cred, 0), axis=1)
            swap = active & (best_val > upd_val)
            slot = jnp.where(
                swap[:, None] & at_update, best_slot[:, None],
                jnp.where(
                    swap[:, None] & (slot == best_slot[:, None]),
                    update[:, None], slot,
                ),
            )
            gain = _i32(jnp.minimum(best_val, need_u)) - _i32(
                jnp.minimum(upd_val, need_u)
            )
            return slot, sum_ret + jnp.where(swap, gain, 0)

        slot, sum_ret = lax.fori_loop(0, steps, repair, (pos, sum_ret))
        fits = (total >= jnp.maximum(c_min, 1)) & (dup | (sum_ret >= need))
        sel_cluster = feas & (slot < need_cnt[:, None]) & fits[:, None]

    with jax.named_scope("select.groups"):
        # group membership and calc_group_score, [B, C, R] fused into the
        # reductions; region ids run 1..r_cap in NAME order
        rid = jnp.arange(1, r_cap + 1, dtype=i32)
        member = feas[:, :, None] & (reg[:, :, None] == rid)
        value = member.sum(axis=1, dtype=i32)  # clusters a group
        exists = value > 0
        n_groups = exists.sum(axis=1, dtype=i32)
        n_hi = (member & hi[:, :, None]).sum(axis=1, dtype=i32)
        # Duplicated: the clusters whose credited covers the replicas
        covers = member & (cred >= need_u[:, None])[:, :, None]
        n_valid = covers.sum(axis=1, dtype=i32)
        hi_valid = (covers & hi[:, :, None]).sum(axis=1, dtype=i32)
        w_dup = n_valid * WEIGHT_UNIT + jnp.where(
            n_valid > 0, LOCALITY_SCORE * hi_valid // jnp.maximum(n_valid, 1), 0
        )
        # Divided: walk the group in order until both the member count and
        # the target are covered. Counts and sums only grow along the walk,
        # so it stops at member max(cmg, first k whose sum covers), and the
        # score sum there is LOCALITY_SCORE x the scored members among them
        # (scored members come first in the order)
        div = jnp.maximum(r_min, 1)
        target = (replicas + div - 1) // div
        addend = _i32(jnp.minimum(cred, target.astype(jnp.uint32)[:, None]))
        run = jnp.cumsum(
            jnp.where(member, addend[:, :, None], 0), axis=1, dtype=i32
        )
        sum_avail = run[:, -1, :]
        k_cover = (member & (run < target[:, None, None])).sum(
            axis=1, dtype=i32
        ) + 1
        cmg = jnp.maximum(c_min, r_min)
        k_stop = jnp.minimum(
            value, jnp.maximum(jnp.maximum(cmg[:, None], k_cover), 1)
        )
        short = sum_avail < target[:, None]
        w_div = jnp.where(
            short,
            sum_avail * WEIGHT_UNIT
            + LOCALITY_SCORE * n_hi // jnp.maximum(value, 1),
            target[:, None] * WEIGHT_UNIT
            + LOCALITY_SCORE * jnp.minimum(k_stop, n_hi)
            // jnp.maximum(k_stop, 1),
        )
        weight = jnp.where(exists, jnp.where(dup[:, None], w_dup, w_div), 0)

    with jax.named_scope("select.paths"):
        # the groups sorted by (value asc, weight desc, name asc), groups
        # without a feasible member last: each group's position
        v_a, v_b = value[:, :, None], value[:, None, :]
        w_a, w_b = weight[:, :, None], weight[:, None, :]
        e_a, e_b = exists[:, :, None], exists[:, None, :]
        name_lt = (rid[:, None] < rid[None, :])[None]  # a's name before b's
        a_first = jnp.where(
            e_a & e_b,
            (v_a < v_b) | ((v_a == v_b) & ((w_a > w_b) | ((w_a == w_b) & name_lt))),
            jnp.where(e_a == e_b, name_lt, e_a),
        )
        gpos = a_first.sum(axis=1, dtype=i32)  # [B, R]: position of group r
        p_iota = jnp.arange(r_cap, dtype=i32)
        at_pos = gpos[:, :, None] == p_iota  # [B, R(group), R(position)]
        v_pos = jnp.where(at_pos, value[:, :, None], 0).sum(axis=1, dtype=i32)
        w_pos = jnp.where(at_pos, weight[:, :, None], 0).sum(axis=1, dtype=i32)
        n_pos = jnp.where(at_pos, rid[None, :, None], 0).sum(axis=1, dtype=i32)
        # every subset's sums, length and validity
        in_sub = ((sub_bits[:, None] >> p_iota) & 1) != 0  # [S, R]
        s_len = in_sub.sum(axis=1, dtype=i32)
        s_top = jnp.where(in_sub, p_iota + 1, 0).max(axis=1)
        sv = jnp.zeros((b, in_sub.shape[0]), i32)
        sw = jnp.zeros((b, in_sub.shape[0]), i32)
        for p in range(r_cap):
            sv = sv + jnp.where(in_sub[None, :, p], v_pos[:, p, None], 0)
            sw = sw + jnp.where(in_sub[None, :, p], w_pos[:, p, None], 0)
        max_len = jnp.where(r_max > 0, r_max, n_groups)
        feasible_sub = (
            (s_top[None, :] <= n_groups[:, None])
            & (sv >= c_min[:, None])
            & (s_len[None, :] >= r_min[:, None])
            & (s_len[None, :] <= max_len[:, None])
        )
        # the DFS records a path at its first feasible prefix and goes no
        # further: a subset is recorded iff no proper prefix of it is feasible
        blocked = jnp.dot(
            feasible_sub.astype(jnp.float32), sub_prefix,
            preferred_element_type=jnp.float32,
        ) > 0.5
        rec = feasible_sub & ~blocked
        # the winner: weight desc, value desc, discovery id asc
        best_w = jnp.max(jnp.where(rec, sw, -1), axis=1)
        tie = rec & (sw == best_w[:, None])
        best_v = jnp.max(jnp.where(tie, sv, -1), axis=1)
        tie = tie & (sv == best_v[:, None])
        s_iota = jnp.arange(in_sub.shape[0], dtype=i32)
        win = jnp.min(jnp.where(tie, s_iota, in_sub.shape[0]), axis=1)
        w_mask = jnp.where(
            s_iota[None, :] == win[:, None], sub_bits[None, :], 0
        ).sum(axis=1, dtype=i32)
        # the shortest recorded proper prefix of the winner's groups in
        # (weight desc, name asc) order takes its place
        in_w = ((w_mask[:, None] >> p_iota) & 1) != 0  # [B, R(position)]
        ahead = (
            in_w[:, :, None] & in_w[:, None, :]
            & (
                (w_pos[:, :, None] > w_pos[:, None, :])
                | (
                    (w_pos[:, :, None] == w_pos[:, None, :])
                    & (n_pos[:, :, None] < n_pos[:, None, :])
                )
            )
        )
        w_rank = ahead.sum(axis=1, dtype=i32)  # [B, R(position)]
        w_len = in_w.sum(axis=1, dtype=i32)
        chosen = w_mask
        for k in range(r_cap - 1, -1, -1):
            head = jnp.where(
                in_w & (w_rank < k), i32(1) << p_iota, 0
            ).sum(axis=1, dtype=i32)
            recorded = (rec & (sub_bits[None, :] == head[:, None])).any(axis=1)
            chosen = jnp.where((k < w_len) & recorded, head, chosen)
        picked = (((chosen[:, None] >> gpos) & 1) != 0) & exists  # [B, R]
        n_picked = picked.sum(axis=1, dtype=i32)
        found = (
            (n_groups >= jnp.maximum(r_min, 1))
            & rec.any(axis=1)
            & (n_picked > 0)
        )

    with jax.named_scope("select.assemble"):
        # the best cluster of every chosen region, then the rest of their
        # members in the order up to the cluster maxGroups (0 keeps one a
        # region)
        of_picked = (member & picked[:, None, :]).any(axis=2)
        first_pos = jnp.min(
            jnp.where(member, pos[:, :, None], c), axis=1
        )  # [B, R]
        is_first = (member & (pos[:, :, None] == first_pos[:, None, :])).any(
            axis=2
        )
        heads = of_picked & is_first
        cand = of_picked & ~is_first
        want = n_picked + cand.sum(axis=1, dtype=i32)
        want = jnp.where(want > c_max, c_max, want)
        cand_rank = jnp.cumsum(_i32(cand), axis=1, dtype=i32)
        rest = want - n_picked
        sel_region = (
            heads | (cand & (cand_rank <= rest[:, None]))
        ) & found[:, None]

    with jax.named_scope("select.unsort"):
        sel = jnp.where(
            (mode == MODE_REGION)[:, None], sel_region,
            jnp.where((mode == MODE_CLUSTER)[:, None], sel_cluster, False),
        )
        # back to cluster order: the packed word sorts by cluster index
        back = lax.sort(cid * 2 + _i32(sel), dimension=1)
        return ((back & 1) != 0) | (mode == MODE_NONE)[:, None]
