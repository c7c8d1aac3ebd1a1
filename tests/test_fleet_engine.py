"""Device-resident fleet path: differential equivalence with the host path.

The fleet table (scheduler/fleet.py) re-implements Filter+Assign as one
fused resident-state program; these tests pin it to the general host path
(_schedule_host) — same placements, same errors, same feasible sets — over
randomized mixed-strategy fleets, plus the no-idx dispense mode, snapshot
swap-in-place, and the entry-buffer overflow fallback."""

import numpy as np
import jax.numpy as jnp
import pytest

import karmada_tpu.scheduler.fleet as fleet_mod
from karmada_tpu.ops.dispense import take_by_weight, take_by_weight_fast
from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot, TensorScheduler
from karmada_tpu.utils.builders import (
    aggregated_placement,
    duplicated_placement,
    dynamic_weight_placement,
    static_weight_placement,
    synthetic_fleet,
)
from karmada_tpu.utils.quantity import parse_resource_list


REQ = parse_resource_list({"cpu": "250m", "memory": "512Mi"})


def _mixed_problems(clusters, n, seed):
    rng = np.random.default_rng(seed)
    pls = [
        dynamic_weight_placement(),
        duplicated_placement(),
        static_weight_placement(
            {c.name: (i % 3) + 1 for i, c in enumerate(clusters[:10])}
        ),
        aggregated_placement(),
    ]
    out = []
    for i in range(n):
        prev_n = int(rng.integers(0, 5))
        prev_idx = rng.choice(len(clusters), prev_n, replace=False)
        out.append(
            BindingProblem(
                key=f"b{i}",
                placement=pls[i % 4],
                replicas=int(rng.integers(0, 40)),
                requests=REQ,
                gvk="apps/v1/Deployment",
                prev={
                    clusters[j].name: int(rng.integers(1, 9)) for j in prev_idx
                },
                fresh=bool(rng.random() < 0.2),
            )
        )
    return out


def _assert_same(slow, fast):
    for s, f in zip(slow, fast):
        assert s.success == f.success, (s.key, s.error, f.error)
        assert s.error == f.error, s.key
        assert s.clusters == f.clusters, (s.key, s.clusters, f.clusters)
        assert sorted(s.feasible) == sorted(f.feasible), s.key
        assert s.affinity_name == f.affinity_name, s.key


@pytest.mark.parametrize("seed", [1, 2])
def test_fleet_matches_host_path_mixed_strategies(seed):
    clusters = synthetic_fleet(50, seed=7)
    snap = ClusterSnapshot(clusters)
    problems = _mixed_problems(clusters, 300, seed)
    host = TensorScheduler(snap)
    slow = host._schedule_host(
        problems, [host._compiled(p.placement) for p in problems]
    )
    eng = TensorScheduler(snap)
    eng.fleet_threshold = 1
    fast = eng.schedule(problems)
    assert eng._fleet is not None, "fleet path did not engage"
    _assert_same(slow, fast)
    # repeat pass: identity fast path must return identical placements
    again = eng.schedule(problems)
    _assert_same(fast, again)
    # rebuilt problem objects (the controller case): fingerprint dedupe
    rebuilt = [
        BindingProblem(
            key=p.key, placement=p.placement, replicas=p.replicas,
            requests=p.requests, gvk=p.gvk, prev=p.prev, fresh=p.fresh,
        )
        for p in problems
    ]
    _assert_same(fast, eng.schedule(rebuilt))


def test_fleet_incremental_update_changes_only_touched_rows():
    clusters = synthetic_fleet(50, seed=7)
    snap = ClusterSnapshot(clusters)
    problems = _mixed_problems(clusters, 200, 3)
    eng = TensorScheduler(snap)
    eng.fleet_threshold = 1
    first = eng.schedule(problems)
    # mutate a handful of bindings (replicas change)
    changed = []
    for i in (5, 17, 101):
        p = problems[i]
        changed.append(
            BindingProblem(
                key=p.key, placement=p.placement,
                replicas=max(1, p.replicas + 3), requests=p.requests,
                gvk=p.gvk, prev=p.prev, fresh=p.fresh,
            )
        )
    problems2 = list(problems)
    for p in changed:
        problems2[int(p.key[1:])] = p
    second = eng.schedule(problems2)
    host = TensorScheduler(snap)
    want = host._schedule_host(
        problems2, [host._compiled(p.placement) for p in problems2]
    )
    _assert_same(want, second)


def test_update_snapshot_keeps_fleet_valid():
    clusters = synthetic_fleet(40, seed=9)
    snap = ClusterSnapshot(clusters)
    problems = _mixed_problems(clusters, 150, 4)
    eng = TensorScheduler(snap)
    eng.fleet_threshold = 1
    eng.schedule(problems)
    fleet_before = eng._fleet
    # capacity drift on the same cluster set
    for cl in clusters:
        rs = cl.status.resource_summary
        for d in list(rs.allocated):
            rs.allocated[d] = int(rs.allocated[d] * 1.5) + 1
    snap2 = ClusterSnapshot(clusters)
    assert eng.update_snapshot(snap2)
    got = eng.schedule(problems)
    assert eng._fleet is fleet_before  # table survived the swap
    fresh_engine = TensorScheduler(snap2)
    want = fresh_engine._schedule_host(
        problems, [fresh_engine._compiled(p.placement) for p in problems]
    )
    _assert_same(want, got)
    # cluster-set change must refuse the in-place swap
    snap3 = ClusterSnapshot(clusters[:-1])
    assert not eng.update_snapshot(snap3)


def test_entry_buffer_overflow_falls_back_to_safe_bound(monkeypatch):
    clusters = synthetic_fleet(30, seed=5)
    snap = ClusterSnapshot(clusters)
    problems = _mixed_problems(clusters, 120, 6)
    monkeypatch.setattr(fleet_mod, "E_ROUND", 16)
    eng = TensorScheduler(snap)
    eng.fleet_threshold = 1
    first = eng.schedule(problems)
    # result views are valid only until the next pass (generation-guarded):
    # snapshot pass 1 eagerly before re-scheduling
    first = [
        (r.success, r.error, dict(r.clusters), tuple(r.feasible), r.key)
        for r in first
    ]
    # lie about the last total so the tuned cap must overflow and retry
    eng._fleet._last_total = 1
    second = eng.schedule(problems)
    for (succ, err, clus, feas, key), f in zip(first, second):
        assert succ == f.success and err == f.error, key
        assert clus == f.clusters, (key, clus, f.clusters)
        assert sorted(feas) == sorted(f.feasible), key


def test_slot_eviction_survives_generational_placement_churn(monkeypatch):
    """Crossing the unique-placement cap with RETIRED placements must not
    rebuild the table per call: idle rows are reclaimed, their slots
    swept, and the SAME FleetTable keeps scheduling (delta base intact).
    Placements exceeding the cap while all still live do rebuild — that
    is the genuine capacity limit, not the cliff."""
    from karmada_tpu.utils.builders import static_weight_placement

    monkeypatch.setattr(fleet_mod, "MAX_SLOTS", 16)
    monkeypatch.setattr(fleet_mod, "MAX_SLOTS_HARD", 16)
    monkeypatch.setattr(fleet_mod, "CP_TABLE_MAX_BYTES", 0)
    clusters = synthetic_fleet(20, seed=3)
    snap = ClusterSnapshot(clusters)
    names = [c.name for c in clusters]
    eng = TensorScheduler(snap)
    eng.fleet_threshold = 1

    def gen_problems(gen: int):
        pls = [
            static_weight_placement({names[j]: j + k + 1 for j in range(5)})
            for k in range(10)  # 10 unique placements per generation
        ]
        return [
            BindingProblem(
                key=f"g{gen}_{i}", placement=pls[i % 10], replicas=4 + i % 7,
                requests={}, gvk="apps/v1/Deployment",
            )
            for i in range(40)
        ]

    tables = set()
    for gen in range(4):  # 40 uniques over the table's life vs cap 16
        probs = gen_problems(gen)
        for _ in range(6):  # age the previous generation past the window
            res = eng.schedule(probs)
        tables.add(id(eng._fleet))
        host = TensorScheduler(snap)
        want = host._schedule_host(
            probs, [host._compiled(p.placement) for p in probs]
        )
        _assert_same(want, res)
    # generations retire cleanly: one table (first gen fills 10/16; later
    # gens evict the retired ones instead of tripping the rebuild path).
    # At most the live generation + its not-yet-swept predecessor remain
    # (the sweep runs at the NEXT cap-pressure check).
    assert len(tables) == 1, "table rebuilt despite retirable slots"
    assert len(eng._fleet._cp_pl) <= 20, len(eng._fleet._cp_pl)


def test_batch_reuse_survives_compaction():
    """The batch-identity fast path skips upsert (and its last-used bump);
    a compaction sweep must still see those rows as live, not idle."""
    clusters = synthetic_fleet(30, seed=8)
    snap = ClusterSnapshot(clusters)
    problems = _mixed_problems(clusters, 600, 3)
    eng = TensorScheduler(snap)
    eng.fleet_threshold = 1
    for _ in range(8):  # advance _pass well past COMPACT_IDLE_PASSES
        eng.schedule(problems)
    ft = eng._fleet
    assert ft.batch.armed  # the fast path engaged
    keys_before = set(ft._key_row)
    assert not ft._compact()  # live batch: nothing to reclaim
    assert set(ft._key_row) == keys_before


def test_dispense_no_idx_mode_matches_sort_dispense():
    """Tie-heavy fuzz of with_idx=False (two-stage top_k) vs the exact
    3-key sort, including placed-site coverage of the returned top-k."""
    rng = np.random.default_rng(0)
    for trial in range(60):
        c = int(rng.integers(3, 120))
        num = int(rng.integers(0, 60))
        w = rng.choice(
            [0, 1, 2, 5, 7], size=c, p=[0.2, 0.3, 0.2, 0.2, 0.1]
        ).astype(np.int32)
        last = rng.integers(0, 4, c).astype(np.int32)
        init = np.zeros(c, np.int32)
        ref = np.asarray(
            take_by_weight(
                jnp.int32(num), jnp.asarray(w), jnp.asarray(last),
                jnp.asarray(init), True,
            )
        )
        k_top = min(c, 1 << max(1, max(1, num) - 1).bit_length())
        got, sites = take_by_weight_fast(
            jnp.int32(num), jnp.asarray(w), jnp.asarray(last),
            jnp.asarray(init), 23, 8, k_top, True,
            with_idx=False, return_sites=True,
        )
        got, sites = np.asarray(got), np.asarray(sites)
        assert np.array_equal(ref, got), (trial, c, num)
        placed = set(np.flatnonzero(got).tolist())
        assert placed <= set(sites.tolist()), (trial, placed)


def test_fleet_compacts_rows_of_deleted_bindings():
    """Create/delete churn must not grow the table without bound: rows idle
    past the compaction window are reclaimed before the table grows."""
    clusters = synthetic_fleet(10, seed=1)
    snap = ClusterSnapshot(clusters)
    eng = TensorScheduler(snap, chunk_size=64)
    eng.fleet_threshold = 1
    pl = dynamic_weight_placement()

    def gen(tag, n):
        return [
            BindingProblem(
                key=f"{tag}-{i}", placement=pl, replicas=3, requests=REQ,
                gvk="apps/v1/Deployment",
            )
            for i in range(n)
        ]

    caps = []
    for gen_i in range(12):  # each generation uses entirely fresh keys
        res = eng.schedule(gen(f"g{gen_i}", 48))
        assert all(r.success for r in res)
        caps.append(eng._fleet.cap)
    # without eviction cap would reach >= 12*48 rounded up; with the
    # 4-pass idle window it stays bounded by a few live generations
    assert eng._fleet.cap <= 512, caps
    assert eng._fleet.n_rows <= 48 * (eng._fleet.COMPACT_IDLE_PASSES + 2)


def test_fleet_lazy_results_expose_schedule_result_surface():
    clusters = synthetic_fleet(20, seed=2)
    snap = ClusterSnapshot(clusters)
    problems = [
        BindingProblem(
            key="w", placement=dynamic_weight_placement(), replicas=6,
            requests=REQ, gvk="apps/v1/Deployment",
        ),
        # zero-replica (non-workload): all feasible clusters, no counts
        BindingProblem(key="cfg", placement=duplicated_placement(),
                       replicas=0, requests={}, gvk="apps/v1/Deployment"),
    ]
    eng = TensorScheduler(snap)
    eng.fleet_threshold = 1
    res = eng.schedule(problems)
    assert res[0].success and sum(res[0].clusters.values()) == 6
    assert res[1].success and res[1].clusters == {}
    assert len(res[1].feasible) > 0


def test_delta_fetch_sequence_fuzz():
    """Multi-pass mutation fuzz for the delta-fetch machinery: random
    per-pass mutations (replica bumps, prev rewrites, fresh flips, NEW
    bindings, availability-only snapshot swaps, partial batches) must keep
    the fleet path identical to a fresh host-path run on EVERY pass — the
    resident entry base / host mirror / changed-bit protocol can never
    serve a stale placement."""
    rng = np.random.default_rng(123)
    clusters = synthetic_fleet(40, seed=21)
    snap = ClusterSnapshot(clusters)
    problems = _mixed_problems(clusters, 240, 11)
    eng = TensorScheduler(snap, chunk_size=64)
    eng.fleet_threshold = 1
    next_key = len(problems)
    for pass_no in range(8):
        op = pass_no % 4
        if op == 1:  # mutate ~10% of rows
            for i in rng.choice(len(problems), 24, replace=False):
                p = problems[i]
                problems[i] = BindingProblem(
                    key=p.key, placement=p.placement,
                    replicas=int(rng.integers(0, 40)), requests=p.requests,
                    gvk=p.gvk,
                    prev={
                        clusters[int(j)].name: int(rng.integers(1, 9))
                        for j in rng.choice(len(clusters), 2, replace=False)
                    } if rng.random() < 0.5 else {},
                    fresh=bool(rng.random() < 0.3),
                )
        elif op == 2:  # availability-only snapshot swap (token unchanged)
            for cl in clusters:
                rs = cl.status.resource_summary
                for dim, q in list(rs.allocated.items()):
                    cap = rs.allocatable.get(dim, 0)
                    rs.allocated[dim] = int(
                        min(max(0, q + int(rng.integers(-2, 3)) * max(1, cap // 100)), cap)
                    )
            snap = ClusterSnapshot(clusters)
            assert eng.update_snapshot(snap)
        elif op == 3:  # grow the fleet with new bindings
            for _ in range(16):
                problems.append(
                    BindingProblem(
                        key=f"b{next_key}",
                        placement=problems[int(rng.integers(0, 4))].placement,
                        replicas=int(rng.integers(0, 40)), requests=REQ,
                        gvk="apps/v1/Deployment",
                    )
                )
                next_key += 1
        # alternate full batches with partial ones (delta rows subset)
        if pass_no % 2 == 0:
            batch = problems
        else:
            idx = sorted(
                int(j) for j in rng.choice(len(problems), 96, replace=False)
            )
            batch = [problems[j] for j in idx]
        got = eng.schedule(batch)
        assert eng._fleet is not None, "fleet path did not engage"
        host = TensorScheduler(snap)
        want = host._schedule_host(
            batch, [host._compiled(p.placement) for p in batch]
        )
        try:
            _assert_same(want, got)
        except AssertionError as e:
            raise AssertionError(f"pass {pass_no}: {e}") from e


def test_spread_rows_ride_the_fleet_and_match_host_path():
    """Spread-constraint selections intern as DERIVED placements so those
    rows ride the device-resident path; placements must equal the host
    path exactly, and capacity drift that changes the selection must
    re-pack the affected rows (derived identity = selection content)."""
    from karmada_tpu.api.policy import (
        ClusterAffinity, LabelSelector, SpreadConstraint,
    )

    rng = np.random.default_rng(77)
    clusters = synthetic_fleet(60, seed=13)
    snap = ClusterSnapshot(clusters)
    pls = []
    for _ in range(4):
        pls.append(
            dynamic_weight_placement(
                cluster_affinity=ClusterAffinity(
                    label_selector=LabelSelector(
                        match_labels={"env": str(rng.choice(["prod", "staging", "dev"]))}
                    )
                ),
                spread_constraints=[
                    SpreadConstraint(
                        spread_by_field="region",
                        min_groups=int(rng.integers(1, 3)),
                        max_groups=int(rng.integers(3, 6)),
                    ),
                    SpreadConstraint(
                        spread_by_field="cluster",
                        min_groups=2,
                        max_groups=int(rng.integers(4, 12)),
                    ),
                ],
            )
        )
    problems = [
        BindingProblem(
            key=f"s{i}", placement=pls[i % 4],
            replicas=int(rng.integers(1, 30)), requests=REQ,
            gvk="apps/v1/Deployment",
            prev={
                clusters[int(j)].name: int(rng.integers(1, 6))
                for j in rng.choice(len(clusters), 2, replace=False)
            } if rng.random() < 0.4 else {},
        )
        for i in range(300)
    ]
    eng = TensorScheduler(snap, chunk_size=128)
    eng.fleet_threshold = 1
    got = eng.schedule(problems)
    assert eng._fleet is not None, "spread rows must engage the fleet"
    # the fleet table actually carries them (derived placements interned)
    assert eng._fleet.n_rows >= 250
    host = TensorScheduler(snap)
    want = host._schedule_host(
        problems, [host._compiled(p.placement) for p in problems]
    )
    _assert_same(want, got)

    # capacity drift changes selections: the derived identities change and
    # the fleet re-packs — still identical to a fresh host run
    for cl in clusters:
        rs = cl.status.resource_summary
        rs.allocated["cpu"] = int(rs.allocatable.get("cpu", 0) * float(rng.uniform(0.1, 0.9)))
    snap2 = ClusterSnapshot(clusters)
    assert eng.update_snapshot(snap2)
    got2 = eng.schedule(problems)
    host2 = TensorScheduler(snap2)
    want2 = host2._schedule_host(
        problems, [host2._compiled(p.placement) for p in problems]
    )
    _assert_same(want2, got2)


def test_zero_replica_spread_rows_match_host_path():
    """Zero-replica (non-workload) spread rows must expose the same
    feasible/selected set on the fleet path as on the host path — the
    selection availability mirrors merge_estimates' zero-replica
    short-circuit exactly."""
    from karmada_tpu.api.policy import (
        ClusterAffinity, LabelSelector, SpreadConstraint,
    )

    clusters = synthetic_fleet(30, seed=4)
    snap = ClusterSnapshot(clusters)
    pl = dynamic_weight_placement(
        cluster_affinity=ClusterAffinity(
            label_selector=LabelSelector(match_labels={"env": "prod"})
        ),
        spread_constraints=[
            SpreadConstraint(spread_by_field="region", min_groups=1, max_groups=3),
            SpreadConstraint(spread_by_field="cluster", min_groups=1, max_groups=5),
        ],
    )
    problems = [
        BindingProblem(key=f"z{i}", placement=pl, replicas=(0 if i % 3 == 0 else 5),
                       requests=REQ, gvk="apps/v1/Deployment")
        for i in range(120)
    ]
    eng = TensorScheduler(snap, chunk_size=64)
    eng.fleet_threshold = 1
    got = eng.schedule(problems)
    assert eng._fleet is not None
    host = TensorScheduler(snap)
    want = host._schedule_host(
        problems, [host._compiled(p.placement) for p in problems]
    )
    _assert_same(want, got)


def test_cell_delta_overflow_rows_fall_back_to_full_fetch():
    """A churn pass whose rows moved MORE than 62 cells must fetch those
    rows' full entry runs (the 6-bit delta field saturates) while normal
    rows still ride the delta wire — and both stay host-identical."""
    clusters = synthetic_fleet(200, seed=31)
    snap = ClusterSnapshot(clusters)
    pl = dynamic_weight_placement()
    problems = [
        BindingProblem(
            key=f"b{i}", placement=pl, replicas=100, requests=REQ,
            gvk="apps/v1/Deployment",
        )
        for i in range(128)
    ]
    eng = TensorScheduler(snap, chunk_size=64)
    eng.fleet_threshold = 1
    eng.schedule(problems)
    eng.schedule(problems)
    assert eng._fleet is not None and eng._fleet._delta_live is False
    # shrink replicas 100 -> 3: ~all of each row's ~100 placed cells
    # change, saturating the per-row delta field
    problems = [
        BindingProblem(
            key=p.key, placement=p.placement, replicas=3, requests=p.requests,
            gvk=p.gvk,
        )
        for p in problems
    ]
    res = eng.schedule(problems)
    bd = eng.last_breakdown
    assert bd.get("changed_rows") == 128.0
    # every row overflowed: delta path engaged but served them via the
    # exact full-row fetch
    assert bd.get("delta_rows") == 0.0, bd
    host = TensorScheduler(snap)
    want = host._schedule_host(
        problems, [host._compiled(p.placement) for p in problems]
    )
    _assert_same(want, res)
    # ...and a subsequent small mutation (a few cells per row) rides the
    # delta wire again
    problems = [
        BindingProblem(
            key=p.key, placement=p.placement,
            replicas=5 if i < 30 else p.replicas, requests=p.requests,
            gvk=p.gvk,
        )
        for i, p in enumerate(problems)
    ]
    res2 = eng.schedule(problems)
    bd2 = eng.last_breakdown
    assert bd2.get("changed_rows", 0) >= 30, bd2
    assert bd2.get("delta_rows", 0) >= 30, bd2
    host2 = TensorScheduler(snap)
    want2 = host2._schedule_host(
        problems, [host2._compiled(p.placement) for p in problems]
    )
    _assert_same(want2, res2)


def test_post_compaction_delta_pass_is_host_identical():
    """After _compact() remaps rows, a DELTA-carried pass (small table:
    total and dtotal under the floor caps, so use_delta engages on the
    very first post-compact pass) must not merge insert-only deltas into
    another binding's stale host-mirror run — the reset must drop the
    entry mirror with the residents."""
    clusters = synthetic_fleet(50, seed=13)
    snap = ClusterSnapshot(clusters)
    pl = dynamic_weight_placement()

    def mk(key, reps):
        return BindingProblem(key=key, placement=pl, replicas=reps,
                              requests=REQ, gvk="apps/v1/Deployment")

    doomed = [mk(f"d{i}", 5 + i % 7) for i in range(80)]
    kept = [mk(f"k{i}", 3 + i % 9) for i in range(80)]
    eng = TensorScheduler(snap, chunk_size=64)
    eng.fleet_threshold = 1
    eng.schedule(doomed + kept)
    # age the doomed rows out, then compact: rows remap (kept rows shift
    # down into the doomed rows' slots)
    for _ in range(10):
        eng.schedule(kept)
    table = eng._fleet
    assert table._compact(), "compaction must trigger for this layout"
    res = eng.schedule(kept)
    bd = eng.last_breakdown
    # the point of the test: this pass must be delta-carried
    assert bd.get("delta_rows", 0) > 0, bd
    host = TensorScheduler(snap)
    want = host._schedule_host(kept, [host._compiled(p.placement) for p in kept])
    _assert_same(want, res)


def test_caps_compile_stable_after_warm_window():
    """Cap tuning must never dispatch an unseen XLA trace once the warm
    window (SHRINK_SUSTAIN + a couple of passes) has run: growth lands at
    churn onset, sustained shrinks land inside the window, and wobbles
    ride already-compiled traces. A vote-delayed shrink used to fire MID
    storm — a 94s dispatch stall on the TPU bench."""
    import copy

    clusters = synthetic_fleet(48, seed=21)
    snap = ClusterSnapshot(clusters)
    pl = dynamic_weight_placement()
    problems = [
        BindingProblem(key=f"b{i}", placement=pl, replicas=(i % 25) + 1,
                       requests=REQ, gvk="apps/v1/Deployment")
        for i in range(1500)
    ]
    eng = TensorScheduler(snap, chunk_size=256)
    eng.schedule(problems)  # warm/compile

    rng = np.random.default_rng(3)

    def drift():
        for cl in clusters:
            rs = cl.status.resource_summary
            for dim, q in list(rs.allocated.items()):
                alloc = rs.allocatable.get(dim, 0)
                rs.allocated[dim] = int(min(max(
                    0, q + int(rng.integers(-2, 3)) * max(1, alloc // 100)
                ), alloc))
        assert eng.update_snapshot(ClusterSnapshot(clusters))

    # warm window: steady settle + churn onset + the sustained-shrink span
    window = fleet_mod.SHRINK_SUSTAIN + 4
    for _ in range(3):
        eng.schedule(problems)
    for _ in range(window):
        drift()
        eng.schedule(problems)
    # beyond the window: alternate steady and churn passes — no pass may
    # compile anything new, whatever the cap tuner wants
    for i in range(8):
        if i % 3:
            drift()
        eng.schedule(problems)
        assert not eng.last_pass_new_trace, (
            f"pass {i} dispatched an unseen trace after the warm window"
        )
