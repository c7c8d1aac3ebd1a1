"""ISSUE 36: a swapped batch costs the engine its swapped rows too. Where a
batch is armed and the snapshot's generation AND ``mask_token`` moved, the
full path diffs a batch of the armed batch's length by object identity
(``ResidentBatch.diff``, then ``TensorScheduler._moved_pass``): one ``id()``
sweep, the armed batch's distinct placements compiled anew, the moved
positions alone compiled and held to the fleet-eligibility predicate.

(a)-(g) equivalence: every row's ``clusters``, ``affinity_name`` and
    ``error`` against a FRESH engine over the same snapshot and batch; what
    ``scheduler.pack`` says it visited and kept; the path of the next pass
    over the same list; no compiled placement of the old token handed on;
(h) a ring ``L r L r`` with identity passes between, every pass against a
    fresh engine;
(i) counting: a swap pass of n rows with k moved calls ``row_rides`` k times
    and ``_compiled`` O(k + distinct placements) times, and sweeps the batch
    once, the table's diff included;
(j) the counter.

The same route where the generation moved under an UNMOVED
``mask_token`` (availability alone drifted, the armed batch missed its
identity check and the table declined the replay for the moved
generation): the identity branch's sweep reused, the armed compiled list
kept (no compile), the moved positions visited, every row dispatched. (a)
and (i) again under such a move, a ring of drifts with a minority of
swapped objects a step, and the route under an active ``QuotaSnapshot``
against the partition route. Last, the route choice itself: seven waves,
one diff each, four routes; host selections a moved pass spares, a pass
with host rows, and a key twice in the batch, each against a fresh engine.
"""

import copy
import dataclasses

import numpy as np
import pytest

from karmada_tpu.api.cluster import Taint
from karmada_tpu.api.policy import SpreadConstraint
from karmada_tpu.scheduler import (
    BindingProblem,
    ClusterSnapshot,
    TensorScheduler,
)
from karmada_tpu.scheduler import core as core_mod
from karmada_tpu.scheduler import fleet as fleet_mod
from karmada_tpu.scheduler.fleet import K_EVICT
from karmada_tpu.scheduler.snapshot import compile_placement
from karmada_tpu.utils import metrics
from karmada_tpu.utils.tracing import tracer
from test_fleet_failover import (
    NOT_READY,
    C,
    _clusters,
    _copy_out,
    _place,
    _placement,
    _placements,
    _problem,
    _same,
)
from test_fleet_upsert import _twin
import test_fleet_quota_rows as quota_rows

NAMES = [f"m{j:02d}" for j in range(C)]
N = 400
SPREAD = (SpreadConstraint(spread_by_field="region", min_groups=1,
                           max_groups=2),)


def _federation(rng) -> tuple:
    """(healthy, tainted, the lost members' names): region r1 lost."""
    clusters = _clusters(rng, allocated_share=0.3)
    healthy = ClusterSnapshot(clusters)
    clusters = copy.deepcopy(clusters)  # healthy's members stay untainted
    lost_at = [j for j in range(C) if _place(j)[0] == "r1"]
    for j in lost_at:
        clusters[j].spec.taints = [Taint(key=NOT_READY, effect="NoExecute")]
    tainted = ClusterSnapshot(clusters)
    assert healthy.mask_token != tainted.mask_token
    return healthy, tainted, {NAMES[j] for j in lost_at}


def _drifted(snap, rng, low=0.1, high=0.6) -> ClusterSnapshot:
    """The same members with another share of each one's cpu allocated:
    availability alone moved, so the mask_token stands."""
    clusters = copy.deepcopy(snap.clusters)
    for cl in clusters:
        summary = cl.status.resource_summary
        summary.allocated = {"cpu": int(
            summary.allocatable["cpu"] * rng.uniform(low, high))}
    out = ClusterSnapshot(clusters)
    assert out.mask_token == snap.mask_token
    assert not np.array_equal(out.available_cap, snap.available_cap)
    return out


def _batch(rng, placements, n=N) -> list:
    base = [_problem(rng, i, placements[i % len(placements)])
            for i in range(n)]
    for p in base:
        p.evict_clusters = ()
    return base


def _evicted(p, lost, tasks=None):
    """The binding as the loss presents it: a NEW object, its sites on the
    lost members turned into eviction tasks."""
    hit = [n for n in p.prev if n in lost] or sorted(lost)[:1]
    return _twin(
        p, prev={n: v for n, v in p.prev.items() if n not in lost},
        evict_clusters=tuple(hit[:K_EVICT]) if tasks is None else tasks)


def _after(base, lost, every=5, **kw) -> list:
    """The batch after the loss: every ``every``-th position a new object."""
    return [_evicted(p, lost, **kw) if i % every == 0 else p
            for i, p in enumerate(base)]


def _spans(name: str) -> list:
    return [s for s in tracer.dump() if s["name"] == name]


def _engine(snap) -> TensorScheduler:
    return TensorScheduler(snap, chunk_size=256, mesh=False)


def _same_as_fresh(snap, problems, got) -> None:
    want = _copy_out(_engine(snap).schedule(problems))
    assert len(got) == len(want) == len(problems)
    for i, (a, b) in enumerate(zip(got, want)):
        _same(a, b, i)


def _compiled_under(engine, fc) -> None:
    """Every compiled placement handed on is the one the engine's cache
    holds, and the cache was emptied when the token moved; its masks are
    the placement's under this snapshot."""
    seen = {}
    for cp in fc:
        seen[id(cp)] = cp
    for cp in seen.values():
        key = id(cp.placement) if cp.placement is not None else 0
        assert engine._placement_cache[key][1] is cp
        now = compile_placement(cp.placement, engine.snapshot)
        assert np.array_equal(cp.taint_ok, now.taint_ok)
        for (_, a), (_, b) in zip(cp.terms, now.terms):
            assert np.array_equal(a, b)


def _case(rng, name: str, move: str = "token") -> tuple:
    """(the snapshot before, the one after the move, the batch before, the
    batch presented after it, positions visited or None for the walk, rows
    on the host path). ``move``: ``token`` (region r1 lost) or ``drift``
    (availability alone; the lost region's sites still become tasks)."""
    healthy, tainted, lost = _federation(rng)
    if move == "drift":
        tainted = _drifted(healthy, rng)
    placements = _placements(rng, terms=(1, 2, 3))
    if name in ("spread-rows", "new-placement"):
        placements += [_placement(rng, s, 1, tol, SPREAD)
                       for s in ("dynamic", "aggregated", "duplicated")
                       for tol in (False, True)]
    base = _batch(rng, placements)
    if name == "same-list":
        return healthy, tainted, base, base, 0, 0
    after = _after(base, lost)
    moved = sum(1 for a, b in zip(after, base) if a is not b)
    assert 0 < moved * 2 < N
    if name == "minority":
        return healthy, tainted, base, after, moved, 0
    if name == "spread-rows":
        spread_at = [i for i, p in enumerate(base)
                     if p.placement.spread_constraints]
        assert any(after[i] is base[i] for i in spread_at)
        assert any(after[i] is not base[i] for i in spread_at)
        return healthy, tainted, base, after, moved, 0
    if name == "new-placement":
        # a moved row names a placement the armed batch did not
        joined = _placement(rng, "dynamic", 1, True, SPREAD)
        at = next(i for i, p in enumerate(after) if p is not base[i])
        after[at] = _twin(after[at], placement=joined)
        return healthy, tainted, base, after, moved, 0
    if name == "past-k-evict":
        at = next(i for i, p in enumerate(after)
                  if p is not base[i] and p.replicas > 0)
        after[at] = _twin(after[at],
                          evict_clusters=tuple(NAMES[: K_EVICT + 1]))
        return healthy, tainted, base, after, None, 1
    if name == "all-moved":
        return healthy, tainted, base, _after(base, lost, every=1), None, 0
    assert name == "another-length"
    return healthy, tainted, base, after[:-7], None, 0


CASES = ("same-list", "minority", "spread-rows", "new-placement",
         "past-k-evict", "all-moved", "another-length")
# the same list at a moved generation under a standing token is the
# identity path's, not this route's
MOVES = [("token", c) for c in CASES] + [
    ("drift", c) for c in CASES if c != "same-list"]


@pytest.mark.parametrize(
    ("move", "name"), MOVES,
    ids=[c if m == "token" else f"{m}-{c}" for m, c in MOVES])
def test_a_swapped_batch_answers_as_a_fresh_engine(move, name):
    rng = np.random.default_rng(36 + CASES.index(name))
    healthy, tainted, base, after, visited, host_rows = _case(
        rng, name, move)
    engine = _engine(healthy)
    engine.schedule(base)
    old_fc = list(engine._fleet.batch.compiled)
    assert engine.update_snapshot(tainted)
    tracer.clear()
    got = _copy_out(engine.schedule(after))
    (root,) = _spans("scheduler.schedule")
    assert root["attrs"]["path"] == "full"
    (pack,) = _spans("scheduler.pack")
    (ident,) = _spans("scheduler.identity") if len(after) == N else (None,)
    (solve,) = _spans("scheduler.solve")
    (rearm,) = _spans("scheduler.rearm")
    _same_as_fresh(tainted, after, got)
    n = len(after)
    if visited is None:
        assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (n, 0)
    else:
        assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (
            visited, n - visited)
    if ident is not None:
        # one sweep, whichever way the pass then went: inside pack where
        # the token moved, the identity branch's before it where it stands
        parent = pack if move == "token" else root
        assert ident["parent_id"] == parent["span_id"]
        assert (ident["attrs"]["rows"], ident["attrs"]["hit"]) == (n, 0)
        assert ident["attrs"]["moved"] == sum(
            1 for a, b in zip(after, base) if a is not b)
    assert solve["attrs"]["host_rows"] == host_rows
    assert rearm["attrs"]["host_rows"] == host_rows
    assert "eligible" in engine.last_breakdown
    tracer.clear()
    again = _copy_out(engine.schedule(after))
    (root,) = _spans("scheduler.schedule")
    if host_rows:
        # a host row arms nothing: the next pass walks again
        assert root["attrs"]["path"] == "full"
    else:
        assert root["attrs"]["path"] == "identity"
        fp, fc = engine._fleet.batch.problems, engine._fleet.batch.compiled
        assert all(a is b for a, b in zip(fp, after))
        if move == "token":
            # no compiled placement of the old token handed on
            assert not {id(cp) for cp in fc} & {id(cp) for cp in old_fc}
        else:
            # the armed compiled list kept: nothing compiled anew
            assert all(cp is old_fc[i] for i, (cp, p, q) in enumerate(
                zip(fc, after, base)) if p.placement is q.placement)
        _compiled_under(engine, fc)
    for i, (a, b) in enumerate(zip(again, got)):
        _same(a, b, i)


@pytest.mark.parametrize("seed", [3, 11])
def test_a_ring_of_losses_and_returns(seed):
    """``L r L r``, identity passes between; the second ``L`` loses the
    region under ANOTHER batch, so the index by placement is corrected
    twice and built once."""
    rng = np.random.default_rng(seed)
    healthy, tainted, lost = _federation(rng)
    placements = _placements(rng, terms=(1, 2)) + [
        _placement(rng, "dynamic", 1, True, SPREAD)]
    base = _batch(rng, placements)
    first, second = _after(base, lost, every=4), _after(base, lost, every=3)
    engine = _engine(healthy)
    engine.schedule(base)
    built = []
    for snap, problems in ((tainted, first), (healthy, base),
                           (tainted, second), (healthy, base)):
        assert engine.update_snapshot(snap)
        tracer.clear()
        got = _copy_out(engine.schedule(problems))
        (pack,) = _spans("scheduler.pack")
        assert 0 < pack["attrs"]["rows"] < N // 2
        assert pack["attrs"]["rows"] + pack["attrs"]["kept"] == N
        _same_as_fresh(snap, problems, got)
        built.append(engine._fleet.batch.placements)
        tracer.clear()
        engine.schedule(problems)
        (root,) = _spans("scheduler.schedule")
        assert root["attrs"]["path"] == "identity"
    # the batch by placement: one list of placements throughout, the index
    # a new array each pass (the armed one is never written)
    assert all(b is not None and b[0] is built[0][0] for b in built)
    assert len({id(b[2]) for b in built}) == 4
    for (_, _, index), problems in zip(built[-2:], (second, base)):
        assert [built[0][0][j] for j in index.tolist()] == [
            p.placement for p in problems]


def _count_a_swap_pass(monkeypatch, move: str) -> None:
    """A swap pass of n rows with k moved, counted: ``row_rides`` k times,
    ``_compiled`` at most k + the distinct placements + the table's slots,
    one sweep of the batch (the table's diff and the re-arm included), the
    table's ``rows_visited`` k."""
    rng = np.random.default_rng(53)
    healthy, tainted, lost = _federation(rng)
    placements = _placements(rng, terms=(1, 2, 3))
    n = 2000
    base = _batch(rng, placements, n)
    after = _after(base, lost, every=10)
    k = sum(1 for a, b in zip(after, base) if a is not b)
    engine = _engine(healthy)
    engine.schedule(base)
    if move == "drift":
        ring = [_drifted(healthy, rng) for _ in range(3)]
        tainted = ring[-1]
    else:
        ring = [tainted, healthy, tainted]
    # one turn of the ring first: the table's first diff after a walk reads
    # the ids of the objects its rows hold (a sweep of its own, once)
    for snap, problems in zip(ring[:2], (after, base)):
        assert engine.update_snapshot(snap)
        engine.schedule(problems)
    slots = len(engine._fleet._cp_pl)
    calls = {"row_rides": 0, "compiled": 0, "swept": 0, "compiles": 0}
    rides, compiled = fleet_mod.row_rides, engine._compiled
    compile_ = core_mod.compile_placement

    def counted_compile(placement, snapshot):
        calls["compiles"] += 1
        return compile_(placement, snapshot)

    def counted_rides(p, cp):
        calls["row_rides"] += 1
        return rides(p, cp)

    def counted_compiled(placement):
        calls["compiled"] += 1
        return compiled(placement)

    def counted_id(obj):
        calls["swept"] += isinstance(obj, BindingProblem)
        return id(obj)

    monkeypatch.setattr(fleet_mod, "row_rides", counted_rides)
    monkeypatch.setattr(engine, "_compiled", counted_compiled)
    monkeypatch.setattr(core_mod, "compile_placement", counted_compile)
    monkeypatch.setattr(core_mod, "id", counted_id, raising=False)
    monkeypatch.setattr(fleet_mod, "id", counted_id, raising=False)
    assert engine.update_snapshot(tainted)
    tracer.clear()
    got = _copy_out(engine.schedule(after))
    (pack,) = _spans("scheduler.pack")
    assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (k, n - k)
    assert calls["row_rides"] == k
    # the moved positions, the armed batch's distinct placements, and the
    # table's own recompile of its slots
    assert calls["compiled"] <= k + len(placements) + slots
    # a standing token compiles nothing: the armed list and look-ups
    assert calls["compiles"] == (
        0 if move == "drift" else len(placements))
    assert calls["swept"] == n  # one sweep: none in the table, none to re-arm
    (solve,) = _spans("scheduler.solve")
    assert solve["attrs"]["rows_visited"] == k
    monkeypatch.undo()
    _same_as_fresh(tainted, after, got)


def test_a_swap_pass_costs_its_moved_positions(monkeypatch):
    _count_a_swap_pass(monkeypatch, "token")


def test_a_drifted_swap_pass_costs_its_moved_positions(monkeypatch):
    """Under a standing token: no placement compiles (look-ups alone), and
    the sweep is the identity branch's."""
    _count_a_swap_pass(monkeypatch, "drift")


def test_the_counter_tells_kept_from_visited():
    rng = np.random.default_rng(59)
    healthy, tainted, lost = _federation(rng)
    base = _batch(rng, _placements(rng, terms=(1, 2)))
    after = _after(base, lost)
    k = sum(1 for a, b in zip(after, base) if a is not b)
    engine = _engine(healthy)

    def tally():
        return {o: metrics.scheduler_prologue_rows.value(outcome=o)
                for o in ("kept", "visited")}

    before = tally()
    engine.schedule(base)  # the walk
    walked = tally()
    assert {o: walked[o] - before[o] for o in walked} == {
        "kept": 0, "visited": N}
    engine.schedule(base)  # identity: no prologue
    assert tally() == walked
    assert engine.update_snapshot(tainted)
    engine.schedule(after)
    swapped = tally()
    assert {o: swapped[o] - walked[o] for o in walked} == {
        "kept": N - k, "visited": k}


@pytest.mark.parametrize("seed", [5, 17])
def test_a_ring_of_drifts_with_swapped_objects(seed):
    """Availability drifts a step (the token stands); in each step a seeded
    minority of positions holds a rescaled copy of its binding, and the
    previous step's copies go back to their base objects (the ClusterLoader2
    scale phase): spread-constrained, multi-term and Divided rows among
    them. Every pass answers as a fresh engine; its prologue visits the
    positions that hold another object than the armed batch's; the batch
    by placement is built once and kept; the same list again is an identity
    pass."""
    rng = np.random.default_rng(seed)
    healthy, _, _ = _federation(rng)
    placements = _placements(rng, terms=(1, 2, 3)) + [
        _placement(rng, s, 1, tol, SPREAD)
        for s in ("dynamic", "aggregated", "duplicated")
        for tol in (False, True)]
    base = _batch(rng, placements)
    engine = _engine(healthy)
    engine.schedule(base)

    def tally():
        return [metrics.scheduler_prologue_rows.value(outcome=o)
                for o in ("kept", "visited")]

    armed, built, kinds = base, [], set()
    for step in range(6):
        snap = _drifted(healthy, rng)
        at = set(rng.choice(N, int(rng.integers(N // 20, N // 6)),
                            replace=False).tolist())
        problems = [
            _twin(p, replicas=max(1, p.replicas * int(rng.integers(1, 4))
                                  // 2))
            if i in at else p for i, p in enumerate(base)]
        moved = [i for i, (a, b) in enumerate(zip(problems, armed))
                 if a is not b]
        k = len(moved)
        assert 0 < k * 2 < N
        kinds |= {(len(problems[i].placement.cluster_affinities) > 1,
                   bool(problems[i].placement.spread_constraints),
                   problems[i].placement.replica_scheduling
                   .replica_scheduling_type) for i in moved}
        before = tally()
        assert engine.update_snapshot(snap)
        tracer.clear()
        got = _copy_out(engine.schedule(problems))
        (root,) = _spans("scheduler.schedule")
        assert root["attrs"]["path"] == "full"
        (ident,) = _spans("scheduler.identity")
        assert ident["parent_id"] == root["span_id"]
        assert (ident["attrs"]["hit"], ident["attrs"]["moved"]) == (0, k)
        (pack,) = _spans("scheduler.pack")
        assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (k, N - k)
        (solve,) = _spans("scheduler.solve")
        assert solve["attrs"]["rows_visited"] == k
        assert [b - a for a, b in zip(before, tally())] == [N - k, k]
        _same_as_fresh(snap, problems, got)
        built.append(engine._fleet.batch.placements)
        if step % 2:
            tracer.clear()
            again = _copy_out(engine.schedule(problems))
            (root,) = _spans("scheduler.schedule")
            assert root["attrs"]["path"] == "identity"
            for i, (a, b) in enumerate(zip(again, got)):
                _same(a, b, i)
        armed = problems
    # multi-term, spread-constrained and Divided rows were among the moved
    assert {m for m, _, _ in kinds} == {False, True}
    assert {sp for _, sp, _ in kinds} == {False, True}
    assert "Divided" in {t for _, _, t in kinds}
    # built from the armed compiled list by the first such pass, then kept
    assert all(b is not None and b[0] is built[0][0] for b in built)
    assert [built[0][0][j] for j in built[-1][2].tolist()] == [
        p.placement for p in armed]


def test_a_drifted_swap_under_quota_answers_as_the_partition_route():
    """The route under an active QuotaSnapshot: the batch rides the table
    whole and is admitted from its row state, FIFO over the presented
    order, as the partition route admits and places it (a fresh engine held
    under the fleet threshold) and as the reference does."""
    rng = np.random.default_rng(42)
    snap = ClusterSnapshot([
        quota_rows.new_cluster(f"m{i:02d}", cpu=str(600 + 40 * (i % 5)),
                               memory="4000Gi", pods=100_000)
        for i in range(quota_rows.C)
    ])
    base = quota_rows.build_problems(snap)
    engine = quota_rows.engine(snap)
    engine.set_quota(quota_rows.make_quota(snap, base, generation=1))
    engine.schedule(base)
    drifted = _drifted(snap, rng, 0.05, 0.3)
    after = [dataclasses.replace(p, replicas=p.replicas + 3)
             if i % 7 == 3 else p for i, p in enumerate(base)]
    k = sum(1 for a, b in zip(after, base) if a is not b)
    quota = quota_rows.make_quota(drifted, after, generation=2)
    before = quota.remaining.copy()
    resident = quota_rows.route_count("resident")
    assert engine.update_snapshot(drifted)
    engine.set_quota(quota)
    tracer.clear()
    got = engine.schedule(after)
    assert quota_rows.route_count("resident") == resident + 1
    (root,) = _spans("scheduler.schedule")
    assert root["attrs"]["path"] == "full"
    (pack,) = _spans("scheduler.pack")
    assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (
        k, len(after) - k)
    (span,) = _spans("scheduler.quota")
    assert span["attrs"]["host_rows"] == 0
    denied = quota_rows.assert_wave(drifted, after, got, quota, before)
    assert denied
    partition = quota_rows.route_count("partition")
    ref = quota_rows.engine(drifted)
    ref.fleet_threshold = 10 ** 9  # every batch under it: the partition
    ref.set_quota(quota_rows.make_quota(drifted, after, generation=2))
    want = ref.schedule(after)
    assert quota_rows.route_count("partition") == partition + 1
    for i, (a, b) in enumerate(zip(got, want)):
        assert (a.key, a.error) == (b.key, b.error), i
        assert dict(a.clusters) == dict(b.clusters), i


# -- the route choice: one diff, four routes ---------------------------------


WAVES = ("identity", "delta", "swap-drift", "swap-token", "walk",
         "dirty-unmapped", "delta-spread")


@pytest.mark.parametrize("wave", WAVES)
def test_one_diff_chooses_the_route(wave):
    """Each wave kind after an armed pass: the root ``path``, exactly one
    ``scheduler.identity`` record (its ``hit`` and ``moved``), what
    ``scheduler.pack`` visited and kept, what the table visited, and every
    answer as a fresh engine gives it. ``dirty-unmapped``: the same list
    with a dirty key the batch does not hold, which the table serves as a
    pure replay; ``delta-spread``: a delta at the standing generation that
    moves spread-constrained rows, whose selection the full pass arranges:
    every row is dispatched."""
    rng = np.random.default_rng(4300 + WAVES.index(wave))
    healthy, tainted, lost = _federation(rng)
    placements = _placements(rng, terms=(1, 2))
    if wave == "delta-spread":
        placements += [_placement(rng, s, 1, False, SPREAD)
                       for s in ("dynamic", "aggregated", "duplicated")]
    base = _batch(rng, placements)
    engine = _engine(healthy)
    engine.schedule(base)
    snap, after, dirty = healthy, _after(base, lost), None
    if wave in ("identity", "dirty-unmapped"):
        after = base
    if wave == "dirty-unmapped":
        dirty = {"no-such-binding"}
    elif wave == "swap-drift":
        snap = _drifted(healthy, rng)  # the generation moved, the token not
    elif wave == "swap-token":
        snap = tainted  # both moved
    elif wave == "walk":
        after = _after(base, lost, every=1)  # every position moved
    elif wave == "delta-spread":
        assert any(after[i] is not p and p.placement.spread_constraints
                   for i, p in enumerate(base))
    if snap is not healthy:
        assert engine.update_snapshot(snap)
    k = sum(1 for a, b in zip(after, base) if a is not b)
    assert (k == 0) == (after is base) and (k == N) == (wave == "walk")
    replays = wave in ("delta", "dirty-unmapped")
    tracer.clear()
    got = _copy_out(engine.schedule(after, dirty_keys=dirty))
    (root,) = _spans("scheduler.schedule")
    assert root["attrs"]["path"] == (
        "identity" if wave == "identity" else "delta" if replays else "full")
    (ident,) = _spans("scheduler.identity")
    assert (ident["attrs"]["rows"], ident["attrs"]["hit"],
            ident["attrs"]["moved"]) == (N, int(wave == "identity"), k)
    packs = _spans("scheduler.pack")
    if wave == "identity":
        assert packs == []
    else:
        (pack,) = packs
        assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (
            (N, 0) if wave == "walk" else (k, N - k))
        # the sweep is the prologue's first stage where the token moved
        parent = pack if wave == "swap-token" else root
        assert ident["parent_id"] == parent["span_id"]
    (solve,) = _spans("scheduler.solve")
    assert solve["attrs"]["rows_visited"] == k
    assert solve["attrs"]["dirty_rows"] == (k if replays else 0)
    _same_as_fresh(snap, after, got)
    # and the next pass over the same list is the identity route
    tracer.clear()
    again = _copy_out(engine.schedule(after))
    (root,) = _spans("scheduler.schedule")
    assert root["attrs"]["path"] == "identity"
    for i, (a, b) in enumerate(zip(again, got)):
        _same(a, b, i)


def test_host_selections_a_moved_pass_spared_are_not_served_after_a_drift():
    """More regions than the device's subset table: the host selects for
    the spread rows, and the batch is armed with no token. A minority of
    other rows moves at the standing generation with the preemption plane
    armed (the table dispatches every row, no replay): the spared rows
    keep the host's selections, so the record still carries no token, and
    the drift that follows walks the batch and selects anew."""
    from karmada_tpu.scheduler import BindingProblem
    from karmada_tpu.scheduler import select as select_mod
    from karmada_tpu.utils import builders

    regions = [f"r{k}" for k in range(select_mod.R_CAP + 1)]
    rng = np.random.default_rng(4301)
    clusters = [
        builders.new_cluster(
            f"m{j:03d}", cpu=str(int(rng.integers(40, 400))),
            memory="2048Gi", pods=5000, region=regions[j % len(regions)])
        for j in range(3 * len(regions))
    ]

    def generation(g):
        # the regions with room trade places from one generation to the
        # next, and with them the host's selections
        for j, cl in enumerate(clusters):
            full = (j % len(regions) < len(regions) // 2) == (g == 0)
            alloc = cl.status.resource_summary.allocatable
            cl.status.resource_summary.allocated = {
                d: int(v * (0.97 if full else 0.1))
                for d, v in alloc.items()}
        return ClusterSnapshot(copy.deepcopy(clusters))

    spread = [builders.dynamic_weight_placement(spread_constraints=[
        SpreadConstraint(spread_by_field="region", min_groups=2,
                         max_groups=3),
        SpreadConstraint(spread_by_field="cluster", min_groups=2,
                         max_groups=6)]),
        builders.dynamic_weight_placement(spread_constraints=[
            SpreadConstraint(spread_by_field="cluster", min_groups=2,
                             max_groups=4)])]
    plain = [builders.dynamic_weight_placement(),
             builders.duplicated_placement()]
    problems = [
        BindingProblem(
            key=f"b{i}", placement=(spread + plain)[i % 4],
            replicas=int(rng.integers(1, 30)),
            requests={"cpu": int(rng.choice([250, 1000])),
                      "memory": 1 << 30},
            gvk="apps/v1/Deployment")
        for i in range(360)
    ]

    def engine_at(snap):
        eng = TensorScheduler(snap, chunk_size=256, mesh=False)
        eng.set_preemption(lambda exclude: [])
        return eng

    snap = generation(0)
    assert select_mod.region_table(snap) is None
    engine = engine_at(snap)
    engine.schedule(problems)
    rec = engine._fleet.batch
    assert rec.armed and rec.token is None  # every row rode, host-selected
    # plain rows move, the spread rows (i % 4 < 2) are spared
    after = [_twin(p, replicas=p.replicas + 1) if i % 12 == 2 else p
             for i, p in enumerate(problems)]
    k = sum(1 for a, b in zip(after, problems) if a is not b)
    tracer.clear()
    got = _copy_out(engine.schedule(after))
    (root,) = _spans("scheduler.schedule")
    (pack,) = _spans("scheduler.pack")
    assert root["attrs"]["path"] == "full"
    assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (k, len(after) - k)
    assert engine._fleet.batch.token is None
    want = _copy_out(engine_at(snap).schedule(after))
    for i, (a, b) in enumerate(zip(got, want)):
        _same(a, b, i)
    # availability alone drifts: the token stands
    token = snap.mask_token
    snap = generation(1)
    assert engine.update_snapshot(snap) and snap.mask_token == token
    tracer.clear()
    got = _copy_out(engine.schedule(after))
    (root,) = _spans("scheduler.schedule")
    (pack,) = _spans("scheduler.pack")
    assert root["attrs"]["path"] == "full"
    assert _spans("scheduler.identity") == []
    assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (len(after), 0)
    want = _copy_out(engine_at(snap).schedule(after))
    for i, (a, b) in enumerate(zip(got, want)):
        _same(a, b, i)


def test_a_pass_with_host_rows_disarms_the_batch():
    """A pass whose batch leaves a row to the host path holds the fleet
    rows of that batch, not the armed one: a later batch of the armed
    batch's length is walked, and none of its answers is another
    binding's."""
    rng = np.random.default_rng(4302)
    healthy, _, _ = _federation(rng)
    base = _batch(rng, _placements(rng, terms=(1, 2)))
    engine = _engine(healthy)
    engine.schedule(base)
    # the same bindings in reverse order, and one past the eviction tasks
    # a row holds: N rows ride the table, one takes the host path
    extra = _twin(base[0], key=base[0].key + "-x",
                  evict_clusters=tuple(NAMES[: K_EVICT + 1]))
    engine.schedule(list(reversed(base)) + [extra])
    assert not engine._fleet.batch.armed
    after = list(base)
    after[3] = _twin(base[3], replicas=base[3].replicas + 1)
    tracer.clear()
    got = _copy_out(engine.schedule(after))
    (root,) = _spans("scheduler.schedule")
    (pack,) = _spans("scheduler.pack")
    assert root["attrs"]["path"] == "full"
    assert _spans("scheduler.identity") == []
    assert (pack["attrs"]["rows"], pack["attrs"]["kept"]) == (N, 0)
    _same_as_fresh(healthy, after, got)


def test_a_key_twice_in_the_batch_replays_nothing():
    """A batch that holds a binding's key at two positions is armed: the
    same list again is the identity route, and a swap diffs it. A delta
    at the standing generation replays nothing (the two positions share a
    row, so an answer replayed for one may be the other's): every row is
    dispatched, and every answer is a fresh engine's."""
    rng = np.random.default_rng(4303)
    healthy, _, _ = _federation(rng)
    base = _batch(rng, _placements(rng, terms=(1, 2)))
    base[7] = _twin(base[20])  # base[20]'s key at position 7 too
    engine = _engine(healthy)
    engine.schedule(base)
    assert engine._fleet.batch.armed and not engine._fleet.batch.unique
    drifted = _drifted(healthy, rng)
    moved = {"identity": (), "delta": (7, 30, 41), "swap": (20, 50)}
    for wave, at in moved.items():
        after = [_twin(p, replicas=p.replicas + 1) if i in at else p
                 for i, p in enumerate(base)]
        snap = drifted if wave == "swap" else healthy
        if wave == "swap":
            assert engine.update_snapshot(snap)
        tracer.clear()
        got = _copy_out(engine.schedule(after))
        (root,) = _spans("scheduler.schedule")
        assert root["attrs"]["path"] == (
            "identity" if wave == "identity" else "full"), wave
        packs = _spans("scheduler.pack")
        assert [p["attrs"]["rows"] for p in packs] == (
            [] if wave == "identity" else [len(at)]), wave
        _same_as_fresh(snap, after, got)
        base = after
