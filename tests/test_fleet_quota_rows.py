"""A FederatedResourceQuota denial as row state of the fleet table (PR 37).

A batch that rides the table whole is admitted from the table's row state
(``FleetTable._admit_on_device``: ``_fleet_quota`` derives every row's
namespace and demand on the device, ``ops.quota.quota_admit`` admits them in
presented order) and keeps its length and its list: a denial is a bit beside
the row's answer. Every case holds admission AND placements to
``refimpl/quota_np.py`` (``admit_and_place``: sequential FIFO admission, the
static-assignment ceiling, the numpy divider), on seeded content, at a small
size, on the CPU.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from karmada_tpu.ops.quota import DEMAND_CLAMP, UNLIMITED
from karmada_tpu.refimpl.quota_np import (
    admit_and_place,
    admit_wave_np,
    asking_ns_ids,
    cluster_caps_seq,
)
from karmada_tpu.scheduler import (
    QUOTA_EXCEEDED_ERROR,
    BindingProblem,
    ClusterSnapshot,
    ScheduleResult,
    TensorScheduler,
)
from karmada_tpu.scheduler import fleet as fleet_mod
from karmada_tpu.scheduler.quota import QuotaSnapshot, per_replica_vector
from karmada_tpu.scheduler.snapshot import compile_placement
from karmada_tpu.utils.builders import (
    duplicated_placement,
    dynamic_weight_placement,
    new_cluster,
)
from karmada_tpu.utils.metrics import (
    quota_admission_passes,
    quota_admission_rows,
)
from karmada_tpu.utils.tracing import tracer

C = 12
N_NS = 6  # t0..t5; t0..t3 carry a quota, t1 and t3 static assignments too
B = 400
PLACEMENT = dynamic_weight_placement()
PROFILES = [{"cpu": 250 * (k + 1), "memory": (512 << 20) * (k + 1)}
            for k in range(4)]


@pytest.fixture(scope="module")
def snap():
    return ClusterSnapshot([
        new_cluster(f"m{i:02d}", cpu=str(600 + 40 * (i % 5)),
                    memory="4000Gi", pods=100_000)
        for i in range(C)
    ])


def build_problems(snap, n=B, seed=5, prefix="b", ns_of=None):
    """Dynamic-weight rows over N_NS namespaces (skewed: the first holds a
    third of the rows), 70% holding a previous result."""
    rng = np.random.default_rng(seed)
    names = snap.names
    weights = np.asarray([1 / (k + 1) for k in range(N_NS)])
    ns = rng.choice(N_NS, n, p=weights / weights.sum())
    out = []
    for i in range(n):
        prev = {}
        if rng.random() < 0.7:
            for j in rng.choice(C, int(rng.integers(1, 4)), replace=False):
                prev[names[int(j)]] = int(rng.integers(1, 6))
        out.append(BindingProblem(
            key=f"{prefix}{i}", placement=PLACEMENT,
            replicas=int(rng.integers(1, 30)),
            requests=PROFILES[int(rng.integers(0, 4))],
            gvk="apps/v1/Deployment", prev=prev,
            fresh=bool(rng.random() < 0.05),
            namespace=(ns_of(i) if ns_of else f"t{int(ns[i])}"),
        ))
    return out


def demand_of(problems, dims):
    q = QuotaSnapshot(dims, {}, np.zeros((0, len(dims)), np.int64), {},
                      np.zeros((0, C, len(dims)), np.int64), 0, 0)
    return np.stack([
        q.demand_row(p.requests, p.replicas - sum(p.prev.values()))
        for p in problems
    ])


def make_quota(snap, problems, share=0.5, generation=1, quotad=("t0", "t1",
               "t2", "t3"), capped=("t1", "t3"), cap_cpu=9000, raise_=()):
    """``overall`` leaves each quota'd namespace ``share`` of its demand
    (``raise_``: namespaces given all of it); each capped one a ``cpu``
    hard limit on the four first members."""
    dims = list(snap.dims)
    r = len(dims)
    ns_index = {ns: i for i, ns in enumerate(quotad)}
    demand = demand_of(problems, dims)
    total = np.zeros((len(quotad), r), np.int64)
    for p, d in zip(problems, demand):
        i = ns_index.get(p.namespace)
        if i is not None:
            total[i] += d
    remaining = (total * share).astype(np.int64)
    for ns in raise_:
        remaining[ns_index[ns]] = total[ns_index[ns]]
    remaining[:, dims.index("pods")] = UNLIMITED
    cap_index = {ns: i for i, ns in enumerate(c for c in capped
                                              if c in ns_index)}
    caps = np.full((len(cap_index), C, r), UNLIMITED, np.int64)
    caps[:, :4, dims.index("cpu")] = cap_cpu
    return QuotaSnapshot(
        dims=dims, ns_index=ns_index, remaining=remaining,
        cap_index=cap_index, cluster_caps=caps, generation=generation,
        cap_token=hash((tuple(cap_index), cap_cpu)) if cap_index else 0,
    )


def oracle(snap, problems, quota, remaining=None):
    """(admitted by key, placements by key) of refimpl.quota_np for the
    wave, at ``remaining`` (default: the snapshot's own, before a debit)."""
    dims = list(snap.dims)
    remaining = quota.remaining if remaining is None else remaining
    ref = TensorScheduler(snap, trace_manifest="")  # availability only
    cp = compile_placement(PLACEMENT, snap)
    cand = cp.terms[0][1] & cp.taint_ok & cp.spread_field_ok
    avail, caps = {}, {}
    for p in problems:
        req = per_replica_vector(p.requests, dims)
        avail[p.key] = ref._availability_np(
            req[None, :], np.asarray([p.replicas], np.int32))[0]
        row = quota.cap_index.get(p.namespace, -1)
        if row >= 0:
            caps[p.key] = cluster_caps_seq(quota.cluster_caps, row, req)
    return admit_and_place(
        [p.key for p in problems],
        [quota.ns_index.get(p.namespace, -1) for p in problems],
        demand_of(problems, dims), remaining,
        names=snap.names,
        placements={p.key: p.prev for p in problems},
        candidates={p.key: cand for p in problems},
        strategies={p.key: cp.strategy for p in problems},
        replicas={p.key: p.replicas for p in problems},
        static_w={p.key: cp.static_weights for p in problems},
        avail=avail, cap_rows=caps,
        fresh={p.key: p.fresh for p in problems},
    )


def assert_wave(snap, problems, results, quota, remaining=None):
    """Admission and placements of every row against the oracle. Returns
    the denied positions."""
    admitted, placed = oracle(snap, problems, quota, remaining)
    denied = []
    assert len(results) == len(problems)
    for i, (p, res) in enumerate(zip(problems, results)):
        assert res.key == p.key
        if not admitted[p.key]:
            assert res.error == QUOTA_EXCEEDED_ERROR, (i, res.error)
            assert dict(res.clusters) == {}
            denied.append(i)
        elif res.success:
            assert dict(res.clusters) == placed[p.key], (i, p.key)
        else:
            # unschedulable: the oracle leaves the previous placement
            assert res.error != QUOTA_EXCEEDED_ERROR
            assert placed[p.key] == dict(p.prev), (i, res.error)
    return denied


def engine(snap):
    eng = TensorScheduler(snap, chunk_size=256, trace_manifest="")
    eng.fleet_threshold = 64
    return eng


def last_span(name):
    return [s for s in tracer.dump() if s["name"] == name][-1]


def route_count(route):
    return quota_admission_passes.value(route=route)


# -- the resident route -------------------------------------------------------


def test_a_quotad_batch_rides_the_table_whole(snap):
    """Admission and placements as the reference's, both outcomes in every
    quota'd namespace, an unquota'd namespace never denied, the answer lazy
    but for the rows read, the table's batch as long as the presented one."""
    eng = engine(snap)
    problems = build_problems(snap)
    quota = make_quota(snap, problems)
    before = quota.remaining.copy()
    eng.set_quota(quota)
    resident = route_count("resident")
    rows0 = [quota_admission_rows.value(outcome=o)
             for o in ("admitted", "denied", "unquotad")]
    res = eng.schedule(problems)
    assert isinstance(res, fleet_mod._FleetResultList)
    assert len(eng._fleet.batch.rows_np) == len(problems)
    assert route_count("resident") == resident + 1
    first_denied = int(np.flatnonzero(res.quota.denied())[0])
    assert type(res[first_denied]) is ScheduleResult
    assert res[first_denied].error == QUOTA_EXCEEDED_ERROR
    assert set(res._cache) == {first_denied}  # nothing else materialised
    denied = assert_wave(snap, problems, res, quota, before)
    by_ns = {}
    for i, p in enumerate(problems):
        by_ns.setdefault(p.namespace, set()).add(i in denied)
    for ns in ("t0", "t1", "t2", "t3"):
        assert by_ns[ns] == {True, False}, ns  # the FIFO cut lies inside
    assert by_ns["t4"] == by_ns["t5"] == {False}
    span = last_span("scheduler.quota")
    assert span["attrs"]["rows"] == len(problems)
    assert span["attrs"]["denied"] == len(denied)
    assert span["attrs"]["dispatched"] == 1
    assert span["attrs"]["host_rows"] == 0  # the host derives no demand
    quota_rows = sum(p.namespace in quota.ns_index for p in problems)
    assert span["attrs"]["quota_rows"] == quota_rows
    rows1 = [quota_admission_rows.value(outcome=o)
             for o in ("admitted", "denied", "unquotad")]
    assert [b - a for a, b in zip(rows0, rows1)] == [
        quota_rows - len(denied), len(denied), len(problems) - quota_rows]
    # the debit: committed after the solve, from the admitted demand
    demand = demand_of(problems, list(snap.dims))
    used = np.zeros_like(before)
    for i, p in enumerate(problems):
        j = quota.ns_index.get(p.namespace)
        if j is not None and i not in denied:
            used[j] += demand[i]
    limited = before < UNLIMITED
    assert np.array_equal(
        quota.remaining, np.where(limited, before - used, before))


def test_a_generation_move_is_an_identity_pass(snap):
    """A quota generation that moves ``remaining`` alone (a raise, then
    back) under an unmoved mask_token: ``path`` = identity, nothing
    packed, the list's length kept, denied rows cleared by the raise and
    denied again after it; the same generation again replays."""
    eng = engine(snap)
    problems = build_problems(snap)
    eng.set_quota(make_quota(snap, problems, generation=1))
    first = eng.schedule(problems)
    denied1 = set(np.flatnonzero(first.quota.denied()).tolist())

    raised = make_quota(snap, problems, generation=2, raise_=("t0", "t2"))
    before = raised.remaining.copy()
    eng.set_quota(raised)
    res = eng.schedule(problems)
    assert last_span("scheduler.schedule")["attrs"]["path"] == "identity"
    assert last_span("scheduler.solve")["attrs"]["rows_packed"] == 0
    span = last_span("scheduler.quota")
    assert (span["attrs"]["host_rows"], span["attrs"]["dispatched"]) == (0, 1)
    assert len(res) == len(problems)
    denied2 = set(assert_wave(snap, problems, res, raised, before))
    cleared = denied1 - denied2
    assert cleared and all(
        problems[i].namespace in ("t0", "t2") for i in cleared)
    assert not any(problems[i].namespace in ("t0", "t2") for i in denied2)

    replayed = route_count("replayed")
    again = eng.schedule(problems)  # the same rows, the same generation
    assert route_count("replayed") == replayed + 1
    assert last_span("scheduler.quota")["attrs"]["dispatched"] == 0
    assert set(np.flatnonzero(again.quota.denied()).tolist()) == denied2

    lowered = make_quota(snap, problems, generation=3)
    before = lowered.remaining.copy()
    eng.set_quota(lowered)
    res = eng.schedule(problems)
    assert last_span("scheduler.schedule")["attrs"]["path"] == "identity"
    assert set(assert_wave(snap, problems, res, lowered, before)) == denied1


def test_fifo_follows_the_presented_order_not_the_slot_order(snap):
    """The same bindings presented in another order than the table gave
    them slots in: the FIFO prefix of every namespace is cut in the
    PRESENTED order."""
    eng = engine(snap)
    problems = build_problems(snap)
    eng.set_quota(make_quota(snap, problems, generation=1))
    eng.schedule(problems)
    order = np.random.default_rng(11).permutation(len(problems))
    shuffled = [problems[int(i)] for i in order]
    quota = make_quota(snap, shuffled, generation=2)
    before = quota.remaining.copy()
    eng.set_quota(quota)
    res = eng.schedule(shuffled)
    rows = eng._fleet.batch.rows_np
    assert not np.array_equal(rows, np.sort(rows))  # slots are not in order
    assert isinstance(res, fleet_mod._FleetResultList)
    denied = assert_wave(snap, shuffled, res, quota, before)
    # and it is not the slot order's cut
    in_slot_order, _ = oracle(snap, problems, quota, before)
    assert {shuffled[i].key for i in denied} != {
        k for k, ok in in_slot_order.items() if not ok}


def test_two_passes_in_one_generation_share_the_debited_budget(snap):
    eng = engine(snap)
    a = build_problems(snap, prefix="a", seed=21)
    b = build_problems(snap, prefix="b", seed=22)
    quota = make_quota(snap, a + b, share=0.5)
    eng.set_quota(quota)
    start = quota.remaining.copy()
    res_a = eng.schedule(a)
    assert_wave(snap, a, res_a, quota, start)
    after_a = quota.remaining.copy()
    assert (after_a <= start).all() and (after_a < start).any()
    res_b = eng.schedule(b)  # another batch, the same generation
    assert isinstance(res_b, fleet_mod._FleetResultList)
    denied_b = assert_wave(snap, b, res_b, quota, after_a)
    fresh_budget, _ = oracle(snap, b, quota, start)
    assert len(denied_b) > sum(not ok for ok in fresh_budget.values())


def test_a_cap_move_drops_the_table_and_bounds_anew(snap):
    eng = engine(snap)
    problems = build_problems(snap)
    quota = make_quota(snap, problems, cap_cpu=9000, generation=1)
    before = quota.remaining.copy()
    eng.set_quota(quota)
    assert_wave(snap, problems, eng.schedule(problems), quota, before)
    table = eng._fleet
    tight = make_quota(snap, problems, cap_cpu=2000, generation=2)
    before = tight.remaining.copy()
    eng.set_quota(tight)
    assert eng._fleet is None  # cap rows are baked into the profile slots
    res = eng.schedule(problems)
    assert eng._fleet is not table
    assert_wave(snap, problems, res, tight, before)
    capped = [i for i, p in enumerate(problems)
              if p.namespace in tight.cap_index and res[i].success]
    assert capped and any(
        max(res[i].clusters.get(f"m{j:02d}", 0) for j in range(4)) > 0
        for i in capped)


def test_a_namespace_set_move_rederives_the_column(snap):
    """Another namespace SET (one leaves, one joins, the indices shift):
    the column is derived anew from the pinned bindings, no row is packed,
    the pass stays an identity pass."""
    eng = engine(snap)
    problems = build_problems(snap)
    eng.set_quota(make_quota(snap, problems, capped=(), generation=1))
    eng.schedule(problems)
    col = eng._fleet._st["ns_idx"][eng._fleet.batch.rows_np].copy()
    moved = make_quota(snap, problems, quotad=("t1", "t2", "t4"), capped=(),
                       generation=2)
    before = moved.remaining.copy()
    eng.set_quota(moved)
    res = eng.schedule(problems)
    assert last_span("scheduler.schedule")["attrs"]["path"] == "identity"
    assert last_span("scheduler.solve")["attrs"]["rows_packed"] == 0
    now = eng._fleet._st["ns_idx"][eng._fleet.batch.rows_np]
    assert not np.array_equal(col, now)
    assert now.tolist() == [
        moved.ns_index.get(p.namespace, -1) for p in problems]
    denied = assert_wave(snap, problems, res, moved, before)
    assert {problems[i].namespace for i in denied} == {"t1", "t2", "t4"}
    # the same set under another dict object touches nothing
    same = make_quota(snap, problems, quotad=("t1", "t2", "t4"), capped=(),
                      generation=3)
    eng.set_quota(same)
    eng.schedule(problems)
    assert eng._fleet._ns_src is same.ns_index
    assert eng._fleet._st["ns_idx"][eng._fleet.batch.rows_np].tolist() == (
        now.tolist())


def test_rows_off_the_fleet_keep_the_partition_route(snap):
    """Rows of a quota'd namespace that leave the fleet (a Divided row of
    more than MAX_REPLICAS_FAST replicas): the whole wave takes the host
    partition, with the same answers, FIFO over the presented order."""
    eng = engine(snap)
    problems = build_problems(snap)
    for i in (7, 130, 131, 290):
        problems[i] = dataclasses.replace(
            problems[i], replicas=fleet_mod.MAX_REPLICAS_FAST + 40 + i,
            namespace="t0", prev={})
    quota = make_quota(snap, problems, capped=())
    before = quota.remaining.copy()
    eng.set_quota(quota)
    partition = route_count("partition")
    res = eng.schedule(problems)
    assert route_count("partition") == partition + 1
    assert type(res) is list
    span = last_span("scheduler.quota")
    assert span["attrs"]["host_rows"] == len(problems)
    assert span["parent_id"] == last_span("scheduler.schedule")["span_id"]
    denied = assert_wave(snap, problems, res, quota, before)
    assert any(problems[i].namespace == "t0" for i in denied)


def test_a_small_batch_keeps_the_partition_route(snap):
    eng = engine(snap)
    problems = build_problems(snap, n=40)
    quota = make_quota(snap, problems, capped=())
    before = quota.remaining.copy()
    eng.set_quota(quota)
    res = eng.schedule(problems)  # under fleet_threshold: the host path
    assert type(res) is list
    assert assert_wave(snap, problems, res, quota, before)


def test_replicas_held_on_a_member_that_left_count_as_held(snap):
    """prev_counts keeps no site the snapshot lacks; the demand still
    counts what the binding holds there (the usage controller does)."""
    eng = engine(snap)
    problems = build_problems(snap)
    for i in range(0, len(problems), 3):
        p = problems[i]
        problems[i] = dataclasses.replace(
            p, prev={**p.prev, "gone-member": 4}, namespace="t0")
    quota = make_quota(snap, problems, capped=())
    before = quota.remaining.copy()
    eng.set_quota(quota)
    res = eng.schedule(problems)
    assert isinstance(res, fleet_mod._FleetResultList)
    rows = eng._fleet.batch.rows_np
    assert int(eng._fleet._st["prev_rest"][rows].sum()) == 4 * len(
        range(0, len(problems), 3))
    # admission alone: the divider's oracle has no column for such a site
    admitted, _ = oracle(snap, problems, quota, before)
    assert [not admitted[p.key] for p in problems] == (
        res.quota.denied().tolist())


# -- a row that asks nothing --------------------------------------------------


def test_the_oracle_lets_a_row_that_asks_nothing_through():
    """The rule alone, on a hand-made line of one namespace: the kernel's
    mirror (admit_wave_np) denies a zero demand behind the cut; the wave's
    rule (asking_ns_ids) takes the row out of the line first."""
    ns = [0, 0, 0, 0]
    demand = np.asarray([[3], [4], [0], [1]], np.int64)
    remaining = np.asarray([[5]], np.int64)
    assert admit_wave_np(ns, demand, remaining)[0] == [True, False, False,
                                                       False]
    asking = asking_ns_ids(ns, demand)
    assert asking == [0, 0, -1, 0]
    flags, used = admit_wave_np(asking, demand, remaining)
    assert flags == [True, False, True, False] and used.tolist() == [[3]]


@pytest.mark.parametrize("route", ["resident", "partition"])
def test_a_row_that_asks_nothing_is_admitted_behind_the_cut(snap, route):
    """A binding whose delta is not positive (it holds what it wants, or
    scales down) is not the quota's to deny: behind its namespace's FIFO
    cut, and in a namespace with nothing left at all, it is admitted and
    divided, on both routes, and charges nothing."""
    eng = engine(snap)
    problems = build_problems(snap)
    names = snap.names
    steady = list(range(len(problems) - 40, len(problems), 2))
    for k, i in enumerate(steady):
        held = {names[k % C]: 3, names[(k + 1) % C]: 2}
        problems[i] = dataclasses.replace(
            problems[i], prev=held, replicas=5 if k % 2 else 4,  # or 1 down
            namespace="t0" if k % 4 < 2 else "t1", fresh=False)
    if route == "partition":
        # a row off the fleet, in a namespace without a quota (its demand
        # would hold the front of a quota'd line)
        problems[3] = dataclasses.replace(
            problems[3], replicas=fleet_mod.MAX_REPLICAS_FAST + 9, prev={},
            namespace="t5")
    quota = make_quota(snap, problems, share=0.3)
    dims = list(snap.dims)
    quota.remaining[quota.ns_index["t1"], dims.index("cpu")] = 0
    before = quota.remaining.copy()
    eng.set_quota(quota)
    count = route_count(route)
    res = eng.schedule(problems)
    assert route_count(route) == count + 1
    denied = set(assert_wave(snap, problems, res, quota, before))
    assert not denied & set(steady)
    assert all(res[i].success and sum(res[i].clusters.values())
               == problems[i].replicas for i in steady)
    asked = {ns: [i for i, p in enumerate(problems) if p.namespace == ns
                  and p.replicas > sum(p.prev.values())]
             for ns in ("t0", "t1")}
    assert set(asked["t1"]) <= denied  # nothing left: every asking row
    assert denied & set(asked["t0"]) and set(asked["t0"]) - denied
    assert min(steady) > min(denied & set(asked["t0"]))  # behind the cut


# -- the demand on the device -------------------------------------------------


def test_the_device_demand_is_demand_row_s_clamped_product():
    """Every (request, delta) the host rule can meet, the absurd ones
    too: the product is never read where it would pass the clamp."""
    import jax.numpy as jnp

    dims = ["cpu", "memory", "pods"]
    big = 1 << 40
    reqs = [
        {"cpu": 250, "memory": 512 << 20},
        {"cpu": big, "memory": (1 << 62)},  # times any delta: wraps int64
        {"cpu": DEMAND_CLAMP // 128, "memory": DEMAND_CLAMP // 128 + 1},
        {"cpu": DEMAND_CLAMP, "memory": DEMAND_CLAMP + 1},
        {"cpu": 0, "memory": 1},
    ]
    cases = [(k, rep, held) for k in range(len(reqs))
             for rep, held in ((1, 0), (128, 0), (128, 1), (7, 9), (0, 0),
                               (2_000_000_000, 0), (65_536, 3))]
    n = len(cases)
    profs = np.stack([per_replica_vector(r, dims) for r in reqs])
    q = QuotaSnapshot(dims, {}, np.zeros((0, 3), np.int64), {},
                      np.zeros((0, 1, 3), np.int64), 0, 0)
    want = np.stack([
        q.demand_row(reqs[k], rep - held) for k, rep, held in cases])
    prev = np.zeros((n, fleet_mod.K_PREV), np.int32)
    prev[:, 0] = [min(h, 2) for _, _, h in cases]
    lost = np.asarray([h - min(h, 2) for _, _, h in cases], np.int32)
    ns, demand, quota_rows = fleet_mod._fleet_quota(
        jnp.asarray(profs), jnp.arange(n, dtype=jnp.int32),
        jnp.zeros(n, jnp.int32), jnp.asarray(lost),
        jnp.asarray([k for k, _, _ in cases], jnp.int32),
        jnp.asarray([r for _, r, _ in cases], jnp.int32), jnp.asarray(prev),
    )
    # a row that asks nothing is handed on as a row without a quota, and
    # still counted among its namespace's
    assert int(quota_rows) == n
    assert np.array_equal(np.asarray(ns), np.where(want.any(axis=1), 0, -1))
    assert (np.asarray(ns) < 0).sum() == 2 * len(reqs)  # (7, 9) and (0, 0)
    assert np.array_equal(np.asarray(demand), want)
    assert want.max() == DEMAND_CLAMP and (want >= 0).all()


def test_a_row_outside_every_quota_has_no_demand():
    import jax.numpy as jnp

    n = 4
    ns_idx = jnp.asarray([-1, 0, -1, 2], jnp.int32)
    ns, demand, quota_rows = fleet_mod._fleet_quota(
        jnp.full((4, 3), 5, jnp.int64),
        jnp.asarray([3, 1, 0, -1], jnp.int32),  # the last is padding
        ns_idx, jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.full(n, 2, jnp.int32),
        jnp.zeros((n, fleet_mod.K_PREV), jnp.int32),
    )
    assert np.asarray(ns).tolist() == [2, 0, -1, -1]
    assert int(quota_rows) == 2
    assert np.asarray(demand).tolist() == [[10] * 3, [10] * 3, [0] * 3,
                                           [0] * 3]


# -- a Duplicated row, a newcomer of another namespace ------------------------


def test_a_newcomer_of_another_namespace_is_packed_anew(snap):
    """An object equal in everything but its namespace is not 'equal': its
    row takes the new namespace's index (and cap profile)."""
    eng = engine(snap)
    problems = build_problems(snap, ns_of=lambda i: "t5")
    quota = make_quota(snap, problems, capped=())
    eng.set_quota(quota)
    res = eng.schedule(problems)
    assert not res.quota.denied().any()
    moved = list(problems)
    for i in range(0, 60):
        moved[i] = dataclasses.replace(problems[i], namespace="t0")
    tight = make_quota(snap, moved, capped=(), generation=2, share=0.3)
    before = tight.remaining.copy()
    eng.set_quota(tight)
    res = eng.schedule(moved)
    assert last_span("scheduler.solve")["attrs"]["rows_packed"] == 60
    denied = assert_wave(snap, moved, res, tight, before)
    assert denied and all(i < 60 for i in denied)


def test_a_duplicated_row_s_demand_is_its_delta_too(snap):
    eng = engine(snap)
    dup = duplicated_placement()
    problems = build_problems(snap)
    for i in range(0, len(problems), 5):
        problems[i] = dataclasses.replace(
            problems[i], placement=dup, replicas=3, prev={}, namespace="t2")
    quota = make_quota(snap, problems, capped=(), share=0.4)
    before = quota.remaining.copy()
    eng.set_quota(quota)
    res = eng.schedule(problems)
    assert isinstance(res, fleet_mod._FleetResultList)
    demand = demand_of(problems, list(snap.dims))
    want, _ = admit_wave_np(
        asking_ns_ids(
            [quota.ns_index.get(p.namespace, -1) for p in problems], demand),
        demand, before)
    assert [not ok for ok in want] == res.quota.denied().tolist()
    first = next(i for i in range(0, len(problems), 5) if want[i])
    assert res[first].success and set(res[first].clusters.values()) == {3}
