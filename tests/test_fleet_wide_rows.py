"""Wide rows as row state of the fleet table.

A Divided row whose counts pass a one-byte cell, or a row whose previous
result names more members than a row's K_PREV columns hold, rides the fleet
table like any other:

- a previous result past K_PREV sites lives in a slot of the wide table
  (its first column names the slot), read by every kernel that reads a
  previous result; the quota's held sum reads it from ``prev_rest``;
- the table's cells widen to two bytes from the first Divided row past 255
  replicas (the meta, cell-delta and entry words with them).

Every case holds the fleet's answers to the general host path and, where a
plain reference exists, to ``refimpl/divider_np.py`` or
``refimpl/quota_np.py``, on seeded content, at a small size, on the CPU.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from karmada_tpu import native
from karmada_tpu.api.policy import ClusterAffinity, ClusterAffinityTerm
from karmada_tpu.ops.quota import UNLIMITED
from karmada_tpu.refimpl.divider_np import assign_batch_np
from karmada_tpu.refimpl.quota_np import admit_and_place
from karmada_tpu.scheduler import (
    QUOTA_EXCEEDED_ERROR,
    BindingProblem,
    ClusterSnapshot,
    TensorScheduler,
)
from karmada_tpu.scheduler import fleet as fleet_mod
from karmada_tpu.scheduler.fleet import K_PREV, NARROW_CELL_MAX, FleetTable
from karmada_tpu.scheduler.quota import QuotaSnapshot, per_replica_vector
from karmada_tpu.utils import metrics
from karmada_tpu.utils.builders import (
    aggregated_placement,
    duplicated_placement,
    dynamic_weight_placement,
    new_cluster,
    static_weight_placement,
)
from karmada_tpu.utils.tracing import tracer

C = 40
NOT_ENOUGH = "clusters available replicas are not enough"
NO_FIT = "no clusters fit the placement"
PROFILES = [{"cpu": 250 * (k + 1), "memory": (256 << 20) * (k + 1)}
            for k in range(3)]


def _snapshot(c: int = C, cpu=lambda i: 200 + 37 * (i % 7)) -> ClusterSnapshot:
    return ClusterSnapshot([
        new_cluster(f"m{i:03d}", cpu=str(cpu(i)), memory="8000Gi",
                    pods=100_000)
        for i in range(c)
    ])


SNAP = _snapshot()
NAMES = SNAP.names
PLACEMENTS = [
    dynamic_weight_placement(),
    aggregated_placement(),
    static_weight_placement(
        {n: (i % 3) + 1 for i, n in enumerate(NAMES[:12])}),
    duplicated_placement(),
]


def _row(rng, key: str, kind: str, placement=None) -> BindingProblem:
    """A row of one of the kinds: ``narrow`` (what every sibling cell
    draws: 1-39 replicas, at most 8 previous sites), ``sites`` (33..C
    previous sites), ``mid`` (129-255 replicas), ``big`` (256-1,000
    replicas, any previous result)."""
    reps, n_prev = {
        "narrow": (rng.integers(1, 40), rng.integers(0, 9)),
        "sites": (rng.integers(1, 300), rng.integers(K_PREV + 1, C + 1)),
        "mid": (rng.integers(129, 256), rng.integers(0, 9)),
        "big": (rng.integers(256, 1001), rng.integers(0, C + 1)),
    }[kind]
    sites = rng.choice(C, int(n_prev), replace=False)
    return BindingProblem(
        key=key,
        placement=placement or PLACEMENTS[int(rng.integers(0, 4))],
        replicas=int(reps), requests=PROFILES[int(rng.integers(0, 3))],
        gvk="apps/v1/Deployment",
        prev={NAMES[j]: int(rng.integers(1, 25)) for j in sites},
        fresh=bool(rng.random() < 0.1),
    )


def _batch(seed: int, n: int = 320, kinds=("narrow", "sites", "mid", "big"),
           prefix: str = "b") -> list:
    rng = np.random.default_rng(seed)
    return [_row(rng, f"{prefix}{i}", kinds[i % len(kinds)])
            for i in range(n)]


def _engine(snap=SNAP) -> TensorScheduler:
    eng = TensorScheduler(snap, chunk_size=256, mesh=False,
                          trace_manifest="")
    eng.fleet_threshold = 1
    return eng


def _host(snap, problems) -> list:
    ref = TensorScheduler(snap, mesh=False, trace_manifest="")
    return ref._schedule_host(
        problems, [ref._compiled(p.placement) for p in problems])


def _answer(res) -> tuple:
    return res.error, dict(res.clusters), res.affinity_name


def _assert_same(got, want) -> None:
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.key == w.key
        assert _answer(g) == _answer(w), g.key


def _refimpl(snap, problems) -> list:
    """(error, clusters) of refimpl/divider_np for each row: its candidates
    (affinity, the taint leniency of its previous members) and its merged
    availability as the general estimator answers."""
    ref = TensorScheduler(snap, mesh=False, trace_manifest="")
    dims = list(snap.dims)
    out = []
    for p in problems:
        cp = ref._compiled(p.placement)
        prev = np.zeros(snap.num_clusters, np.int32)
        for name, n in p.prev.items():
            prev[snap.index[name]] = n
        cand = cp.terms[0][1] & cp.spread_field_ok & (cp.taint_ok | (prev > 0))
        req = per_replica_vector(p.requests, dims)
        avail = ref._availability_np(
            req[None, :], np.asarray([p.replicas], np.int32))[0]
        got, uns = assign_batch_np(
            np.asarray([cp.strategy], np.int32),
            np.asarray([p.replicas], np.int32), cand[None, :],
            cp.static_weights.astype(np.int32)[None, :],
            np.minimum(avail, 2**31 - 1).astype(np.int32)[None, :],
            prev[None, :], np.asarray([p.fresh]))
        if not cand.any():
            out.append((NO_FIT, {}))
        elif uns[0]:
            out.append((NOT_ENOUGH, {}))
        else:
            out.append(("", {snap.names[j]: int(got[0, j])
                             for j in np.flatnonzero(got[0])}))
    return out


def _solve_spans() -> list:
    return [s for s in tracer.dump() if s["name"] == "scheduler.solve"]


# -- the answers --------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 29])
def test_wide_rows_ride_the_table_with_the_host_path_s_answers(seed):
    problems = _batch(seed)
    eng = _engine()
    tracer.clear()
    got = eng.schedule(problems)
    # the batch rode the table whole: the lazy list, no host path
    assert isinstance(got, fleet_mod._FleetResultList)
    assert eng._pass_path == "full"
    names = {s["name"] for s in tracer.dump()}
    assert "scheduler.host" not in names
    (solve,) = _solve_spans()
    assert solve["attrs"]["host_rows"] == 0
    table = eng._fleet
    assert table._cell_bytes == 2
    wide = sum(1 for p in problems if len(p.prev) > K_PREV or (
        p.replicas > NARROW_CELL_MAX and p.placement is not PLACEMENTS[3]))
    assert solve["attrs"]["wide_rows"] == wide
    assert table._wide_n == sum(1 for p in problems if len(p.prev) > K_PREV)
    _assert_same(got, _host(SNAP, problems))
    want = _refimpl(SNAP, problems)
    assert [(g.error, dict(g.clusters)) for g in got] == want


def test_a_count_past_one_byte_on_one_feasible_member():
    """Every replica on the one member the affinity leaves: a count of
    several hundred in one cell, and the member's previous count too."""
    one = dynamic_weight_placement(
        cluster_affinity=ClusterAffinity(cluster_names=[NAMES[5]]))
    rng = np.random.default_rng(7)
    problems = _batch(7, n=260, kinds=("narrow",))
    for i, reps in enumerate((300, 700, 999, 256)):
        problems[i * 50] = dataclasses.replace(
            problems[i * 50], placement=one, replicas=reps, fresh=False,
            requests=PROFILES[0],
            prev={NAMES[5]: int(rng.integers(260, 500))} if i % 2 else {})
    eng = _engine()
    got = eng.schedule(problems)
    assert eng._fleet._cell_bytes == 2
    counts = [dict(got[i * 50].clusters) for i in range(4)]
    assert counts[1] == {NAMES[5]: 700} and counts[2] == {NAMES[5]: 999}
    _assert_same(got, _host(SNAP, problems))
    assert [(g.error, dict(g.clusters)) for g in got] == _refimpl(
        SNAP, problems)


def test_more_than_255_members_placed_at_512_members():
    snap = _snapshot(512, cpu=lambda i: 40 + (i % 5))
    rng = np.random.default_rng(11)
    problems = [
        BindingProblem(
            key=f"w{i}", placement=dynamic_weight_placement(),
            replicas=int(rng.integers(900, 1001)),
            requests=PROFILES[0], gvk="apps/v1/Deployment",
            prev={snap.names[j]: 1 for j in rng.choice(512, 300,
                                                       replace=False)}
            if i % 2 else {},
            fresh=bool(i % 3 == 0),
        )
        for i in range(8)
    ]
    eng = _engine(snap)
    got = eng.schedule(problems)
    assert eng._fleet._cell_bytes == 2
    assert max(len(g.clusters) for g in got) > 255
    _assert_same(got, _host(snap, problems))
    assert [(g.error, dict(g.clusters)) for g in got] == _refimpl(
        snap, problems)


def test_a_multi_term_row_past_128_replicas_takes_the_term_that_fits():
    """The term kernel's predicate holds a row's replicas whole: 4 members
    of 50 replicas each cannot take 300 (a cut at 128 replicas would say
    they can), so the row falls back to its second term, as the host path
    chooses."""
    snap = _snapshot(cpu=lambda i: 25 if i < 4 else 400)
    terms = [
        ClusterAffinityTerm(affinity_name="small",
                            cluster_names=list(snap.names[:4])),
        ClusterAffinityTerm(affinity_name="rest",
                            cluster_names=list(snap.names[4:])),
    ]
    failover = dynamic_weight_placement(cluster_affinities=terms)
    rng = np.random.default_rng(13)
    problems = _batch(13, n=256, kinds=("narrow",))
    for i, reps in enumerate((300, 150, 190, 1000, 100)):
        problems[i * 40] = _row(rng, f"t{i}", "narrow", failover)
        problems[i * 40] = dataclasses.replace(
            problems[i * 40], replicas=reps, prev={}, fresh=False,
            requests=PROFILES[1])
    eng = _engine(snap)
    got = eng.schedule(problems)
    assert eng._pass_path == "full"
    assert [got[i * 40].affinity_name for i in range(5)] == [
        "rest", "small", "small", "rest", "small"]
    _assert_same(got, _host(snap, problems))


def test_wide_rows_under_a_mesh_answer_as_on_one_device():
    """The wide table replicates over the mesh as the slot tables do, and
    two-byte residents shard by rows as one-byte ones."""
    from karmada_tpu.parallel.mesh import scheduling_mesh

    problems = _batch(5, n=300)
    eng = TensorScheduler(SNAP, chunk_size=256, mesh=scheduling_mesh(2),
                          trace_manifest="")
    eng.fleet_threshold = 1
    _assert_same(eng.schedule(problems), _host(SNAP, problems))
    assert eng._fleet._cell_bytes == 2 and eng._fleet._dev_wide is not None
    _assert_same(eng.schedule(problems), _host(SNAP, problems))
    assert eng._pass_path == "identity"


# -- the slots ----------------------------------------------------------------


def _slot_of(table: FleetTable, key: str) -> int:
    first = int(table._st["prev_sites"][table._key_row[key], 0])
    return -1 - first if first < 0 else -1


def test_a_wide_row_swapped_narrow_and_back_frees_and_takes_its_slot():
    problems = _batch(17, kinds=("narrow", "sites"))
    eng = _engine()
    eng.schedule(problems)
    table = eng._fleet
    wide_key = problems[1].key
    slot = _slot_of(table, wide_key)
    assert slot >= 0
    held = table._wide_n
    narrow = dataclasses.replace(
        problems[1], prev=dict(list(problems[1].prev.items())[:K_PREV]))
    swapped = list(problems)
    swapped[1] = narrow
    _assert_same(eng.schedule(swapped), _host(SNAP, swapped))
    assert _slot_of(table, wide_key) == -1
    assert slot in table._wide_free
    # back: the freed slot is taken again, nothing new handed out
    again = list(problems)
    again[1] = dataclasses.replace(problems[1])
    _assert_same(eng.schedule(again), _host(SNAP, again))
    assert _slot_of(table, wide_key) == slot
    assert table._wide_n == held and slot not in table._wide_free


def test_growth_and_compaction_keep_each_wide_row_s_slot():
    eng = _engine()
    first = _batch(19, n=200, kinds=("narrow", "sites"), prefix="a")
    _assert_same(eng.schedule(first), _host(SNAP, first))
    table = eng._fleet
    cap = table.cap
    # growth: 500 rows, the wide ones among them
    grown = first + _batch(23, n=300, kinds=("sites", "big"), prefix="g")
    _assert_same(eng.schedule(grown), _host(SNAP, grown))
    assert table.cap > cap
    # compaction: a small batch long enough for the others to go idle, then
    # as many new keys as the cap cannot hold beside them
    small = _batch(31, n=30, kinds=("narrow", "sites"), prefix="s")
    for _ in range(FleetTable.COMPACT_IDLE_PASSES + 2):
        eng.schedule(list(small))
    new = _batch(37, n=table.cap - 100, kinds=("sites", "narrow"),
                 prefix="n")
    n_before, handed = table.n_rows, table._wide_n
    got = eng.schedule(new)
    assert table.n_rows < n_before + len(new)  # the idle rows were dropped
    _assert_same(got, _host(SNAP, new))
    live = [_slot_of(table, k) for k in table._key_row]
    live = [s for s in live if s >= 0]
    assert len(live) == len(set(live))  # no slot held twice
    assert not set(live) & set(table._wide_free)
    # the dropped rows' slots were taken again: no more slots than ever
    # lived at once, and every one not held is free
    assert table._wide_n == max(handed, len(live))
    assert len(table._wide_free) == table._wide_n - len(live)
    _assert_same(eng.schedule(list(small)), _host(SNAP, small))


# -- the quota's held sum -----------------------------------------------------


def test_the_held_sum_of_a_wide_row_admits_as_the_reference_does():
    """``_fleet_quota`` reads a wide row's held replicas from ``prev_rest``
    (its columns hold none): demand = replicas - held, FIFO, against
    refimpl/quota_np."""
    problems = _batch(41, n=300, kinds=("narrow", "sites", "big"))
    # half the wide rows ask for more than they hold
    problems = [dataclasses.replace(
        p, namespace=f"t{i % 3}",
        replicas=sum(p.prev.values()) + 40 if (
            len(p.prev) > K_PREV and i % 2) else p.replicas,
    ) for i, p in enumerate(problems)]
    dims = list(SNAP.dims)
    probe = QuotaSnapshot(dims, {}, np.zeros((0, len(dims)), np.int64), {},
                          np.zeros((0, C, len(dims)), np.int64), 0, 0)
    demand = np.stack([
        probe.demand_row(p.requests, p.replicas - sum(p.prev.values()))
        for p in problems
    ])
    ns_index = {"t0": 0, "t1": 1}
    remaining = np.zeros((2, len(dims)), np.int64)
    for p, d in zip(problems, demand):
        if p.namespace in ns_index:
            remaining[ns_index[p.namespace]] += d
    remaining = remaining // 2
    remaining[:, dims.index("pods")] = UNLIMITED
    quota = QuotaSnapshot(dims, ns_index, remaining.copy(), {},
                          np.zeros((0, C, len(dims)), np.int64), 1, 0)
    eng = _engine()
    eng.set_quota(quota)
    got = eng.schedule(problems)
    assert isinstance(got, fleet_mod._FleetResultList)
    rows = eng._fleet.batch.rows_np
    wide = [i for i, p in enumerate(problems) if len(p.prev) > K_PREV]
    assert wide
    assert eng._fleet._st["prev_rest"][rows[wide]].tolist() == [
        sum(problems[i].prev.values()) for i in wide]
    ref = TensorScheduler(SNAP, mesh=False, trace_manifest="")
    cps = {p.key: ref._compiled(p.placement) for p in problems}
    avail = {
        p.key: ref._availability_np(
            per_replica_vector(p.requests, dims)[None, :],
            np.asarray([p.replicas], np.int32))[0]
        for p in problems
    }
    admitted, placed = admit_and_place(
        [p.key for p in problems],
        [ns_index.get(p.namespace, -1) for p in problems],
        demand, remaining, names=NAMES,
        placements={p.key: p.prev for p in problems},
        candidates={k: cp.terms[0][1] & cp.spread_field_ok
                    for k, cp in cps.items()},
        strategies={k: cp.strategy for k, cp in cps.items()},
        replicas={p.key: p.replicas for p in problems},
        static_w={k: cp.static_weights for k, cp in cps.items()},
        avail=avail, fresh={p.key: p.fresh for p in problems},
    )
    denied = 0
    for p, res in zip(problems, got):
        if not admitted[p.key]:
            denied += 1
            assert res.error == QUOTA_EXCEEDED_ERROR, p.key
        elif res.success:
            assert dict(res.clusters) == placed[p.key], p.key
        else:
            assert res.error != QUOTA_EXCEEDED_ERROR
    assert denied and any(not admitted[problems[i].key] for i in wide)


# -- the routes and what they record ------------------------------------------


def test_the_identity_and_delta_routes_carry_wide_rows():
    problems = _batch(43)
    eng = _engine()
    before = metrics.fleet_wide_rows.value()
    tracer.clear()
    first = [_answer(r) for r in eng.schedule(problems)]
    again = eng.schedule(problems)
    assert eng._pass_path == "identity"
    assert [_answer(r) for r in again] == first
    # a wide row moved (another object of the same key): the delta path
    moved = list(problems)
    for i in (1, 3, 6):
        moved[i] = dataclasses.replace(problems[i],
                                       replicas=problems[i].replicas + 7)
    got = eng.schedule(moved)
    assert eng._pass_path == "delta"
    _assert_same(got, _host(SNAP, moved))
    solves = _solve_spans()
    assert len(solves) == 3
    wide = [s["attrs"]["wide_rows"] for s in solves]
    assert all(w > 0 for w in wide) and wide[0] == wide[1]
    assert {s["attrs"]["cell_bytes"] for s in solves} == {2}
    assert metrics.fleet_wide_rows.value() - before == sum(wide)
    preps = [s for s in tracer.dump() if s["name"] == "kernel.host"
             and s["attrs"].get("phase") == "prep"]
    assert preps and all(s["attrs"]["cell_bytes"] == 2 for s in preps)
    assert preps[0]["attrs"]["wide_rows"] == wide[0]


def test_a_sibling_layout_runs_the_narrow_traces():
    """Rows such as every sibling cell draws (1-39 replicas, at most 8
    previous sites) engage no part of the wide form: one-byte cells, no
    wide table, no trace key that names either."""
    problems = _batch(47, kinds=("narrow",))
    eng = _engine()
    tracer.clear()
    _assert_same(eng.schedule(problems), _host(SNAP, problems))
    eng.schedule(problems)
    table = eng._fleet
    assert table._cell_bytes == 1 and table._wide_prev is None
    assert table._dev_wide is None
    pass_keys = [k for k in table._seen_traces if k[0] == "A"]
    assert pass_keys and all(k[-2:] == (1, None) for k in pass_keys)
    assert all(k[-1] == 1 for k in table._seen_traces if k[0] == "E")
    assert not [k for k in table._seen_traces if "wide" in k]
    assert {(s["attrs"]["wide_rows"], s["attrs"]["cell_bytes"])
            for s in _solve_spans()} == {(0, 1)}


# -- the host's half of the two-byte words ------------------------------------


@pytest.mark.parametrize("cell_bits", [8, 16])
def test_cell_deltas_fold_alike_in_c_and_numpy(cell_bits, monkeypatch):
    rng = np.random.default_rng(53)
    k_res, rows = 6, np.asarray([0, 2, 3], np.int32)
    top = (1 << cell_bits) - 1
    mirror = np.zeros((4, k_res), np.int32)
    for r in range(4):
        sites = np.sort(rng.choice(40, 3, replace=False))
        mirror[r, :3] = (sites << cell_bits) | rng.integers(1, top, 3)
    deltas, dcounts = [], []
    for r in rows.tolist():
        sites = np.sort(rng.choice(40, 4, replace=False))
        counts = rng.integers(0, top, 4)  # 0 removes the site
        deltas += ((sites << (cell_bits + 1)) | (counts + 1)).tolist()
        dcounts.append(4)
    stream = np.asarray(deltas, np.int32)
    dcounts = np.asarray(dcounts, np.int64)
    via_c = mirror.copy()
    native.apply_deltas(via_c, rows, dcounts, stream, cell_bits=cell_bits)
    monkeypatch.setattr(native, "get", lambda: None)
    via_np = mirror.copy()
    native.apply_deltas(via_np, rows, dcounts, stream, cell_bits=cell_bits)
    assert np.array_equal(via_c, via_np)
    words = np.asarray([1, -2, 0x7FFF0001], np.int32)
    assert native.decode4(words.astype("<i4").view(np.uint8)).tolist() == (
        words.tolist())
