"""ISSUE 33: failover on the fleet path. Ordered ``clusterAffinities`` are
term slots of a row, chosen on the device by the table's term kernel
(``_fleet_terms``); graceful-eviction tasks are row state masked in
``_row_masks``; NoExecute taints ride the placement slots' taint plane.

(a) fleet path == refimpl/failover_np == the host path on seeded random
    batches: 1-4 terms x every strategy x fresh / scale-up / scale-down /
    steady x 0-8 eviction tasks x tolerated and untolerated NoExecute taints
    x held-member leniency; ``clusters``, ``affinity_name`` and ``error``
    equal, one pass = one solve;
(b) rows past T_CAP / K_EVICT and multi-term + spread rows take the host
    path in the same batch and answer the same;
(c) the predicate's int32 sums hold at availabilities near MAX_INT32;
(d) a taint swap through ``update_snapshot`` and back: the slot tables are
    rebuilt, no slot is minted, no trace is new on the second turn;
(e) the identity fast path holds over a batch with eviction tasks and
    several terms across an availability-only generation move;
(f) the cell's rehearsal ends ``correct`` and its control does not;
(g) the four readers the cell brings, on a recorded span set.
"""

import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from karmada_tpu.api.cluster import Taint, Toleration
from karmada_tpu.api.policy import (
    ClusterAffinityTerm,
    LabelSelector,
    SpreadConstraint,
)
from karmada_tpu.ops import masks as mops
from karmada_tpu.refimpl.failover_np import solve_one_ordered
from karmada_tpu.scheduler import (
    BindingProblem,
    ClusterSnapshot,
    TensorScheduler,
)
from karmada_tpu.scheduler import fleet as fleet_mod
from karmada_tpu.scheduler.fleet import K_EVICT, K_PREV, T_CAP
from karmada_tpu.scheduler.snapshot import compile_placement
from karmada_tpu.utils import builders, metrics
from karmada_tpu.utils.tracing import tracer

REGIONS, ZONES, PER_ZONE = 4, 2, 3
C = REGIONS * ZONES * PER_ZONE
NOT_READY = "cluster.karmada.io/not-ready"
STRATEGIES = ("duplicated", "static", "dynamic", "aggregated")


def _place(j: int) -> tuple:
    r, z = j // (ZONES * PER_ZONE), (j // PER_ZONE) % ZONES
    return f"r{r}", f"r{r}z{z}"


def _clusters(rng, tainted=(), cpu=None, allocated_share=0.0) -> list:
    out = []
    for j in range(C):
        region, zone = _place(j)
        cores = int(cpu[j]) if cpu is not None else int(rng.integers(1, 20))
        taints = []
        if j in tainted:
            taints.append(Taint(key=NOT_READY, effect="NoExecute"))
        out.append(builders.new_cluster(
            f"m{j:02d}", cpu=str(cores), memory="4096Gi", pods=4000,
            labels={"region": region, "zone": zone}, region=region, zone=zone,
            taints=taints,
            allocated={"cpu": f"{int(cores * 1000 * allocated_share)}m"},
        ))
    return out


def _term(name: str, labels: dict | None) -> ClusterAffinityTerm:
    return ClusterAffinityTerm(
        affinity_name=name,
        label_selector=LabelSelector(match_labels=labels) if labels else None,
    )


def _selector(rng) -> dict | None:
    kind = int(rng.integers(0, 4))
    j = int(rng.integers(0, C))
    region, zone = _place(j)
    return (None, {"region": region}, {"zone": zone}, {"region": region})[kind]


def _placement(rng, strategy: str, n_terms: int, tolerant: bool, spread=()):
    kw = {"cluster_affinities": [
        _term(f"t{k}", _selector(rng)) for k in range(n_terms)]}
    if tolerant:
        kw["cluster_tolerations"] = [
            Toleration(key=NOT_READY, operator="Exists")]
    if spread:
        kw["spread_constraints"] = list(spread)
    if strategy == "static":
        named = rng.choice(C, 8, replace=False)
        return builders.static_weight_placement(
            {f"m{j:02d}": int(rng.integers(1, 5)) for j in named}, **kw)
    return {
        "duplicated": builders.duplicated_placement,
        "dynamic": builders.dynamic_weight_placement,
        "aggregated": builders.aggregated_placement,
    }[strategy](**kw)


def _placements(rng, terms=(1, 2, 3, 4)) -> list:
    return [
        _placement(rng, s, t, tol)
        for s in STRATEGIES for t in terms for tol in (False, True)
    ]


def _problem(rng, i: int, placement, max_tasks: int = K_EVICT):
    names = [f"m{j:02d}" for j in range(C)]
    replicas = int(rng.integers(0, 41)) if rng.random() > 0.03 else 0
    prev = {}
    if rng.random() < 0.75:
        for j in rng.choice(C, int(rng.integers(1, 9)), replace=False):
            prev[names[j]] = int(rng.integers(1, 10))
    if prev and rng.random() < 0.15:
        replicas = sum(prev.values())  # steady where the sites stay candidates
    evict = ()
    if rng.random() < 0.45:
        k = int(rng.integers(1, max_tasks + 1))
        evict = tuple(names[j] for j in rng.choice(C, k, replace=False))
    return BindingProblem(
        key=f"b{i}", placement=placement, replicas=replicas,
        requests={"cpu": 1000 * int(rng.integers(1, 3))},
        gvk="apps/v1/Deployment", prev=prev, evict_clusters=evict,
        fresh=bool(rng.random() < 0.1),
    )


def _problems(rng, placements, n: int) -> list:
    return [_problem(rng, i, placements[i % len(placements)])
            for i in range(n)]


def _copy_out(results) -> list:
    return [
        SimpleNamespace(clusters=dict(r.clusters), error=r.error,
                        affinity_name=r.affinity_name,
                        feasible=tuple(sorted(r.feasible)))
        for r in results
    ]


def _host_path(snap, problems) -> list:
    engine = TensorScheduler(snap, chunk_size=256, mesh=False)
    engine.fleet_threshold = 10 ** 9  # every batch stays under it
    return _copy_out(engine.schedule(problems))


def _oracle(snap, problems) -> list:
    """Each row through the reference's own control flow (try a group,
    divide, on failure the next): refimpl.failover_np over masks made here."""
    names = snap.names
    index = {n: j for j, n in enumerate(names)}
    dims = list(snap.dims)
    out = []
    for p in problems:
        cp = compile_placement(p.placement, snap)
        prev = np.zeros(C, np.int32)
        for n, v in p.prev.items():
            prev[index[n]] = v
        evicted = np.zeros(C, bool)
        evicted[[index[n] for n in p.evict_clusters]] = True
        base = (cp.taint_ok | (prev > 0)) & ~evicted & cp.spread_field_ok
        req = np.zeros((1, len(dims)), np.int64)
        for d, q in p.requests.items():
            req[0, dims.index(d)] = q
        if p.replicas > 0:
            req[0, dims.index("pods")] = max(req[0, dims.index("pods")], 1)
        avail = bench._general_avail_np(snap.available_cap, req)[0]
        avail = np.where(avail == 2**31 - 1, p.replicas, avail)
        if p.replicas == 0:
            avail = np.zeros(C, np.int64)
        got, ti, err = solve_one_ordered(
            np.stack([m for _, m in cp.terms]), base, cp.strategy,
            p.replicas, cp.static_weights, avail.astype(np.int32), prev,
            p.fresh)
        out.append(SimpleNamespace(
            clusters={} if got is None else {
                names[j]: int(got[j]) for j in np.flatnonzero(got)},
            error=err, affinity_name=cp.terms[ti][0]))
    return out


def _same(a, b, i, clusters_of_zero=True):
    assert a.error == b.error, (i, a.error, b.error)
    assert a.affinity_name == b.affinity_name, (i, a, b)
    if clusters_of_zero:
        assert a.clusters == b.clusters, (i, a.clusters, b.clusters)


# -- (a) ---------------------------------------------------------------------


@pytest.fixture(scope="module", params=[7, 2147483777, 3200100999])
def batch(request):
    rng = np.random.default_rng(request.param)
    tainted = set(rng.choice(C, 5, replace=False).tolist())
    snap = ClusterSnapshot(_clusters(rng, tainted, allocated_share=0.3))
    problems = _problems(rng, _placements(rng), 640)
    engine = TensorScheduler(snap, chunk_size=256, mesh=False)
    before = engine.solve_batches
    tracer.clear()
    got = _copy_out(engine.schedule(problems))
    return SimpleNamespace(
        snap=snap, problems=problems, engine=engine, got=got,
        solves=engine.solve_batches - before, spans=tracer.dump(),
        tainted=tainted, host_gauge=metrics.fleet_host_path_rows.value())


def test_the_batches_hold_what_they_say(batch):
    ps = batch.problems
    names = [f"m{j:02d}" for j in batch.tainted]
    terms = [len(p.placement.cluster_affinities) for p in ps]
    assert {1, 2, 3, 4} == set(terms)
    assert max(len(p.evict_clusters) for p in ps) == K_EVICT
    assert sum(1 for p in ps if not p.evict_clusters) > 200
    # held members that are tainted: the leniency decides
    assert sum(1 for p in ps if set(p.prev) & set(names)
               and not p.placement.cluster_tolerations) > 20
    assert sum(1 for p in ps if p.placement.cluster_tolerations) > 200
    assert sum(1 for p in ps if p.fresh) > 20
    assert sum(1 for p in ps if p.prev and sum(p.prev.values()) == p.replicas) > 20
    assert sum(1 for p in ps if p.replicas == 0) >= 5
    # every outcome occurs: first term, a later term, no term at all
    outcomes = {(r.affinity_name, bool(r.error)) for r in batch.got}
    assert {("t0", False), ("t1", False), ("t2", False)} <= outcomes
    assert any(err for _, err in outcomes)


def test_one_pass_is_one_solve_and_no_row_leaves_the_fleet(batch):
    assert batch.solves == 1
    assert batch.host_gauge == 0
    solve = [s for s in batch.spans if s["name"] == "scheduler.solve"]
    assert len(solve) == 1 and solve[0]["attrs"]["rows"] == 640
    assert solve[0]["attrs"]["host_rows"] == 0
    assert not [s for s in batch.spans if s["name"] == "scheduler.host"]


def test_fleet_equals_the_host_path(batch):
    want = _host_path(batch.snap, batch.problems)
    for i, (a, b) in enumerate(zip(batch.got, want)):
        _same(a, b, i)
        assert a.feasible == b.feasible, i


def test_fleet_equals_the_failover_oracle(batch):
    want = _oracle(batch.snap, batch.problems)
    for i, (a, b) in enumerate(zip(batch.got, want)):
        p = batch.problems[i]
        # a binding without replicas is assigned nothing by the oracle's
        # divider and answers its feasible set instead
        _same(a, b, i, clusters_of_zero=p.replicas > 0)


def test_the_terms_span_and_counters_carry_the_counts(batch):
    terms = [s for s in batch.spans if s["name"] == "scheduler.terms"]
    solve = [s for s in batch.spans if s["name"] == "scheduler.solve"]
    assert len(terms) == 1 and terms[0]["parent_id"] == solve[0]["span_id"]
    a = terms[0]["attrs"]
    multi = [i for i, p in enumerate(batch.problems)
             if len(p.placement.cluster_affinities) > 1]
    assert a["rows"] == len(multi) == 480
    fit = [i for i in multi if batch.got[i].error == ""
           or batch.got[i].affinity_name != "t%d" % (
               len(batch.problems[i].placement.cluster_affinities) - 1)]
    fallback = sum(1 for i in multi if not batch.got[i].error
                   and batch.got[i].affinity_name != "t0")
    assert a["fallback"] == fallback > 0
    assert 0 < a["unfit"] <= len(multi) - len(
        [i for i in fit if not batch.got[i].error])
    assert a["evicted_rows"] == sum(
        1 for p in batch.problems if p.evict_clusters)


# -- (b) ---------------------------------------------------------------------


def test_rows_past_the_caps_take_the_host_path_in_the_same_batch(capfd):
    rng = np.random.default_rng(11)
    snap = ClusterSnapshot(_clusters(rng, {1, 9}, allocated_share=0.2))
    problems = _problems(rng, _placements(rng), 512)
    n = len(problems)
    five = _placement(rng, "dynamic", T_CAP + 1, False)
    spread = _placement(rng, "aggregated", 2, False, spread=[
        SpreadConstraint(spread_by_field="cluster", min_groups=2,
                         max_groups=4)])
    past = []
    for k in range(8):
        past.append(_problem(rng, n + 3 * k, five))
        many = _problem(rng, n + 3 * k + 1, problems[2].placement)
        many.evict_clusters = tuple(
            f"m{j:02d}" for j in rng.choice(C, K_EVICT + 1, replace=False))
        past.append(many)
        past.append(_problem(rng, n + 3 * k + 2, spread))
    problems = problems + past
    engine = TensorScheduler(snap, chunk_size=256, mesh=False)
    before = engine.solve_batches
    tracer.clear()
    got = _copy_out(engine.schedule(problems))
    spans = tracer.dump()
    # the fleet's pass plus the two host chunks (ranked, round loop)
    assert engine.solve_batches - before > 1
    solve = [s for s in spans if s["name"] == "scheduler.solve"]
    assert solve[0]["attrs"]["host_rows"] == 24
    assert solve[0]["attrs"]["rows"] == n
    assert [s["attrs"]["rows"] for s in spans
            if s["name"] == "scheduler.host"] == [24]
    assert metrics.fleet_host_path_rows.value() == 24
    line = [ln for ln in capfd.readouterr().err.splitlines()
            if ln.startswith("# fleet host path")]
    assert len(line) == 1 and "24 of 536 rows" in line[0]
    assert f"8 with more than {T_CAP} affinity terms" in line[0]
    assert f"8 with more than {K_EVICT} eviction tasks" in line[0]
    assert "8 with several terms and spread constraints" in line[0]
    # the same layout again says nothing; the gauge still does
    engine.schedule(list(problems))
    assert "# fleet host path" not in capfd.readouterr().err
    assert metrics.fleet_host_path_rows.value() == 24
    want = _host_path(snap, problems)
    for i, (a, b) in enumerate(zip(got, want)):
        _same(a, b, i)
    oracle = _oracle(snap, problems)
    for i in range(len(problems)):
        if problems[i].placement is not spread and problems[i].replicas:
            _same(got[i], oracle[i], i)


# -- (c) ---------------------------------------------------------------------


def test_the_predicate_is_32_bit_and_holds_near_max_int32():
    rng = np.random.default_rng(5)
    # every member answers close to MAX_INT32 replicas for a 1-milli request
    cpu = [(2**31 - 1 - int(k)) // 1000 for k in rng.integers(0, 9, C)]
    clusters = _clusters(rng, cpu=cpu)
    for cl in clusters:
        cl.status.resource_summary.allocatable["pods"] = 2**31 - 1
    snap = ClusterSnapshot(clusters)
    placements = [
        builders.dynamic_weight_placement(cluster_affinities=[
            _term("primary", {"region": "r1"}), _term("backup", None)]),
        builders.aggregated_placement(cluster_affinities=[
            _term("primary", {"zone": "r2z1"}), _term("secondary",
                                                      {"region": "r2"}),
            _term("backup", None)]),
    ]
    problems = []
    for i in range(300):
        p = _problem(rng, i, placements[i % 2])
        p.requests = {"cpu": 1}
        p.evict_clusters = ()
        problems.append(p)
    engine = TensorScheduler(snap, chunk_size=256, mesh=False)
    got = _copy_out(engine.schedule(problems))
    # a wrapped 32-bit sum of six such members is negative: the first term
    # would read as too small for every row
    assert {r.affinity_name for r in got} == {"primary"}
    assert not any(r.error for r in got)
    want = _host_path(snap, problems)
    for i, (a, b) in enumerate(zip(got, want)):
        _same(a, b, i)

    b, c, t = 8, 12, 3
    i32 = jax.ShapeDtypeStruct((b, c), jnp.int32)
    row = jax.ShapeDtypeStruct((b,), jnp.int32)
    flag = jax.ShapeDtypeStruct((b,), jnp.bool_)
    jaxpr = jax.make_jaxpr(
        lambda *a: mops._first_fit_group_kernel(jnp, *a))(
        jax.ShapeDtypeStruct((b, t, c), jnp.bool_), row, i32, row, i32,
        flag, flag)
    wide = set()

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                if getattr(v.aval, "dtype", None) is not None and (
                        v.aval.dtype.itemsize > 4):
                    wide.add((eqn.primitive.name, str(v.aval.dtype)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert not wide, wide
    table = engine._fleet
    tr = table.batch.terms
    text = fleet_mod._fleet_terms.lower(
        *table._dev_tables, tr.rows_dev, table._dev_term_slots,
        table._dev_term_sel, *table._dev_state[:-1],
        chunk=tr.chunk, n_chunks=tr.n_chunks).as_text(debug_info=True)
    assert "fleet.terms" in text and "fleet.evict" in text


# -- (d) ---------------------------------------------------------------------


def test_a_taint_swap_and_back_mints_no_slot_and_no_trace():
    rng = np.random.default_rng(23)
    clusters = _clusters(rng, allocated_share=0.3)
    healthy = ClusterSnapshot(clusters)
    lost = [j for j in range(C) if _place(j)[0] == "r1"]
    for j in lost:
        clusters[j].spec.taints = [Taint(key=NOT_READY, effect="NoExecute")]
    tainted = ClusterSnapshot(clusters)
    assert healthy.mask_token != tainted.mask_token
    placements = _placements(rng, terms=(1, 2, 3))
    base = _problems(rng, placements, 600)
    for p in base:
        p.evict_clusters = ()
    # the wave after the loss: the bindings that held a lost member and do
    # not tolerate it come as NEW objects, those sites moved to their tasks
    names = {f"m{j:02d}" for j in lost}
    after = []
    for p in base:
        hit = [n for n in p.prev if n in names]
        if hit and not p.placement.cluster_tolerations:
            after.append(BindingProblem(
                key=p.key, placement=p.placement, replicas=p.replicas,
                requests=p.requests, gvk=p.gvk,
                prev={n: v for n, v in p.prev.items() if n not in names},
                evict_clusters=tuple(hit[:K_EVICT]), fresh=p.fresh))
        else:
            after.append(p)
    assert 100 < sum(1 for a, b in zip(after, base) if a is not b) < 400
    engine = TensorScheduler(healthy, chunk_size=256, mesh=False)
    engine.schedule(base)
    table = engine._fleet
    slots = len(table._cp_pl)
    assert slots == sum(len(p.cluster_affinities) for p in placements)
    minted = metrics.fleet_slots_minted.value()
    rebuilds = metrics.fleet_table_rebuilds.value()
    turns = []
    for turn in range(2):
        for snap, problems in ((tainted, after), (healthy, base)):
            assert engine.update_snapshot(snap)
            before = engine.solve_batches
            got = _copy_out(engine.schedule(problems))
            assert engine.solve_batches - before == 1
            turns.append((turn, engine.last_pass_new_trace))
            want = _host_path(snap, problems)
            for i, (a, b) in enumerate(zip(got, want)):
                _same(a, b, i)
            if snap is tainted:
                # nobody without a toleration stays on, or moves to, the
                # lost region
                for p, r in zip(problems, got):
                    if not p.placement.cluster_tolerations:
                        assert not set(r.clusters) & names, p.key
            assert len(table._cp_pl) == slots and engine._fleet is table
    assert metrics.fleet_slots_minted.value() == minted
    assert metrics.fleet_table_rebuilds.value() == rebuilds
    assert [new for turn, new in turns if turn == 1] == [False, False]


# -- (e) ---------------------------------------------------------------------


def test_the_identity_fast_path_holds_with_tasks_and_terms():
    rng = np.random.default_rng(31)
    clusters = _clusters(rng, {3, 4}, allocated_share=0.3)
    problems = _problems(rng, _placements(rng), 512)
    assert sum(1 for p in problems if p.evict_clusters) > 150
    engine = TensorScheduler(ClusterSnapshot(clusters), chunk_size=256,
                             mesh=False)
    engine.schedule(problems)
    assert "eligible" in engine.last_breakdown
    size = fleet_mod._fleet_terms._cache_size()
    for g in range(3):
        for cl in clusters:  # capacities move, no filter field does
            rs = cl.status.resource_summary
            rs.allocated["cpu"] = int(
                rs.allocatable["cpu"] * (0.15 + 0.1 * g + 0.2 * rng.random()))
        snap = ClusterSnapshot(clusters)
        assert snap.mask_token == engine.snapshot.mask_token
        assert engine.update_snapshot(snap)
        before = engine.solve_batches
        tracer.clear()
        got = _copy_out(engine.schedule(problems))
        spans = tracer.dump()
        assert engine.solve_batches - before == 1
        assert "eligible" not in engine.last_breakdown
        assert "terms_dispatch" in engine.last_breakdown
        assert engine.last_breakdown["upload_mb"] == 0
        assert not [s for s in spans if s["name"] == "scheduler.pack"]
        assert len([s for s in spans if s["name"] == "scheduler.terms"]) == 1
        want = _host_path(snap, problems)
        for i, (a, b) in enumerate(zip(got, want)):
            _same(a, b, i)
    assert fleet_mod._fleet_terms._cache_size() == size


def test_row_state_sizes_do_not_grow_with_the_members():
    rng = np.random.default_rng(2)
    engine = TensorScheduler(
        ClusterSnapshot(_clusters(rng)), chunk_size=256, mesh=False)
    engine.schedule(_problems(rng, _placements(rng), 300))
    table = engine._fleet
    assert table._dev_term_slots.shape == (table.cap, T_CAP)
    assert table._dev_term_sel.shape == (table.cap,)
    evict = table._dev_state[fleet_mod._STATE_FIELDS.index("evict_sites")]
    assert evict.shape == (table.cap, K_EVICT) and K_PREV == 32


# -- (f) the cell's rehearsal and its control ---------------------------------

from benchmark import control, failover as bench_failover  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    host_path_rows,
    terms_device_s,
    terms_roofline,
    terms_self_s,
)
from benchmark.reference import failover as bench_reference  # noqa: E402
from benchmark.roofline_terms import fleet_terms_count, least_seconds  # noqa: E402

CELL = "fed-100c-failover.region-loss"
FLOORS = ("rows_of_each_kind", "fallback_decided_rows",
          "capacity_fallback_rows", "eviction_decided_rows", "tolerated_rows",
          "step_kinds_compared", "rows_compared")


@pytest.mark.parametrize("seed", [1, 2147483777, 3200100999])
def test_the_cells_rehearsal_is_correct(capsys, seed):
    res = bench_run.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "4",
         "--trace", "0"], rehearse=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"] == {} and res["rehearsal"] is True
    checks = res["checks"]
    assert checks["mismatched_rows"]["value"] == 0
    assert checks["undivided_rows"]["value"] == 0
    for floor in FLOORS:
        assert checks[floor]["value"] >= checks[floor]["limit"] > 0, floor


@pytest.mark.parametrize("seed", [1, 2, 2147483777])
def test_the_cells_control_is_not_correct(seed):
    checks = control.control_checks(CELL, seed, 40, rehearse=True)
    checks.pop("_failed")
    assert bench_run.verdict(checks) is False
    assert checks["mismatched_rows"]["value"] > 0
    # the control breaks the answers, not the traffic: every floor holds
    for floor in FLOORS:
        assert checks[floor]["value"] >= checks[floor]["limit"], floor


def test_the_traced_rehearsal_reads_the_new_layers(capsys):
    res = bench_run.main(
        ["--workload", CELL, "--seed", "7", "--seconds", "5", "--trace", "1"],
        rehearse=True)
    capsys.readouterr()
    assert res["correct"] is True and res["metrics"] == {}
    assert {"terms_self_s", "host_path_rows", "prologue_self_s",
            "fleet_host_self_s", "compiles_in_window",
            "spans_dropped"} <= set(res["per_layer_read"])


def test_the_configuration_is_the_deployment_it_says():
    _, entry, cfg, traffic = bench_run.load_cell(CELL, False)
    assert entry["chips"] == 1 and cfg["reduced"] == []
    pls = bench_failover.placements(cfg)
    assert len(pls) == 22
    assert sum(len(pl["terms"]) for pl in pls) == 47
    assert max(len(pl["terms"]) for pl in pls) == 3 < T_CAP
    assert cfg["row_state"] == {**cfg["row_state"], "t_cap": T_CAP,
                                "k_evict": K_EVICT}
    assert cfg["bindings_mix"]["prev_sites_max"] <= K_EVICT
    kind = bench_failover.kinds(cfg, 5, pls)
    group = np.asarray([pl["group"] for pl in pls])[kind]
    assert np.bincount(group).tolist() == [
        35000, 20000, 15000, 10000, 10000, 10000]
    # home regions dealt evenly
    homes = np.asarray([pl["home"] for pl in pls])[kind]
    assert np.bincount(homes[homes >= 0]).tolist() == [16000] * 5
    assert bench_failover.steps(traffic) == "hhLddrhhLddr"
    lost = bench_failover.lost_at(traffic, cfg, 5)
    assert lost[2] == lost[3] == lost[4] != lost[8] == lost[9] == lost[10]
    assert [lost[g] for g in (0, 1, 5, 6, 7, 11)] == [-1] * 6
    # every ring step has a hot home zone that is dry
    hot = cfg["fleet"]["hot_zones"]
    for g in range(12):
        dry = [r for r in range(5)
               if bench_failover.home_zone(cfg, r) == hot["zone"]
               and bench_failover.hot_free_units(cfg, r, g) == 0]
        assert len(dry) == 1, g


def test_the_reference_takes_the_groups_in_order():
    members = {
        "labels": [{"region": "a"}, {"region": "a"}, {"region": "b"},
                   {"region": "b"}],
        "api_enabled": np.ones(4, bool), "api_complete": np.ones(4, bool)}
    pls = [{"strategy": "dynamic", "tolerates": [],
            "terms": [("primary", {"region": "a"}),
                      ("backup", {"region": "b"})]},
           {"strategy": "duplicated", "tolerates": ["not-ready"],
            "terms": [("", None)]}]
    cap = np.asarray([[3, 99], [2, 99], [50, 99], [50, 99]], np.int64)
    requests = np.asarray([[1, 1]], np.int64)
    zero = np.zeros((1, 4), np.int64)

    def place(kind, replicas, prev=zero, evict=None, tainted=None, **how):
        out, group, errors, group0 = bench_reference.place(
            pls, np.asarray([kind]), np.asarray([replicas]), requests,
            np.zeros(1, np.int64), prev,
            np.zeros((1, 4), bool) if evict is None else evict,
            np.zeros(1, bool), cap, members,
            np.zeros(4, bool) if tainted is None else tainted,
            ("not-ready",), **how)
        return out[0].tolist(), int(group[0]), errors[0], bool(group0[0])

    # the primary holds 5: dispensed by availability 3:2
    assert place(0, 5) == ([3, 2, 0, 0], 0, "", True)
    # 6 do not fit the primary, though it has candidates: the backup
    assert place(0, 6) == ([0, 0, 3, 3], 1, "", True)
    # nor the backup: the LAST group's failure
    assert place(0, 101)[1:] == (1, bench_reference.NOT_ENOUGH, True)
    # a task on member 0: the primary is member 1 alone
    task = np.asarray([[True, False, False, False]])
    assert place(0, 2, evict=task) == ([0, 2, 0, 0], 0, "", True)
    assert place(0, 3, evict=task) == ([0, 0, 2, 1], 1, "", True)
    assert place(0, 3, evict=task, tasks=False) == ([2, 1, 0, 0], 0, "", True)
    # the primary tainted: no candidate there, unless the binding holds one
    lost = np.asarray([True, True, False, False])
    assert place(0, 2, tainted=lost) == ([0, 0, 1, 1], 1, "", False)
    held = np.asarray([[0, 1, 0, 0]], np.int64)
    assert place(0, 2, prev=held, tainted=lost) == ([0, 2, 0, 0], 0, "", True)
    assert place(0, 2, tainted=lost, first_group_only=True)[1:] == (
        0, bench_reference.NO_FIT, False)
    # a toleration keeps every member a candidate
    assert place(1, 2, tainted=lost) == ([2, 2, 2, 2], 0, "", True)


# -- (g) the readers -----------------------------------------------------------

WAVES = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]
PEAK = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def _span(name, start, dur, span_id=0, parent_id=None, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": start, "duration_s": dur, "attrs": attrs}


def _wave(t, sid, scale=1.0, host_rows=0):
    """One wave's solve span with the term kernel's share inside it."""
    d = 0.01 * scale
    return [
        _span("scheduler.solve", t, 10 * d, span_id=sid, rows=100000,
              slots=47, slots_minted=0, host_rows=host_rows),
        _span("scheduler.terms", t + d, d, span_id=sid + 1, parent_id=sid,
              rows=80000, fallback=3000, unfit=0, evicted_rows=17000),
        _span("kernel.host", t + 2 * d, d, span_id=sid + 2, parent_id=sid,
              phase="prep"),
    ]


def _ctx(spans, op_s=None, waves=4):
    _, _, cfg, _ = bench_run.load_cell(CELL, False)
    return {"spans": spans, "waves": WAVES, "rest_wall": 3.0, "cfg": cfg,
            "peak": PEAK, "trace": {"op_s": op_s or {}, "waves": waves}}


def test_the_span_readers_take_the_median_wave():
    ctx = _ctx(_wave(10.1, 10) + _wave(11.1, 20, 2.0, host_rows=24)
               + _wave(12.1, 30, 3.0, host_rows=48))
    assert terms_self_s.read(ctx) == pytest.approx(0.02)
    assert host_path_rows.read(ctx) == 24
    ctx = _ctx(_wave(10.1, 10) + _wave(11.1, 20) + _wave(12.1, 30))
    assert host_path_rows.read(ctx) == 0


def test_the_device_readers_read_the_term_kernel():
    ctx = _ctx([], {"jit__fleet_terms": 0.008, "jit__fleet_pass": 0.3})
    assert terms_device_s.read(ctx) == pytest.approx(0.002)
    count = fleet_terms_count(
        b=80000, t=3, c=100, k_prev=8, k_evict=8, u=47, p=8)
    assert count["bytes"] == (
        80000 * (12 + 4 + 4 + 64 + 32) + 47 * 13 + 8 * 100 * 4 + 80000 * 5)
    assert count["int_ops"] == 80000 * 100 * (18 + 8 + 8)
    least, bound = least_seconds(count, PEAK)
    assert bound == "bytes" and 1.1e-5 < least < 1.3e-5
    share = terms_roofline.read(ctx)
    assert share == pytest.approx(100 * least / 0.002) and 0 < share < 100
    assert any("terms_roofline bound=bytes" in n for n in ctx["notes"])


def test_the_readers_read_nothing_on_a_program_without_the_spans():
    parent = [_span("scheduler.solve", 10.6, 0.1, rows=100000, slots=6,
                    slots_minted=0)]
    ctx = _ctx(parent, {"jit__fleet_pass": 0.3})
    for reader in (terms_self_s, host_path_rows, terms_device_s,
                   terms_roofline):
        assert reader.read(ctx) is None, reader.__name__
