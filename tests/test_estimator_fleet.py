"""Estimator-fed availability on the fleet path (ISSUE 27).

With a registry's batch estimator as ``extra_estimators`` a fleet-eligible
batch is solved by ``_fleet_pass`` from the resident profile table, into
which every estimator's by-profile answer is min-merged; the in-process
members answer from ONE device-resident node table in one dispatch. Held
here, at small size, seeded:

(a) ``node_sum_table`` == ``refimpl.estimator_np`` == the per-member
    kernel, bit for bit;
(b) ``schedule()`` on the fleet == the host path == the refimpl divider
    fed the merged table;
(c) a moved member's generation changes exactly its column, uploads
    exactly its slice, and the next pass's placements follow; a pass with
    no movement re-folds nothing;
(d) a degraded pass (a registered member answering -1 transiently) is
    never trusted, replayed or kept;
(e) the batch really stayed on the fleet (the fleet's own counters).
"""

import numpy as np
import pytest

from karmada_tpu.estimator.accurate import (
    AccurateEstimator,
    EstimatorRegistry,
    NodeCache,
    NodeSnapshot,
    NodeState,
    NodeTable,
    _node_sum_estimate,
    _node_sum_estimate_np,
    node_sum_table,
)
from karmada_tpu.estimator.grpc_transport import RemoteAccurateEstimator
from karmada_tpu.estimator.service import (
    EstimatorConnection,
    EstimatorService,
    MultiClusterEstimatorService,
)
from karmada_tpu.ops.divide import DYNAMIC_WEIGHT
from karmada_tpu.refimpl import estimator_np as ref
from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot, TensorScheduler
from karmada_tpu.utils.builders import dynamic_weight_placement, new_cluster
from karmada_tpu.utils.metrics import (
    degraded_passes,
    estimator_nodes_estimated,
    estimator_upload_bytes,
)
from karmada_tpu.utils.tracing import tracer

DIMS = ["cpu", "memory", "pods", "ephemeral-storage"]
SEEDS = [3, 11, 2147483777, 40961]
C, N_MAX, P, B = 12, 40, 6, 600
NO_ESTIMATOR, NO_SUMMARY = 3, 7
NODE = np.asarray([8000, 32 << 30, 110, 0], np.int64)


class Federation:
    """12 members of 20-40 nodes (ragged), skewed load a node; member 3 has
    no estimator, member 7 reports no summary; 6 request profiles; 600
    bindings of 0-39 replicas, most holding a previous result."""

    def __init__(self, seed: int):
        rng = np.random.default_rng(seed)
        self.names = [f"m{i:02d}" for i in range(C)]
        self.nodes = []
        for _ in range(C):
            n = int(rng.integers(N_MAX // 2, N_MAX + 1))
            pods = rng.zipf(1.6, n).clip(max=60)
            used = np.stack(
                [pods * int(rng.integers(100, 700)),
                 pods * int(rng.integers(200, 900)) * (1 << 20),
                 pods, np.zeros(n, np.int64)], axis=1)
            self.nodes.append(NODE[None, :] - used)
        self.nodes[0][0, 0] = -500  # an overcommitted node reads as 0
        self.clusters = [new_cluster(name) for name in self.names]
        for i, cl in enumerate(self.clusters):
            self.summarise(i)
        self.clusters[NO_SUMMARY].status.resource_summary.allocatable = {}
        self.profiles = np.zeros((P, len(DIMS)), np.int64)
        self.profiles[:, 0] = 250 * np.arange(1, P + 1)
        self.profiles[:, 1] = (512 << 20) * np.arange(1, P + 1)
        self.replicas = rng.integers(0, 40, B)
        self.prof_idx = rng.integers(0, P, B)
        self.fresh = rng.random(B) < 0.05
        self.prev = np.zeros((B, C), np.int64)
        for i in np.flatnonzero(rng.random(B) < 0.7):
            sites = rng.integers(0, C, int(rng.integers(1, 5)))
            self.prev[i, sites] = rng.integers(1, 10, len(sites))
        placement = dynamic_weight_placement()
        self.problems = [
            BindingProblem(
                key=f"b{i}", placement=placement,
                replicas=int(self.replicas[i]),
                requests={"cpu": int(self.profiles[self.prof_idx[i], 0]),
                          "memory": int(self.profiles[self.prof_idx[i], 1])},
                gvk="apps/v1/Deployment",
                prev={self.names[j]: int(self.prev[i, j])
                      for j in np.flatnonzero(self.prev[i])},
                fresh=bool(self.fresh[i]),
            )
            for i in range(B)
        ]

    def summarise(self, i: int) -> None:
        """The member's ResourceSummary is the sum over its nodes."""
        rs = self.clusters[i].status.resource_summary
        n = len(self.nodes[i])
        rs.allocatable = dict(zip(DIMS, (NODE * n).tolist()))
        rs.allocated = dict(
            zip(DIMS, (NODE * n - np.maximum(self.nodes[i], 0).sum(0)).tolist()))

    def registry(self) -> EstimatorRegistry:
        reg = EstimatorRegistry()
        for i, name in enumerate(self.names):
            if i != NO_ESTIMATOR:
                reg.register(AccurateEstimator(
                    name, NodeSnapshot.from_arrays(self.nodes[i].copy(), DIMS)))
        return reg

    def engine(self, reg, host: bool = False) -> TensorScheduler:
        eng = TensorScheduler(
            ClusterSnapshot(self.clusters),
            extra_estimators=[reg.make_batch_estimator(self.names)])
        if host:
            eng.fleet_threshold = 10**9  # the parent's path for this batch
        return eng

    def reference(self) -> list:
        """refimpl: estimator table, general table, merge, numpy divider."""
        free = np.asarray(
            [np.maximum(a, 0).sum(0) for a in self.nodes], np.int64)
        has_summary = np.arange(C) != NO_SUMMARY
        reqs = self.profiles.copy()
        reqs[:, 2] = 1  # each replica occupies a pod
        members = [None if i == NO_ESTIMATOR else a
                   for i, a in enumerate(self.nodes)]
        merged = ref.merge_tables(
            ref.general_table(free, reqs, has_summary),
            ref.estimator_table(members, reqs))
        out, uns = ref.place(
            np.full(B, DYNAMIC_WEIGHT), self.replicas, self.prof_idx,
            np.ones((B, C), bool), np.zeros((B, C)), self.prev, self.fresh,
            merged)
        return [
            (not uns[i], {self.names[j]: int(out[i, j])
                          for j in np.flatnonzero(out[i])} if not uns[i] else {})
            for i in range(B)
        ]


def answers(results) -> list:
    return [(r.success, dict(r.clusters)) for r in results]


def estimator_spans() -> dict:
    return {s["name"]: s["attrs"] for s in tracer.dump()
            if s["name"].startswith("estimator.")}


# -- (a) the kernel -------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_node_sum_table_equals_refimpl_and_the_per_member_kernel(seed):
    fed = Federation(seed)
    reqs = fed.profiles.copy()
    reqs[:, 2] = 1
    reqs = np.concatenate([reqs, np.zeros((2, len(DIMS)), np.int64)])  # pad rows
    table = NodeTable()
    members = [(name, AccurateEstimator(name, NodeSnapshot.from_arrays(a, DIMS)))
               for name, a in zip(fed.names, fed.nodes)]
    assert table.sync(members)["members"] == C
    got = np.asarray(table.estimate(reqs))
    assert got.dtype == np.int32 and got.shape == (len(reqs), C)
    want = ref.estimator_table(fed.nodes, reqs)
    assert (got == want).all()
    for c, (_name, est) in enumerate(members):
        n = len(fed.nodes[c])
        ok = np.ones((len(reqs), n), bool)
        assert (_node_sum_estimate_np(fed.nodes[c], ok, reqs) == got[:, c]).all()
        assert (np.asarray(_node_sum_estimate(fed.nodes[c], ok, reqs))
                == got[:, c]).all()
        assert (est.max_available_replicas(None, reqs) == got[:, c]).all()
    assert table.bound(reqs) >= int(got.max())


def test_node_sum_table_clamps_to_int32_and_masks_pad_nodes():
    huge = np.full((3, 2, 1), 2**40, np.int64)
    out = np.asarray(node_sum_table(
        huge, np.asarray([2, 1, 0], np.int32), np.asarray([[1]], np.int64)))
    assert out.tolist() == [[2**31 - 1, 2**31 - 1, 0]]


def test_from_arrays_adopts_the_array_and_stamps_a_fresh_generation():
    free = np.asarray([[4000, 1 << 30, 10], [2000, 1 << 30, 0]], np.int64)
    a = NodeSnapshot.from_arrays(free, ["cpu", "memory", "pods"])
    b = NodeSnapshot.from_arrays(free, ["cpu", "memory", "pods"])
    assert a.available is free and b.generation > a.generation
    assert len(a.nodes) == 2
    packed = NodeSnapshot(
        [NodeState("n0", {"cpu": 4000, "memory": 1 << 30, "pods": 10}),
         NodeState("n1", {"cpu": 2000, "memory": 1 << 30, "pods": 5}, num_pods=7)],
        ["cpu", "memory", "pods"])
    assert (packed.available == free).all()
    with pytest.raises(ValueError):
        NodeSnapshot.from_arrays(free, ["cpu", "memory"])


# -- (b) + (e) placements, and where they were made ------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_fleet_with_estimators_equals_host_path_and_refimpl(seed):
    fed = Federation(seed)
    eng = fed.engine(fed.registry())
    got = answers(eng.schedule(fed.problems))
    # (e) the whole batch rode the fleet: one solve, every row packed there
    assert eng._fleet is not None and eng.solve_batches == 1
    assert eng._fleet.n_rows == B
    assert eng._fleet.last_breakdown["rows_packed"] == B
    host = fed.engine(fed.registry(), host=True)
    want_host = answers(host.schedule(fed.problems))
    assert host._fleet is None
    assert got == want_host
    assert got == fed.reference()
    # the estimator decides: without it other placements come out
    plain = answers(TensorScheduler(ClusterSnapshot(fed.clusters)).schedule(
        fed.problems))
    assert sum(a != b for a, b in zip(got, plain)) > B // 10


def test_a_bare_callable_keeps_the_host_path():
    fed = Federation(SEEDS[0])
    eng = TensorScheduler(
        ClusterSnapshot(fed.clusters),
        extra_estimators=[lambda reqs, reps: np.full((len(reqs), C), -1, np.int32)])
    eng.schedule(fed.problems)
    assert eng._fleet is None


def test_spread_rows_keep_the_host_path_with_estimators_on():
    from karmada_tpu.api.policy import SpreadConstraint

    fed = Federation(SEEDS[0])
    pl = dynamic_weight_placement()
    pl.spread_constraints = [
        SpreadConstraint(spread_by_field="cluster", min_groups=2, max_groups=4)]
    spread = [
        BindingProblem(key=f"s{i}", placement=pl, replicas=6,
                       requests={"cpu": 1000}, gvk="apps/v1/Deployment")
        for i in range(5)
    ]
    eng = fed.engine(fed.registry())
    host = fed.engine(fed.registry(), host=True)
    got = answers(eng.schedule(fed.problems + spread))
    assert eng._fleet is not None and eng._fleet.n_rows == B
    assert got == answers(host.schedule(fed.problems + spread))


# -- (c) freshness -----------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_a_moved_member_refolds_its_column_and_nothing_else(seed):
    fed = Federation(seed)
    reg = fed.registry()
    eng = fed.engine(reg)
    first = answers(eng.schedule(fed.problems))
    table0 = np.asarray(eng._fleet._dev_tables[3]).copy()

    # no movement, with and without an invalidate: nothing asked, nothing
    # uploaded, nothing folded, same answers
    for invalidate in (False, True):
        if invalidate:
            reg.invalidate()
        tracer.clear()
        up0 = estimator_upload_bytes.value()
        assert answers(eng.schedule(fed.problems)) == first
        assert estimator_spans() == {}
        assert estimator_upload_bytes.value() == up0

    # member 5 loses half of every node's free resources
    moved = 5
    fed.nodes[moved] = fed.nodes[moved] // 2
    fed.summarise(moved)
    reg.get(fed.names[moved]).snapshot = NodeSnapshot.from_arrays(
        fed.nodes[moved].copy(), DIMS)
    assert eng.update_snapshot(ClusterSnapshot(fed.clusters))
    reg.invalidate()
    tracer.clear()
    up0, n0 = estimator_upload_bytes.value(), estimator_nodes_estimated.value()
    second = answers(eng.schedule(fed.problems))
    spans = estimator_spans()
    assert set(spans) == {"estimator.refresh", "estimator.sync",
                          "estimator.dispatch", "estimator.fold"}
    assert spans["estimator.sync"]["members"] == 1
    assert spans["estimator.sync"]["nodes"] == len(fed.nodes[moved])
    assert spans["estimator.fold"]["clusters"] == C
    uploaded = estimator_upload_bytes.value() - up0
    assert uploaded == spans["estimator.sync"]["upload_mb"] * 1e6
    assert uploaded > 0
    assert uploaded < sum(a.nbytes for a in fed.nodes) / 4
    assert estimator_nodes_estimated.value() - n0 == sum(
        len(a) for i, a in enumerate(fed.nodes) if i != NO_ESTIMATOR)
    table1 = np.asarray(eng._fleet._dev_tables[3])
    changed = np.flatnonzero((table0 != table1).any(axis=0))
    assert changed.tolist() == [moved]
    assert second != first and second == fed.reference()
    assert eng._fleet.last_breakdown["rows_packed"] == 0  # no row re-packed


def test_a_node_cache_event_moves_its_member_too():
    fed = Federation(SEEDS[1])
    reg = fed.registry()
    cache = NodeCache(DIMS, [
        NodeState(f"n{i}", dict(zip(DIMS, NODE.tolist()))) for i in range(20)])
    reg.register(AccurateEstimator(fed.names[NO_ESTIMATOR], cache))
    eng = fed.engine(reg)
    eng.schedule(fed.problems)
    col0 = np.asarray(eng._fleet._dev_tables[3])[:, NO_ESTIMATOR].copy()
    for i in range(20):
        cache.add_pod(f"n{i}", {"cpu": 6000, "memory": 1 << 30})
    reg.invalidate()
    eng.schedule(fed.problems)
    col1 = np.asarray(eng._fleet._dev_tables[3])[:, NO_ESTIMATOR]
    assert (col1 <= col0).all() and (col1 < col0).any()


# -- (d) a degraded pass ------------------------------------------------------


class FlakyConn:
    """In-proc estimator connection that can be made unreachable."""

    supports_batch = None

    def __init__(self, service):
        self._inner = EstimatorConnection("multi", service)
        self.down = False

    def call(self, method, request):
        if self.down:
            raise ConnectionError("server unreachable")
        return self._inner.call(method, request)


def test_a_degraded_pass_is_not_trusted_replayed_or_kept():
    fed = Federation(SEEDS[0])
    reg = fed.registry()
    remote = 9  # this member's estimator runs behind a connection
    name = fed.names[remote]
    conn = FlakyConn(MultiClusterEstimatorService({
        name: EstimatorService(AccurateEstimator(
            name, NodeSnapshot.from_arrays(fed.nodes[remote].copy(), DIMS)))}))
    reg.register(RemoteAccurateEstimator(name, conn, lambda: list(DIMS)))
    eng = fed.engine(reg)
    sound = answers(eng.schedule(fed.problems))
    assert sound == fed.reference()  # the remote column took the same fold
    assert eng._fleet._folded_tokens == eng._est_tokens() != (None,)

    # the server drops: the invalidated pass cannot confirm it -> -1, the
    # pass is degraded, and its answers are the ones without that column
    reg.invalidate()
    conn.down = True
    before = degraded_passes.value(channel="estimator")
    degraded = answers(eng.schedule(fed.problems))
    assert degraded_passes.value(channel="estimator") > before
    assert degraded != sound
    assert eng._fleet._folded_tokens == (None,)

    # the server is back before anything invalidates again: a fast path
    # that trusted the resident table would serve the degraded answers
    conn.down = False
    tracer.clear()
    assert answers(eng.schedule(fed.problems)) == sound
    assert "estimator.fold" in estimator_spans()
    # and once sound, the table is trusted again: nothing re-folds
    tracer.clear()
    assert answers(eng.schedule(fed.problems)) == sound
    assert estimator_spans() == {}


def test_a_delta_pass_does_not_replay_rows_over_moved_answers():
    """The dirty-row path replays untouched rows from the host mirrors: with
    an estimator's answers moved it has to hand back to the full pass."""
    fed = Federation(SEEDS[2])
    reg = fed.registry()
    eng = fed.engine(reg)
    eng.schedule(fed.problems)
    eng.schedule(fed.problems)  # armed: identity fast path from here on
    moved = 2
    fed.nodes[moved] = fed.nodes[moved] // 3
    reg.get(fed.names[moved]).snapshot = NodeSnapshot.from_arrays(
        fed.nodes[moved].copy(), DIMS)
    reg.invalidate()
    # one problem object swapped: a one-row delta for the identity diff
    problems = list(fed.problems)
    p = problems[0]
    problems[0] = BindingProblem(
        key=p.key, placement=p.placement, replicas=p.replicas,
        requests=p.requests, gvk=p.gvk, prev=p.prev, fresh=p.fresh)
    got = answers(eng.schedule(problems))
    # the summaries did not move (no update_snapshot): only the estimator did
    host = fed.engine(fed.registry(), host=True)
    assert got == answers(host.schedule(problems))


# -- the plane -------------------------------------------------------------


def test_enable_accurate_estimators_builds_one_batch_estimator(monkeypatch):
    from karmada_tpu import cli

    cp = cli.cmd_local_up(3)
    calls = []
    real = cp.estimators.make_batch_estimator

    def counted(names, **kw):
        calls.append(list(names))
        return real(names, **kw)

    monkeypatch.setattr(cp.estimators, "make_batch_estimator", counted)
    cp.enable_accurate_estimators()
    assert len(calls) == 1 and calls[0] == sorted(cp.members.names())
    [est] = cp.scheduler.extra_estimators
    assert callable(est.profile_table) and callable(est.refresh_token)


def test_the_plane_with_estimators_on_keeps_its_bindings_on_the_fleet():
    from karmada_tpu import cli
    from karmada_tpu.api.core import ObjectMeta
    from karmada_tpu.api.policy import (
        PropagationPolicy,
        PropagationSpec,
        ResourceSelector,
    )
    from karmada_tpu.utils.builders import new_deployment

    cp = cli.cmd_local_up(3)
    cp.store.apply(PropagationPolicy(
        meta=ObjectMeta(name="p", namespace="default"),
        spec=PropagationSpec(
            resource_selectors=[
                ResourceSelector(api_version="apps/v1", kind="Deployment")],
            placement=dynamic_weight_placement())))

    def wave(lo, hi):
        for i in range(lo, hi):
            cp.store.apply(new_deployment(
                f"d{i}", namespace="default", replicas=3, cpu="250m",
                memory="512Mi"))
        cp.settle()

    wave(0, 300)
    engine = cp.scheduler._engine
    assert engine._fleet is not None and engine.extra_estimators == []
    # toggled on a LIVE engine: the next wave folds the estimators' answers
    cp.enable_accurate_estimators()
    wave(300, 600)
    assert cp.scheduler._engine is engine
    assert engine.extra_estimators == cp.scheduler.extra_estimators != []
    assert engine._fleet._folded_ests == tuple(engine.extra_estimators)
    assert None not in engine._fleet._folded_tokens
    # toggled off: the table goes back to the general estimate alone
    cp.disable_accurate_estimators()
    wave(600, 900)
    assert engine.extra_estimators == [] and engine._fleet._folded_ests == ()
    assert (np.asarray(engine._fleet._dev_tables[3])
            == np.asarray(engine._fleet._prof_base[0])).all()
