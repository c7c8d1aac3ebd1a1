"""ISSUE 26: a Cluster event enqueues the bindings it can move, not every
binding of the plane.

``SchedulerController._cluster_movable`` holds the (kind, key)s a Cluster
event re-enqueues. The invariant: it is a superset of the keys for which
``_needs_scheduling`` or the quota gate would say yes. A settled Divided
binding leaves it at the gate and comes back with its next binding event.

- one case a guarantee (a settled plane queues nothing; freed capacity
  re-places an unschedulable binding; a joined cluster reaches a Duplicated
  binding; a quota denial stays parked; a delete leaves the set; an edit
  comes back; neither the scheduler's Cluster handler nor namespace-sync's
  lists the store; a Namespace reaches a joined cluster), each through
  ``ControlPlane`` and ``settle()``;
- a seeded random sequence of events leaves the same store, object for
  object, as the handler that enqueued everything.
"""

from __future__ import annotations

import copy
import itertools
import random
import uuid

import pytest

from karmada_tpu.api import PropagationPolicy, PropagationSpec, ResourceSelector
from karmada_tpu.api.cluster import Taint
from karmada_tpu.api.core import ObjectMeta, Resource
from karmada_tpu.api.work import SCHEDULED
from karmada_tpu.controllers import (
    ObjectReferenceSelector,
    WorkloadRebalancer,
    WorkloadRebalancerSpec,
)
from karmada_tpu.api.policy import (
    FederatedResourceQuota,
    FederatedResourceQuotaSpec,
    LabelSelector,
)
from karmada_tpu.controllers.scheduler_controller import SchedulerController
from karmada_tpu.controlplane import ControlPlane
from karmada_tpu.estimator.accurate import NodeState
from karmada_tpu.utils import metrics
from karmada_tpu.utils.builders import (
    duplicated_placement,
    dynamic_weight_placement,
    new_cluster,
    new_deployment,
)
from karmada_tpu.utils.member import MemberCluster
from karmada_tpu.utils.quantity import parse_resource_list
from karmada_tpu.utils.store import Event, obj_key
from karmada_tpu.utils.tracing import tracer

NS = "default"
KIND = "ResourceBinding"


def _nodes(cpu_used: int, cpu: int = 8) -> list:
    """One node of ``cpu`` cores with ``cpu_used`` of them requested."""
    return [NodeState(
        name="pool",
        allocatable=parse_resource_list(
            {"cpu": cpu, "memory": "64Gi", "pods": 1000}),
        requested=parse_resource_list(
            {"cpu": cpu_used, "memory": "1Gi", "pods": 1}),
    )]


class Plane:
    """A ControlPlane over members whose load the test moves: a status
    report is ``report(name, cpu_used)`` and the next ``settle()``."""

    def __init__(self, n_members: int = 3, cpu_used: int = 0):
        self.clock = [1000.0]
        self.cp = ControlPlane(clock=lambda: self.clock[0])
        self.members: dict = {}
        for i in range(n_members):
            self.join(f"m{i}", cpu_used)
        self.cp.settle()

    def join(self, name: str, cpu_used: int = 0, **cluster_kw) -> None:
        member = self.members[name] = MemberCluster(name)
        member.nodes = _nodes(cpu_used)
        self.cp.join_cluster(
            new_cluster(name, cpu="8", memory="64Gi", **cluster_kw), member)

    def report(self, name: str, cpu_used: int) -> None:
        self.members[name].nodes = _nodes(cpu_used)

    def policy(self, placement, name: str = "pol", **labels) -> None:
        self.cp.store.apply(PropagationPolicy(
            meta=ObjectMeta(name=name, namespace=NS),
            spec=PropagationSpec(
                resource_selectors=[ResourceSelector(
                    api_version="apps/v1", kind="Deployment",
                    label_selector=(
                        LabelSelector(match_labels=dict(labels))
                        if labels else None),
                )],
                placement=placement,
            ),
        ))

    def settle(self) -> None:
        self.clock[0] += 10
        self.cp.settle()
        check_invariant(self.cp)

    def binding(self, app: str):
        return self.cp.store.get(KIND, f"{NS}/{app}-deployment")

    def scheduled(self, app: str) -> bool:
        return next(c for c in self.binding(app).status.conditions
                    if c.type == SCHEDULED).status

    def placed(self, app: str) -> dict:
        return {tc.name: tc.replicas for tc in self.binding(app).spec.clusters}

    def movable(self) -> set:
        return {key for _, key in self.cp.scheduler._cluster_movable}


def check_invariant(cp) -> None:
    """Every binding the gates would pass is in the set, and the set names
    no binding the store no longer holds."""
    sched = cp.scheduler
    for kind in (KIND, "ClusterResourceBinding"):
        for rb in cp.store.list(kind):
            if rb.spec.scheduler_name != sched.scheduler_name:
                continue
            kind_key = (kind, rb.meta.namespaced_name)
            if (sched._needs_scheduling(rb)[0]
                    or kind_key in sched._quota_denied):
                assert kind_key in sched._cluster_movable, kind_key
    for kind, key in sched._cluster_movable:
        assert cp.store.get(kind, key) is not None, key


def fanout() -> float:
    return metrics.cluster_fanout_keys.value()


def scheduler_drains() -> list:
    return [s for s in tracer.dump() if s["name"] == "controller.scheduler"]


# --------------------------------------------------------------------------
# the cases
# --------------------------------------------------------------------------


def case_settled_plane_queues_nothing(monkeypatch):
    p = Plane()
    p.policy(dynamic_weight_placement())
    for i in range(6):
        p.cp.store.apply(new_deployment(f"app{i}", replicas=3, cpu="250m"))
    p.settle()
    p.settle()
    assert all(p.scheduled(f"app{i}") for i in range(6))
    assert p.movable() == set()
    # the event itself: nothing queued, the counter unmoved
    before = fanout()
    cluster = p.cp.store.get("Cluster", "m1")
    cluster.status.resource_summary.allocated["cpu"] = 1000
    p.cp.store.apply(cluster)
    assert len(p.cp.scheduler.worker) == 0
    assert fanout() == before
    # the handler's other duties still ran: the next pass takes a fresh
    # snapshot
    assert p.cp.scheduler._snapshot is None
    # and a member's report through the status collection: no scheduler
    # drain in the wave at all
    p.report("m2", 2)
    tracer.clear()
    p.settle()
    assert p.cp.store.get(
        "Cluster", "m2").status.resource_summary.allocated["cpu"] == 2000
    assert not scheduler_drains()
    assert fanout() == before


def case_freed_capacity_replaces_unschedulable(monkeypatch):
    p = Plane(n_members=2, cpu_used=8)  # every member full
    p.policy(dynamic_weight_placement())
    p.cp.store.apply(new_deployment("big", replicas=4, cpu="1"))
    p.settle()
    assert not p.scheduled("big") and p.placed("big") == {}
    assert p.movable() == {f"{NS}/big-deployment"}
    generation = p.binding("big").meta.generation
    # a Cluster event that frees nothing: retried, still parked, still in
    # the set
    before = fanout()
    p.report("m1", 7)
    p.settle()
    assert not p.scheduled("big")
    assert fanout() == before + 1
    assert p.movable() == {f"{NS}/big-deployment"}
    # the report that frees the capacity re-places it, binding untouched
    p.report("m0", 0)
    p.settle()
    assert p.scheduled("big")
    assert sum(p.placed("big").values()) == 4
    assert p.binding("big").meta.generation == generation
    p.report("m1", 6)
    p.settle()
    assert p.movable() == set()  # settled now: the gate let it go


def case_duplicated_reaches_joined_cluster(monkeypatch):
    p = Plane(n_members=2)
    p.policy(duplicated_placement())
    p.cp.store.apply(new_deployment("dup", replicas=2, cpu="250m"))
    p.settle()
    assert p.placed("dup") == {"m0": 2, "m1": 2}
    # the gate passes a Duplicated binding every time: it never leaves
    p.report("m0", 1)
    p.settle()
    assert p.movable() == {f"{NS}/dup-deployment"}
    p.join("m2")
    p.settle()
    assert p.placed("dup") == {"m0": 2, "m1": 2, "m2": 2}
    assert p.movable() == {f"{NS}/dup-deployment"}


def case_quota_denied_stays_parked(monkeypatch):
    p = Plane()
    p.policy(dynamic_weight_placement())
    p.cp.store.apply(FederatedResourceQuota(
        meta=ObjectMeta(name="q", namespace=NS),
        spec=FederatedResourceQuotaSpec(overall={"cpu": 2000}),
    ))
    p.cp.store.apply(new_deployment("big", replicas=6, cpu="1"))
    p.settle()
    cond = next(c for c in p.binding("big").status.conditions
                if c.type == SCHEDULED)
    assert not cond.status and cond.reason == "QuotaExceeded"
    kind_key = (KIND, f"{NS}/big-deployment")
    assert kind_key in p.cp.scheduler._quota_denied
    # Cluster events re-enqueue it (it is in the set) and the quota gate
    # parks it again: no engine pass
    solves = p.cp.scheduler._engine.solve_batches
    before = fanout()
    for used in (1, 2):
        p.report("m0", used)
        p.settle()
    assert fanout() == before + 2
    assert p.cp.scheduler._engine.solve_batches == solves
    assert not p.scheduled("big")
    assert kind_key in p.cp.scheduler._cluster_movable
    # the quota's next generation retries it
    q = p.cp.store.get("FederatedResourceQuota", f"{NS}/q")
    q.spec.overall = {"cpu": 20000}
    p.cp.store.apply(q)
    p.settle()
    assert p.scheduled("big")
    assert sum(p.placed("big").values()) == 6
    assert p.cp.scheduler._quota_denied == {}


def case_deleted_binding_leaves_the_set(monkeypatch):
    p = Plane(n_members=2)
    p.policy(duplicated_placement())
    p.cp.store.apply(new_deployment("dup", replicas=1, cpu="250m"))
    p.settle()
    assert p.movable() == {f"{NS}/dup-deployment"}
    p.cp.store.delete("Resource", f"{NS}/dup")  # its binding goes with it
    p.settle()
    assert p.binding("dup") is None
    assert p.movable() == set()
    before = fanout()
    p.report("m0", 1)
    p.settle()
    assert fanout() == before  # one event, an empty set
    assert len(p.cp.scheduler.worker) == 0


def case_edited_binding_comes_back(monkeypatch):
    p = Plane()
    p.policy(dynamic_weight_placement())
    p.cp.store.apply(new_deployment("app", replicas=3, cpu="250m"))
    p.cp.store.apply(new_deployment("other", replicas=3, cpu="250m"))
    p.settle()
    p.settle()
    assert p.movable() == set()
    # a scale: the binding's replicas change, it is scheduled again
    tmpl = p.cp.store.get("Resource", f"{NS}/app")
    tmpl.spec["replicas"] = 7
    p.cp.store.apply(tmpl)
    p.settle()
    assert sum(p.placed("app").values()) == 7
    # the next Cluster event brings it by once more, and the gate lets go
    p.report("m1", 1)
    p.settle()
    assert p.movable() == set()
    # a reschedule trigger: divided afresh over this wave's availability
    p.report("m0", 7)
    last = p.binding("app").status.last_scheduled_time
    p.cp.store.apply(WorkloadRebalancer(
        meta=ObjectMeta(name="again"),
        spec=WorkloadRebalancerSpec(workloads=[
            ObjectReferenceSelector(kind="Deployment", namespace=NS,
                                    name="app")]),
    ))
    tracer.clear()
    p.settle()
    rb = p.binding("app")
    assert rb.status.last_scheduled_time > last
    assert rb.spec.reschedule_triggered_at <= rb.status.last_scheduled_time
    assert sum(p.placed("app").values()) == 7
    # only the named binding went through the scheduler, never the other
    assert sum(d["attrs"]["keys"] for d in scheduler_drains()) <= 3
    p.settle()
    assert p.movable() == set()


def case_cluster_handlers_walk_no_store(monkeypatch):
    p = Plane()
    p.policy(dynamic_weight_placement())
    p.policy(duplicated_placement(), name="dup", mode="duplicated")
    for i in range(4):
        p.cp.store.apply(new_deployment(f"app{i}", replicas=3, cpu="250m"))
    p.cp.store.apply(new_deployment(
        "dup", replicas=1, cpu="250m", labels={"mode": "duplicated"}))
    p.cp.store.apply(Resource(
        api_version="v1", kind="Namespace", meta=ObjectMeta(name="team-a")))
    p.settle()
    p.report("m0", 1)
    p.settle()
    event = Event("Modified", "Cluster", "m1",
                  p.cp.store.get("Cluster", "m1"))

    def no_list(*a, **k):
        raise AssertionError("a Cluster handler listed the store")

    monkeypatch.setattr(p.cp.store, "list", no_list)
    before = fanout()
    p.cp.scheduler._on_cluster_event(event)
    p.cp.namespace_sync._on_cluster_event(event)
    monkeypatch.undo()
    # the one Duplicated binding and the one Namespace, nothing settled
    assert fanout() == before + 1
    assert len(p.cp.scheduler.worker) == 1
    assert len(p.cp.namespace_sync.worker) == 1


def case_namespace_reaches_joined_cluster(monkeypatch):
    p = Plane(n_members=2)
    ns = Resource(
        api_version="v1", kind="Namespace", meta=ObjectMeta(name="team-a"))
    p.cp.store.apply(ns)
    p.cp.store.apply(Resource(
        api_version="v1", kind="Namespace", meta=ObjectMeta(name="team-b")))
    p.settle()
    p.join("m2")
    p.settle()
    for name in ("team-a", "team-b"):
        assert p.members["m2"].get("v1/Namespace", "", name) is not None
    # a deleted Namespace is no longer walked
    p.cp.store.delete("Resource", obj_key(ns))
    p.settle()
    assert list(p.cp.namespace_sync._namespaces) == ["team-b"]
    p.join("m3")
    p.settle()
    assert p.members["m3"].get("v1/Namespace", "", "team-b") is not None
    assert p.cp.store.get("Work", "karmada-es-m3/ns-team-a") is None


CASES = [
    case_settled_plane_queues_nothing,
    case_freed_capacity_replaces_unschedulable,
    case_duplicated_reaches_joined_cluster,
    case_quota_denied_stays_parked,
    case_deleted_binding_leaves_the_set,
    case_edited_binding_comes_back,
    case_cluster_handlers_walk_no_store,
    case_namespace_reaches_joined_cluster,
]


@pytest.mark.parametrize(
    "case", CASES, ids=[c.__name__[len("case_"):] for c in CASES])
def test_cluster_movable_set(case, monkeypatch):
    case(monkeypatch)


# --------------------------------------------------------------------------
# equivalence with the handler that enqueued everything
# --------------------------------------------------------------------------


class FanOutAll(SchedulerController):
    """The handler before ISSUE 26: every Cluster event lists both binding
    kinds from the store and enqueues every binding of this scheduler."""

    def _on_cluster_event(self, event) -> None:
        self._cluster_movable.clear()
        super()._on_cluster_event(event)
        for kind in (KIND, "ClusterResourceBinding"):
            for rb in self.store.list(kind):
                if rb.spec.scheduler_name == self.scheduler_name:
                    self.worker.enqueue((kind, rb.meta.namespaced_name))


def _events(seed: int, n: int) -> list:
    """A seeded sequence of plane events, as (op, args) tuples."""
    rng = random.Random(seed)
    apps: list = []
    ids = itertools.count()
    out = [("create", f"app{next(ids)}", rng.randint(1, 6), rng.random() < .3)
           for _ in range(6)]
    apps += [e[1] for e in out]
    joined = 3
    for _ in range(n):
        op = rng.choice(["create", "scale", "delete", "rebalance", "report",
                         "report", "report", "taint", "join"])
        if op == "create" or (op in ("scale", "delete", "rebalance")
                              and not apps):
            name = f"app{next(ids)}"
            apps.append(name)
            out.append(("create", name, rng.randint(1, 9),
                        rng.random() < .3))
        elif op == "scale":
            out.append(("scale", rng.choice(apps), rng.randint(1, 16)))
        elif op == "delete":
            out.append(("delete", apps.pop(rng.randrange(len(apps)))))
        elif op == "rebalance":
            out.append(("rebalance",
                        rng.sample(apps, min(len(apps), rng.randint(1, 3)))))
        elif op == "report":
            out.append(("report", f"m{rng.randrange(joined)}",
                        rng.choice([0, 3, 6, 8, 8])))
        elif op == "taint":
            out.append(("taint", f"m{rng.randrange(joined)}",
                        rng.random() < .5))
        else:
            out.append(("join", f"m{joined}", rng.randint(0, 6)))
            joined += 1
    return out


def _count_ids(monkeypatch) -> None:
    """Permanent ids (policies, bindings, Works) and uids from counters that
    start over, so that two planes fed the same events name their objects
    alike."""
    ids = itertools.count(1)
    monkeypatch.setattr(uuid, "uuid4", lambda: uuid.UUID(int=next(ids)))
    monkeypatch.setattr("karmada_tpu.api.core._uid_counter",
                        itertools.count(1))


def _drive(p: Plane, events: list, check) -> None:
    p.policy(dynamic_weight_placement(), name="divided", mode="divided")
    p.policy(duplicated_placement(), name="duplicated", mode="duplicated")
    rebalancers = itertools.count()
    for step, ev in enumerate(events):
        op = ev[0]
        store = p.cp.store
        if op == "create":
            _, name, replicas, dup = ev
            store.apply(new_deployment(
                name, replicas=replicas, cpu="1",
                labels={"mode": "duplicated" if dup else "divided"}))
        elif op == "scale":
            tmpl = store.get("Resource", f"{NS}/{ev[1]}")
            tmpl.spec["replicas"] = ev[2]
            store.apply(tmpl)
        elif op == "delete":
            store.delete("Resource", f"{NS}/{ev[1]}")
        elif op == "rebalance":
            store.apply(WorkloadRebalancer(
                meta=ObjectMeta(name=f"r{next(rebalancers)}"),
                spec=WorkloadRebalancerSpec(workloads=[
                    ObjectReferenceSelector(kind="Deployment", namespace=NS,
                                            name=a) for a in ev[1]]),
            ))
        elif op == "report":
            p.report(ev[1], ev[2])
        elif op == "taint":
            cluster = store.get("Cluster", ev[1])
            cluster.spec.taints = (
                [Taint(key="maintenance", effect="NoSchedule")]
                if ev[2] else [])
            store.apply(cluster)
        elif op == "join":
            p.join(ev[1], ev[2])
        p.clock[0] += 10
        p.cp.settle()
        check(p.cp, step, ev)


def _store_image(cp) -> dict:
    """Every object the store holds, by (kind, key), with the wall-clock
    stamps that no fake clock reaches taken out."""
    out = {}
    for kind in sorted(cp.store.kinds()):
        for obj in cp.store.list(kind):
            o = copy.deepcopy(obj)
            o.meta.creation_timestamp = None
            for cond in getattr(getattr(o, "status", None),
                                "conditions", None) or []:
                cond.last_transition_time = 0.0
            out[(kind, o.meta.namespaced_name)] = o
    return out


@pytest.mark.parametrize("seed", [5, 12, 13])
def test_same_store_as_the_handler_that_enqueued_everything(seed, monkeypatch):
    events = _events(seed, 40)
    parked: set = set()      # bindings seen unschedulable
    replaced: set = set()    # of them, those a member's event re-placed

    def check(cp, step, ev):
        check_invariant(cp)
        for rb in cp.store.list(KIND):
            ok = next((c.status for c in rb.status.conditions
                       if c.type == SCHEDULED), None)
            if ok is False:
                parked.add(rb.meta.name)
            elif (ok and rb.meta.name in parked
                  and ev[0] in ("report", "join", "taint")):
                replaced.add(rb.meta.name)

    _count_ids(monkeypatch)
    indexed = Plane(cpu_used=6)
    _drive(indexed, events, check)
    # the sequence reaches what the set is for
    assert parked and replaced
    monkeypatch.setattr(
        "karmada_tpu.controlplane.SchedulerController", FanOutAll)
    _count_ids(monkeypatch)
    everything = Plane(cpu_used=6)
    assert type(everything.cp.scheduler) is FanOutAll
    _drive(everything, events, lambda cp, step, ev: None)
    got, want = _store_image(indexed.cp), _store_image(everything.cp)
    assert sorted(got) == sorted(want)
    assert any(k[0] == "Work" for k in got) and any(
        k[0] == KIND for k in got)
    for key in want:
        assert got[key] == want[key], key
