"""A Work's status write is not a spec event (ISSUE 30).

The store moves a Work's ``meta.generation`` on every write but a
status-only one; the execution controller and the Work index read it and do
spec work (apply, re-index) only when it moved; work-status renders a
manifest only to recreate. What the echo of those status writes used to set
off by accident (the retry of a failed apply, the re-apply over a drifted
member object) is asked for by name, and every case here that the parent
commit had behaves as it did there.
"""

import random

import pytest

from karmada_tpu.api import (
    PropagationPolicy,
    PropagationSpec,
    ResourceSelector,
)
from karmada_tpu.api.core import ObjectMeta
from karmada_tpu.api.work import (
    WORK_APPLIED,
    ManifestStatus,
    ResourceBinding,
    Work,
    WorkloadTemplate,
    WorkloadTemplateRef,
    WorkSpec,
)
from karmada_tpu.controllers import execution_namespace
from karmada_tpu.controllers.propagation import (
    WORK_BINDING_LABEL,
    WorkIndex,
)
from karmada_tpu.controlplane import ControlPlane
from karmada_tpu.utils import Store, metrics
from karmada_tpu.utils.builders import (
    duplicated_placement,
    dynamic_weight_placement,
    new_cluster,
    new_deployment,
)
from karmada_tpu.utils.codec import to_jsonable

GVK = "apps/v1/Deployment"


def policy(placement, **spec):
    return PropagationPolicy(
        meta=ObjectMeta(name="p", namespace="default"),
        spec=PropagationSpec(
            resource_selectors=[
                ResourceSelector(api_version="apps/v1", kind="Deployment")
            ],
            placement=placement,
            **spec,
        ),
    )


def make_plane(n_clusters=3):
    cp = ControlPlane()
    for i in range(1, n_clusters + 1):
        cp.join_cluster(new_cluster(f"member{i}", cpu="100", memory="200Gi"))
    cp.settle()
    return cp


def work_key(cluster, name):
    return f"{execution_namespace(cluster)}/default.{name}-deployment"


def applied(work):
    return next(
        (c.status, c.reason) for c in work.status.conditions
        if c.type == WORK_APPLIED
    )


class Readings:
    """The two counters and execution's apply reconciles by Work key,
    since this object was made."""

    def __init__(self, cp):
        self.applies: dict[str, int] = {}
        ec = cp.execution_controller
        inner = ec._reconcile

        def counting(item):
            if item[0] == "apply":
                self.applies[item[1]] = self.applies.get(item[1], 0) + 1
            return inner(item)

        # the batch drain looks the method up on the instance, the
        # one-key drain holds the worker's own reference
        ec._reconcile = ec.worker.reconcile = counting
        self._skipped0 = self._skipped()
        self._renders0 = self._renders()

    @staticmethod
    def _skipped():
        c = metrics.work_status_events_skipped
        return {k: c.value(consumer=k) for k in ("execution", "work-index")}

    @staticmethod
    def _renders():
        c = metrics.work_manifest_renders
        return {
            k: c.value(consumer=k)
            for k in ("execution", "work-status", "agent")
        }

    def skipped(self, consumer):
        return self._skipped()[consumer] - self._skipped0[consumer]

    def renders(self, consumer):
        return self._renders()[consumer] - self._renders0[consumer]


# -- the store: who moves a Work's generation ------------------------------


def _work(name="w", ns="karmada-es-m1", **spec):
    return Work(meta=ObjectMeta(name=name, namespace=ns), spec=WorkSpec(**spec))


class TestStoreGeneration:
    def test_create_keeps_the_writers_generation(self):
        store = Store()
        assert store.apply(_work()).meta.generation == 1

    @pytest.mark.parametrize("batched", [False, True])
    def test_status_only_write_of_the_stored_object_leaves_it(self, batched):
        store = Store()
        work = store.apply(_work())
        work.status.manifest_statuses.append(ManifestStatus())
        if batched:
            assert store.apply_many([work], status_only=True) == []
        else:
            store.apply(work, status_only=True)
        assert work.meta.generation == 1
        assert work.meta.resource_version == 2  # still a write

    @pytest.mark.parametrize("batched", [False, True])
    def test_any_other_write_moves_it(self, batched):
        store = Store()
        work = store.apply(_work())
        work.spec = WorkSpec(suspend_dispatching=True)
        if batched:
            store.apply_many([work])
        else:
            store.apply(work)
        assert work.meta.generation == 2

    @pytest.mark.parametrize("status_only", [False, True])
    def test_another_object_over_the_key_is_a_spec_write(self, status_only):
        """A writer that hands in an object the store does not hold cannot
        be taken at its word: its spec may be anything."""
        store = Store()
        store.apply(_work())
        store.apply(_work())  # generation 2
        fresh = _work(suspend_dispatching=True)
        assert fresh.meta.generation == 1
        store.apply(fresh, status_only=status_only)
        assert store.get("Work", "karmada-es-m1/w") is fresh
        assert fresh.meta.generation == 3

    def test_marking_for_deletion_moves_it(self):
        store = Store()
        work = _work()
        work.meta.finalizers.append("f")
        store.apply(work)
        store.delete("Work", "karmada-es-m1/w")
        assert work.meta.deletion_timestamp is not None
        assert work.meta.generation == 2

    def test_other_kinds_keep_their_writers_generation(self):
        """A binding's generation is its writers' (the scheduler's gate
        compares it with the one it observed): the store leaves it."""
        store = Store()
        rb = ResourceBinding(meta=ObjectMeta(name="rb", namespace="default"))
        store.apply(rb)
        store.apply(rb)
        store.apply(rb, status_only=True)
        assert rb.meta.generation == 1

    def test_delivered_events_carry_the_generation(self):
        store = Store()
        seen = []
        store.watch(
            "Work", lambda e: seen.append((e.type, e.obj.meta.generation))
        )
        work = store.apply(_work())
        store.apply(work, status_only=True)
        store.apply(work)
        assert seen == [("Added", 1), ("Modified", 1), ("Modified", 2)]


def test_tally_counts_beside_inc():
    c = metrics.Counter("t_total")
    tally = c.labels(consumer="a")
    assert c.labels(consumer="a") is tally
    tally.inc()
    tally.inc(2)
    c.inc(4, consumer="a")
    c.inc(1, consumer="b")
    assert c.value(consumer="a") == 7
    assert c.value(consumer="b") == 1
    assert 't_total{consumer="a"} 7.0' in list(c.render())


def test_both_counters_are_exported():
    text = metrics.registry.render()
    assert "# TYPE karmada_tpu_work_status_events_skipped_total counter" in text
    assert "# TYPE karmada_tpu_work_manifest_renders_total counter" in text


# -- (a) one apply reconcile a created Work --------------------------------


@pytest.mark.parametrize(
    "placement", [duplicated_placement, dynamic_weight_placement]
)
def test_each_created_work_is_reconciled_once(placement):
    cp = make_plane(3)
    cp.store.apply(policy(placement()))
    cp.settle()
    before = {w.meta.namespaced_name for w in cp.store.list("Work")}
    r = Readings(cp)
    for i in range(6):
        cp.store.apply(new_deployment(f"app{i}", replicas=3 + i))
    cp.settle()
    created = {
        w.meta.namespaced_name for w in cp.store.list("Work")
    } - before
    assert len(created) >= 6
    assert r.applies == {key: 1 for key in created}
    # two status writes a Work (execution's Applied condition, work-status's
    # manifest status), each turned away by both consumers
    assert r.skipped("execution") == 2 * len(created)
    assert r.skipped("work-index") == 2 * len(created)
    assert r.renders("execution") == len(created)
    assert r.renders("work-status") == 0
    for key in created:
        work = cp.store.get("Work", key)
        assert work.meta.generation == 1
        assert applied(work) == (True, "AppliedSuccessful")
        assert len(work.status.manifest_statuses) == 1


# -- (b) a spec change always arrives, in the same settle -------------------


def _replicas_patch(cp):
    dep = cp.store.get("Resource", "default/web")
    dep.spec["replicas"] = 7
    dep.meta.generation += 1
    cp.store.apply(dep)
    return lambda member: member.get(GVK, "default", "web").spec["replicas"] == 7


def _suspend_flip(cp):
    """Suspended, settled, and resumed: the resume is the spec change."""
    pol = cp.store.get("PropagationPolicy", "default/p")
    dep = cp.store.get("Resource", "default/web")
    pol.spec.suspend_dispatching = True
    cp.store.apply(pol)
    dep.spec["replicas"] = 7
    dep.meta.generation += 1
    cp.store.apply(dep)
    cp.settle()
    for name in ("member1", "member2"):
        assert cp.members.get(name).get(GVK, "default", "web").spec[
            "replicas"
        ] == 2, "a suspended Work must not dispatch"
    pol.spec.suspend_dispatching = False
    cp.store.apply(pol)
    return lambda member: member.get(GVK, "default", "web").spec["replicas"] == 7


def _second_writer(cp):
    """Another writer applies a Work object of its own over each key."""
    for name in ("member1", "member2"):
        old = cp.store.get("Work", work_key(name, "web"))
        manifest = new_deployment("web", replicas=7)
        fresh = Work(
            meta=ObjectMeta(
                name=old.meta.name, namespace=old.meta.namespace,
                labels=dict(old.meta.labels),
            ),
            spec=WorkSpec(workload=[manifest]),
        )
        assert fresh.meta.generation == 1 == old.meta.generation
        cp.store.apply(fresh)
    return lambda member: member.get(GVK, "default", "web").spec["replicas"] == 7


@pytest.mark.parametrize(
    "change", [_replicas_patch, _suspend_flip, _second_writer]
)
def test_spec_change_reaches_execution_and_the_index(change):
    cp = make_plane(2)
    cp.store.apply(new_deployment("web", replicas=2))
    cp.store.apply(policy(duplicated_placement()))
    cp.settle()
    keys = [work_key(m, "web") for m in ("member1", "member2")]
    gens = [cp.store.get("Work", k).meta.generation for k in keys]
    arrived = change(cp)
    r = Readings(cp)
    cp.settle()
    for key, gen in zip(keys, gens):
        work = cp.store.get("Work", key)
        assert work.meta.generation > gen
        assert r.applies.get(key, 0) >= 1
        assert cp.execution_controller._acted[key] == work.meta.generation
        assert cp.work_index._work_meta[key][0] == work.meta.generation
        assert applied(work) == (True, "AppliedSuccessful")
    for name in ("member1", "member2"):
        assert arrived(cp.members.get(name))
        assert cp.work_index.work_for_target(
            name, GVK, "default", "web"
        ) is cp.store.get("Work", work_key(name, "web"))


def test_second_writer_retargets_the_index():
    """The index follows a fresh object whose targets are others."""
    cp = make_plane(1)
    cp.store.apply(new_deployment("web", replicas=2))
    cp.store.apply(policy(duplicated_placement()))
    cp.settle()
    old = cp.store.get("Work", work_key("member1", "web"))
    fresh = Work(
        meta=ObjectMeta(name=old.meta.name, namespace=old.meta.namespace),
        spec=WorkSpec(workload=[new_deployment("other", replicas=1)]),
    )
    cp.store.apply(fresh)
    cp.settle()
    idx = cp.work_index
    assert idx.work_for_target("member1", GVK, "default", "web") is None
    assert idx.work_for_target("member1", GVK, "default", "other") is fresh
    assert fresh not in idx.works_for(old.meta.labels[WORK_BINDING_LABEL])
    assert cp.members.get("member1").get(GVK, "default", "other") is not None


# -- (c) the index equals one rebuilt from the store ------------------------


def _rebuilt(store):
    """An index made from the store as it stands (the replayed Added
    events), taken off the watch again so that it counts nothing later."""
    index = WorkIndex(store)
    store._watchers["Work"].remove(index._on_event)
    return index


def _index_answers(index, refs, targets, digests):
    return (
        {r: [w.meta.namespaced_name for w in index.works_for(r)] for r in refs},
        {
            t: getattr(index.work_for_target(*t), "meta", None)
            and index.work_for_target(*t).meta.namespaced_name
            for t in targets
        },
        {d: index.digest_refcount(d) for d in digests},
    )


@pytest.mark.parametrize("seed", [1, 2, 3, 5, 8, 2_147_483_659])
def test_index_equals_a_rebuild_after_mixed_writes(seed):
    rng = random.Random(seed)
    store = Store()
    index = WorkIndex(store)
    clusters = ["m1", "m2", "m3"]
    names = [f"w{i}" for i in range(6)]
    refs = [f"ResourceBinding:default/rb{i}" for i in range(4)]
    digests = ["d1", "d2", "d3"]
    # a target on a member belongs to one Work (two claimants are settled
    # by event order, which a rebuild cannot know): each Work moves among
    # three names of its own
    apps = {n: [f"{n}-a", f"{n}-b", f"{n}-c"] for n in names}
    targets = [
        (c, GVK, "default", a) for c in clusters for n in names for a in apps[n]
    ]

    def spec(name):
        if rng.random() < 0.5:
            return WorkSpec(workload_template=WorkloadTemplateRef(
                digest=rng.choice(digests), api_version="apps/v1",
                kind="Deployment", namespace="default",
                name=rng.choice(apps[name]),
                patch={"replicas": rng.randint(1, 9)},
            ))
        return WorkSpec(workload=[
            new_deployment(a, replicas=rng.randint(1, 9))
            for a in rng.sample(apps[name], rng.randint(1, 2))
        ])

    def fresh(cluster, name):
        return Work(
            meta=ObjectMeta(
                name=name, namespace=execution_namespace(cluster),
                labels={WORK_BINDING_LABEL: rng.choice(refs)},
            ),
            spec=spec(name),
        )

    skipped0 = metrics.work_status_events_skipped.value(consumer="work-index")
    status_writes = 0
    for step in range(400):
        cluster, name = rng.choice(clusters), rng.choice(names)
        key = f"{execution_namespace(cluster)}/{name}"
        work = store.get("Work", key)
        op = rng.random()
        if work is None or op < 0.15:
            store.apply(fresh(cluster, name))  # create / second writer
        elif op < 0.35:
            work.spec = spec(name)  # the binding controller's in-place write
            if rng.random() < 0.3:
                work.meta.labels[WORK_BINDING_LABEL] = rng.choice(refs)
            store.apply(work)
        elif op < 0.45:
            store.delete("Work", key)
        elif op < 0.5:
            # claims status-only, but is not the stored object
            store.apply(fresh(cluster, name), status_only=True)
        else:
            work.status.manifest_statuses.append(ManifestStatus())
            if rng.random() < 0.5:
                store.apply(work, status_only=True)
            else:
                store.apply_many([work], status_only=True)
            status_writes += 1
        if step % 40 == 39:
            assert _index_answers(index, refs, targets, digests) == (
                _index_answers(_rebuilt(store), refs, targets, digests)
            ), step
    assert _index_answers(index, refs, targets, digests) == _index_answers(
        _rebuilt(store), refs, targets, digests
    )
    assert status_writes > 100
    # every status-only write was turned away, and nothing else
    assert (
        metrics.work_status_events_skipped.value(consumer="work-index")
        - skipped0
    ) == status_writes


# -- (d) work-status renders only to recreate -------------------------------


def test_work_status_renders_only_to_recreate():
    cp = make_plane(2)
    cp.store.apply(policy(dynamic_weight_placement()))
    r = Readings(cp)
    cp.store.apply(new_deployment("web", replicas=6))
    cp.settle()
    assert r.renders("work-status") == 0
    assert cp.work_status_controller.rehydrator._rendered == {}
    rb = cp.store.get("ResourceBinding", "default/web-deployment")
    placed = {tc.name: tc.replicas for tc in rb.spec.clusters}
    victim = next(iter(placed))
    work = cp.store.get("Work", work_key(victim, "web"))
    assert work.spec.workload_template is not None, "a template-delta Work"
    member = cp.members.get(victim)
    before = member.get(GVK, "default", "web")

    member.delete(GVK, "default", "web")  # out of band
    assert member.get(GVK, "default", "web") is None
    cp.settle()

    again = member.get(GVK, "default", "web")
    assert again is not None and again is not before
    assert again.spec["replicas"] == placed[victim]
    assert to_jsonable(again.spec) == to_jsonable(before.spec)
    assert r.renders("work-status") == 1  # rendered at that moment
    assert cp.work_status_controller.rehydrator._rendered == {}
    assert applied(work) == (True, "AppliedSuccessful")


def test_work_status_recreate_waits_for_its_template():
    """The recreate path parks on a template that is not there and is
    un-parked by its arrival."""
    cp = make_plane(1)
    cp.store.apply(policy(duplicated_placement()))
    cp.store.apply(new_deployment("web", replicas=2))
    cp.settle()
    work = cp.store.get("Work", work_key("member1", "web"))
    digest = work.spec.workload_template.digest
    template = cp.store.get("WorkloadTemplate", digest)
    member = cp.members.get("member1")
    cp.store.delete("WorkloadTemplate", digest)
    member.delete(GVK, "default", "web")
    cp.settle()
    assert member.get(GVK, "default", "web") is None
    assert cp.work_status_controller._awaiting_template[digest]
    cp.store.apply(template)
    cp.settle()
    assert member.get(GVK, "default", "web").spec["replicas"] == 2


# -- (e) what the echo used to do, asked for by name -------------------------


def _conflicted_plane():
    cp = make_plane(2)
    cp.members.get("member1").apply(new_deployment("web", replicas=9))
    cp.store.apply(new_deployment("web", replicas=2))
    pol = policy(duplicated_placement())
    pol.spec.conflict_resolution = "Abort"
    cp.store.apply(pol)
    cp.settle()
    work = cp.store.get("Work", work_key("member1", "web"))
    assert applied(work) == (False, "ResourceConflict")
    assert cp.members.get("member1").get(
        GVK, "default", "web"
    ).spec["replicas"] == 9
    return cp, work


def _unmanaged_deleted(member):
    member.delete(GVK, "default", "web")


def _unmanaged_adopted(member):
    from karmada_tpu.utils.member import MANAGED_ANNOTATION

    obj = member.get(GVK, "default", "web")
    obj.meta.annotations[MANAGED_ANNOTATION] = "true"
    member.apply(obj)


@pytest.mark.parametrize(
    "member_change", [_unmanaged_deleted, _unmanaged_adopted]
)
def test_conflicted_work_is_retried_after_a_member_change(member_change):
    """A ResourceConflict is permanent "until the member object changes":
    the change reaches work-status as a member event, and execution
    retries the apply (it was the echo of work-status's write that woke
    it; now work-status asks)."""
    cp, work = _conflicted_plane()
    gen = work.meta.generation
    member = cp.members.get("member1")
    member_change(member)
    cp.settle()
    assert applied(work) == (True, "AppliedSuccessful")
    assert work.meta.generation == gen  # no spec write was needed
    assert member.get(GVK, "default", "web").spec["replicas"] == 2
    rb = cp.store.get("ResourceBinding", "default/web-deployment")
    assert {i.cluster_name: i.applied for i in rb.status.aggregated_status} == {
        "member1": True, "member2": True,
    }


def test_conflict_stays_while_the_member_object_stays():
    cp, work = _conflicted_plane()
    r = Readings(cp)
    cp.settle()
    cp.settle()
    assert r.applies == {}
    assert applied(work) == (False, "ResourceConflict")


def test_member_drift_is_applied_over():
    """The member (a kubelet, a user) writes the propagated object: the
    desired manifest is applied over it once, the member's status kept."""
    cp = make_plane(1)
    cp.store.apply(new_deployment("web", replicas=2))
    cp.store.apply(policy(duplicated_placement()))
    cp.settle()
    member = cp.members.get("member1")
    key = work_key("member1", "web")
    r = Readings(cp)
    obj = member.get(GVK, "default", "web")
    obj.spec["replicas"] = 11  # drift
    obj.status = {"replicas": 2, "readyReplicas": 2, "updatedReplicas": 2}
    member.apply(obj)
    cp.settle()
    now = member.get(GVK, "default", "web")
    assert now.spec["replicas"] == 2
    assert now.status["readyReplicas"] == 2
    assert r.applies == {key: 1}
    work = cp.store.get("Work", key)
    assert work.status.manifest_statuses[0].status is not None
    cp.settle()
    assert r.applies == {key: 1}  # and it rests


def test_unreachable_member_is_retried_until_it_answers():
    """REQUEUE is the retry (wall-clock mode: backoff, without end); no
    Work event is needed for it."""
    cp = make_plane(1)
    cp.store.apply(new_deployment("web", replicas=2))
    cp.store.apply(policy(duplicated_placement()))
    cp.settle()
    member = cp.members.get("member1")
    key = work_key("member1", "web")
    now = [0.0]
    cp.runtime.realtime = True
    cp.execution_controller.worker.clock = lambda: now[0]

    member.reachable = False
    dep = cp.store.get("Resource", "default/web")
    dep.spec["replicas"] = 5
    dep.meta.generation += 1
    cp.store.apply(dep)
    cp.settle()
    work = cp.store.get("Work", key)
    assert applied(work) == (False, "ClusterUnreachable")
    assert cp.execution_controller.worker.delayed == 1

    member.reachable = True
    now[0] += 600.0
    cp.settle()
    assert applied(work) == (True, "AppliedSuccessful")
    assert member.get(GVK, "default", "web").spec["replicas"] == 5
    assert cp.execution_controller.worker.delayed == 0


def test_delete_parked_on_an_unreachable_member_runs_when_it_returns():
    cp = make_plane(2)
    cp.store.apply(new_deployment("web", replicas=2))
    cp.store.apply(policy(duplicated_placement()))
    cp.settle()
    member = cp.members.get("member1")
    member.reachable = False
    cp.store.delete("Work", work_key("member1", "web"))
    cp.execution_controller.worker.process_one()
    assert cp.execution_controller._pending_deletes["member1"]
    member.reachable = True
    assert member.get(GVK, "default", "web") is not None
    cluster = cp.store.get("Cluster", "member1")
    cp.store.apply(cluster)  # any Cluster event un-parks
    cp.execution_controller.worker.process_one()
    assert member.get(GVK, "default", "web") is None
    assert "member1" not in cp.execution_controller._pending_deletes


def test_template_arriving_after_its_work_unparks_it():
    cp = make_plane(1)
    manifest = new_deployment("late", replicas=4)
    ref = WorkloadTemplateRef(
        digest="late-digest", api_version="apps/v1", kind="Deployment",
        namespace="default", name="late", patch={"replicas": 3},
    )
    key = f"{execution_namespace('member1')}/late"
    cp.store.apply(Work(
        meta=ObjectMeta(name="late", namespace=execution_namespace("member1")),
        spec=WorkSpec(workload_template=ref),
    ))
    cp.settle()
    member = cp.members.get("member1")
    assert member.get(GVK, "default", "late") is None
    assert cp.execution_controller._awaiting_template["late-digest"]
    doc = to_jsonable(manifest)
    cp.store.apply(
        WorkloadTemplate(meta=ObjectMeta(name="late-digest"), manifest=doc)
    )
    cp.settle()
    assert member.get(GVK, "default", "late").spec["replicas"] == 3
    assert applied(cp.store.get("Work", key)) == (True, "AppliedSuccessful")
    assert "late-digest" not in cp.execution_controller._awaiting_template


def test_rejected_status_write_is_retried():
    """``_flush`` re-enqueues a Work whose condition write the store
    refused: that enqueue goes past the generation gate."""
    cp = make_plane(1)
    cp.store.apply(policy(duplicated_placement()))
    cp.settle()
    real = cp.store._admission
    refused = []

    def admission(kind, obj):
        if kind == "Work" and obj.status.conditions and not refused:
            refused.append(obj.meta.namespaced_name)
            raise ValueError("refused once")
        real(kind, obj)

    cp.store._admission = admission
    r = Readings(cp)
    cp.store.apply(new_deployment("web", replicas=2))
    cp.settle()
    key = work_key("member1", "web")
    assert refused == [key]
    assert r.applies[key] == 2
    work = cp.store.get("Work", key)
    assert applied(work) == (True, "AppliedSuccessful")
    assert work.meta.resource_version > 0
