"""Conflict resolution, tracing, events, plugin toggles."""

import logging

from karmada_tpu.api import PropagationPolicy, PropagationSpec, ResourceSelector
from karmada_tpu.api.core import ObjectMeta, Resource
from karmada_tpu.controlplane import ControlPlane
from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot, TensorScheduler
from karmada_tpu.utils.builders import (
    duplicated_placement,
    new_cluster,
    new_deployment,
)
from karmada_tpu.utils.tracing import EventRecorder


def make_plane(n=1, **kw):
    cp = ControlPlane(**kw)
    for i in range(1, n + 1):
        cp.join_cluster(new_cluster(f"member{i}", cpu="100", memory="200Gi"))
    cp.settle()
    return cp


def nginx_policy(conflict_resolution="Abort"):
    return PropagationPolicy(
        meta=ObjectMeta(name="p", namespace="default"),
        spec=PropagationSpec(
            resource_selectors=[
                ResourceSelector(api_version="apps/v1", kind="Deployment")
            ],
            placement=duplicated_placement(),
            conflict_resolution=conflict_resolution,
        ),
    )


class TestConflictResolution:
    def test_abort_on_unmanaged_existing_object(self):
        cp = make_plane(1)
        # a pre-existing unmanaged deployment in the member
        cp.members.get("member1").apply(new_deployment("app", replicas=9))
        cp.store.apply(new_deployment("app", replicas=2))
        cp.store.apply(nginx_policy("Abort"))
        cp.settle()
        # member object untouched; work carries the conflict condition
        obj = cp.members.get("member1").get("apps/v1/Deployment", "default", "app")
        assert obj.spec["replicas"] == 9
        work = cp.store.get("Work", "karmada-es-member1/default.app-deployment")
        cond = next(c for c in work.status.conditions if c.type == "Applied")
        assert not cond.status and cond.reason == "ResourceConflict"

    def test_overwrite_takes_over(self):
        cp = make_plane(1)
        cp.members.get("member1").apply(new_deployment("app", replicas=9))
        cp.store.apply(new_deployment("app", replicas=2))
        cp.store.apply(nginx_policy("Overwrite"))
        cp.settle()
        obj = cp.members.get("member1").get("apps/v1/Deployment", "default", "app")
        assert obj.spec["replicas"] == 2
        assert obj.meta.annotations["karmada.io/managed"] == "true"


class TestTracing:
    def test_event_recorder_ring(self):
        rec = EventRecorder(capacity=2)
        for i in range(4):
            rec.event("ResourceBinding/default/x", "Normal", "Scheduled", str(i))
        assert len(rec.events) == 2
        assert [e.message for e in rec.for_object("ResourceBinding/default/x")] == [
            "2", "3",
        ]


class TestPluginToggles:
    def test_disabled_taint_plugin_admits_tainted_cluster(self):
        from karmada_tpu.api.cluster import Taint

        clusters = [
            new_cluster("ok"),
            new_cluster("tainted", taints=[Taint(key="k", value="v",
                                                 effect="NoSchedule")]),
        ]
        snap = ClusterSnapshot(clusters)
        strict = TensorScheduler(snap)
        lenient = TensorScheduler(snap, disabled_plugins=["TaintToleration"])
        problem = BindingProblem(
            key="b", placement=duplicated_placement(), replicas=1,
            gvk="apps/v1/Deployment",
        )
        [r1] = strict.schedule([problem])
        [r2] = lenient.schedule([problem])
        assert set(r1.clusters) == {"ok"}
        assert set(r2.clusters) == {"ok", "tainted"}


class TestPluginFlagsPlumbing:
    """--plugins enable/disable + out-of-tree filters reach the engine from
    the control-plane constructor (options.go:130-165 analogue)."""

    def _plane(self, **kw):
        from karmada_tpu.api import (
            PropagationPolicy, PropagationSpec, ResourceSelector)
        from karmada_tpu.api.core import ObjectMeta
        from karmada_tpu.api.cluster import Taint
        from karmada_tpu.controlplane import ControlPlane
        from karmada_tpu.utils.builders import (
            dynamic_weight_placement, new_cluster, new_deployment)

        cp = ControlPlane(**kw)
        cp.join_cluster(new_cluster("plain"))
        cp.join_cluster(new_cluster(
            "salty", taints=[Taint(key="dedicated", effect="NoSchedule")]))
        cp.settle()
        cp.store.apply(new_deployment("app", replicas=4, cpu="100m"))
        cp.store.apply(PropagationPolicy(
            meta=ObjectMeta(name="p", namespace="default"),
            spec=PropagationSpec(
                resource_selectors=[ResourceSelector(
                    api_version="apps/v1", kind="Deployment")],
                placement=dynamic_weight_placement(),
            )))
        cp.settle()
        rb = next(iter(cp.store.list("ResourceBinding")))
        return {tc.name for tc in rb.spec.clusters}

    def test_default_filters_tainted_cluster(self):
        assert self._plane() == {"plain"}

    def test_disable_taint_toleration_flag(self):
        names = self._plane(disabled_scheduler_plugins=["TaintToleration"])
        assert names == {"plain", "salty"}

    def test_out_of_tree_filter_plugin(self):
        import numpy as np

        def no_salty(snap, problems):
            mask = np.ones((len(problems), snap.num_clusters), bool)
            for j, name in enumerate(snap.names):
                if name == "plain":
                    mask[:, j] = False
            return mask

        names = self._plane(
            disabled_scheduler_plugins=["TaintToleration"],
            scheduler_filter_plugins=[no_salty],
        )
        assert names == {"salty"}
