"""Smaller control-plane components: namespace sync, WorkloadRebalancer,
FederatedResourceQuota, unified auth.

Ref:
- namespace-sync-controller (pkg/controllers/namespace/, 285 LoC):
  auto-propagates user namespaces to every member cluster.
- workloadRebalancer (pkg/controllers/workloadrebalancer/):
  `WorkloadRebalancer` CR sets spec.rescheduleTriggeredAt on listed bindings
  -> Fresh reassignment (assignment.go:109-117).
- federatedResourceQuota sync/status (pkg/controllers/federatedresourcequota/):
  static quota slices propagated to member clusters as Works; status
  aggregates used from members.
- unified-auth-controller (pkg/controllers/unifiedauth/): RBAC sync into
  members for admin subjects.
"""

from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Optional

from ..api.core import ObjectMeta, Resource
from ..api.work import Work, WorkSpec
from ..utils import DONE, Runtime, Store
from .propagation import execution_namespace

SKIP_AUTO_PROPAGATION_LABEL = "namespace.karmada.io/skip-auto-propagation"
_RESERVED_NS_PREFIXES = ("kube-", "karmada-")
_RESERVED_NS = {"default", "kube-system", "kube-public"}


class NamespaceSyncController:
    """Namespace templates -> Works in every member cluster
    (namespace/namespace_sync_controller.go)."""

    def __init__(self, store: Store, runtime: Runtime) -> None:
        self.store = store
        self.worker = runtime.new_worker("namespace-sync", self._reconcile)
        # the Namespace templates' names, in the order they came in: what a
        # Cluster event re-enqueues, without a walk of every Resource
        self._namespaces: dict[str, None] = {}
        store.watch("Resource", self._on_resource_event)
        store.watch("Cluster", self._on_cluster_event)

    def _on_resource_event(self, event) -> None:
        if event.obj.kind == "Namespace":
            name = event.obj.meta.name
            if event.type == "Deleted":
                self._namespaces.pop(name, None)
            else:
                self._namespaces[name] = None
            self.worker.enqueue(name)

    def _on_cluster_event(self, event) -> None:
        for name in list(self._namespaces):
            self.worker.enqueue(name)

    def _should_sync(self, ns: Resource) -> bool:
        name = ns.meta.name
        if name in _RESERVED_NS or any(
            name.startswith(p) for p in _RESERVED_NS_PREFIXES
        ):
            return False
        if ns.meta.labels.get(SKIP_AUTO_PROPAGATION_LABEL) == "true":
            return False
        return True

    def _reconcile(self, name: str) -> Optional[str]:
        ns = self.store.get("Resource", name)
        if ns is None or ns.kind != "Namespace" or not self._should_sync(ns):
            return DONE
        for cluster in self.store.list("Cluster"):
            work_ns = execution_namespace(cluster.name)
            key = f"{work_ns}/ns-{name}"
            if self.store.get("Work", key) is None:
                self.store.apply(
                    Work(
                        meta=ObjectMeta(name=f"ns-{name}", namespace=work_ns),
                        spec=WorkSpec(workload=[ns]),
                    )
                )
        return DONE


# --- WorkloadRebalancer ------------------------------------------------------


@dataclass
class ObjectReferenceSelector:
    api_version: str = "apps/v1"
    kind: str = "Deployment"
    namespace: str = ""
    name: str = ""


@dataclass
class WorkloadRebalancerSpec:
    workloads: list[ObjectReferenceSelector] = field(default_factory=list)
    # lifetime after every workload finished; None = keep forever
    # (workloadrebalancer_types.go:61-67)
    ttl_seconds_after_finished: Optional[int] = None


@dataclass
class WorkloadRebalancerStatus:
    observed_workloads: list[dict] = field(default_factory=list)
    observed_generation: int = 0
    finish_time: Optional[float] = None
    # content digest of the spec.workloads that produced this status —
    # the echo gate's comparison key (see _workloads_digest)
    observed_spec_digest: str = ""


def _workloads_digest(workloads) -> str:
    """Content identity of ``spec.workloads``. The apiserver auto-bumps
    generation on spec writes but Store.apply does not, so a writer that
    edits the list in place hands the reconciler the SAME generation —
    and with a same-length edit, the same workload count. Only content
    tells such an edit apart from our own status-apply echo."""
    h = hashlib.sha256()
    for t in workloads:
        h.update(
            f"{t.api_version}|{t.kind}|{t.namespace}|{t.name}\n".encode()
        )
    return h.hexdigest()


@dataclass
class WorkloadRebalancer:
    KIND = "WorkloadRebalancer"

    meta: ObjectMeta = field(default_factory=ObjectMeta)
    spec: WorkloadRebalancerSpec = field(default_factory=WorkloadRebalancerSpec)
    status: WorkloadRebalancerStatus = field(default_factory=WorkloadRebalancerStatus)


class WorkloadRebalancerController:
    """Sets rescheduleTriggeredAt on the bindings of listed workloads
    (workloadrebalancer controller -> Fresh assignment)."""

    def __init__(self, store: Store, runtime: Runtime, clock=time.time) -> None:
        self.store = store
        self.clock = clock
        self.worker = runtime.new_worker("workload-rebalancer", self._reconcile)
        store.watch("WorkloadRebalancer", lambda e: self.worker.enqueue(e.key))
        runtime.add_ticker(self._sweep_expired)

    def _sweep_expired(self) -> None:
        """TTLSecondsAfterFinished cleanup
        (workloadrebalancer_controller.go:99-107,295-298)."""
        now = self.clock()
        for r in list(self.store.list("WorkloadRebalancer")):
            if (
                r.spec.ttl_seconds_after_finished is not None
                and r.status.finish_time is not None
                and now - r.status.finish_time
                >= r.spec.ttl_seconds_after_finished
            ):
                self.store.delete("WorkloadRebalancer", r.meta.namespaced_name)

    def _reconcile(self, key: str) -> Optional[str]:
        rebalancer = self.store.get("WorkloadRebalancer", key)
        if rebalancer is None:
            return DONE
        spec_digest = _workloads_digest(rebalancer.spec.workloads)
        # getattr: a checkpoint restore unpickles statuses written by a
        # pre-digest build (Store.restore bypasses __init__), so the field
        # can be missing; such a legacy finished status falls back to the
        # old length gate rather than re-triggering every restored
        # rebalancer at boot
        status_digest = getattr(
            rebalancer.status, "observed_spec_digest", ""
        )
        digest_ok = (
            status_digest == spec_digest
            if status_digest
            else len(rebalancer.status.observed_workloads)
            == len(rebalancer.spec.workloads)
        )
        if (
            rebalancer.status.observed_generation == rebalancer.meta.generation
            and rebalancer.status.finish_time is not None
            # generation alone is not enough in this store: the apiserver
            # auto-bumps generation on spec writes, Store.apply does not —
            # an in-place workloads edit hands us the same generation. The
            # digest compares CONTENT, so a same-length in-place edit (a
            # swapped target) re-triggers like any other spec change; the
            # O(W) hash is noise next to the O(W x B) cascade it gates.
            and digest_ok
        ):
            # already fully observed at this generation: the reconcile we
            # are seeing is our own status-apply echo. Without this gate a
            # finished rebalancer RE-TRIGGERED every listed binding on its
            # echo — a 100k-workload storm wave re-ran the whole
            # reschedule cascade once per echo (188 s measured where the
            # clean wave runs 13 s). The reference requeues on generation
            # change only (workloadrebalancer_controller.go predicates).
            return DONE
        # one (kind, name) -> bindings index per reconcile (the reference
        # resolves each workload through an indexed lister): a 20k-workload
        # rebalancer over 20k bindings was O(W x B) = 400M scans — 330 s of
        # a measured whole-plane storm wave; indexed it is O(W + B)
        by_ref: dict[tuple[str, str], list] = {}
        for rb in self.store.list("ResourceBinding"):
            ref = rb.spec.resource
            by_ref.setdefault((ref.kind, ref.name), []).append(rb)
        observed = []
        # (observed index, rb, pre-bump trigger) — maps rejections back and
        # lets the rollback RESTORE a still-pending earlier trigger (the
        # store hands out live references: zeroing the field would erase a
        # legitimate trigger the scheduler had not yet consumed)
        triggered = []
        for target in rebalancer.spec.workloads:
            result = "NotFound"
            for rb in by_ref.get((target.kind, target.name), ()):
                if (
                    target.namespace
                    and rb.spec.resource.namespace != target.namespace
                ):
                    continue
                prior = rb.spec.reschedule_triggered_at
                rb.spec.reschedule_triggered_at = self.clock()
                rb.meta.generation += 1
                triggered.append((len(observed), rb, prior))
                result = "Successful"
            observed.append(
                {"workload": f"{target.kind}/{target.namespace}/{target.name}",
                 "result": result}
            )
        # one batched store sweep for the whole trigger wave; a per-object
        # admission rejection rolls the in-place bump back and surfaces as
        # Failed on the observed workload (the old per-object apply path
        # raised; swallowing it would report Successful for a binding that
        # will never reschedule)
        by_id = {
            id(rb): (idx, prior) for idx, rb, prior in triggered
        }
        apply_many = getattr(self.store, "apply_many", None)
        if apply_many is not None:
            rejected = apply_many([rb for _, rb, _ in triggered])
            for rb, err in rejected:
                idx, prior = by_id[id(rb)]
                rb.meta.generation -= 1
                rb.spec.reschedule_triggered_at = prior
                observed[idx]["result"] = f"Failed: {err}"
        else:
            for idx, rb, prior in triggered:
                try:
                    self.store.apply(rb)
                except Exception as err:  # noqa: BLE001 — per-object verdict
                    rb.meta.generation -= 1
                    rb.spec.reschedule_triggered_at = prior
                    observed[idx]["result"] = f"Failed: {err}"
        finished = all(o["result"] != "Pending" for o in observed)
        finish_time = rebalancer.status.finish_time
        reprocessed = (
            rebalancer.status.observed_workloads != observed
            or rebalancer.status.observed_generation
            != rebalancer.meta.generation
        )
        if finished and (finish_time is None or reprocessed):
            # a fresh observation wave RESTAMPS the finish: the TTL window
            # (ttlSecondsAfterFinished) must count from the LATEST finish,
            # or a spec update near the deadline would complete its
            # re-trigger and be swept with the new results seconds later
            finish_time = self.clock()
        elif not finished:
            # new unfinished work (e.g. a spec update added workloads) must
            # clear the stamp, or the TTL sweep deletes a pending rebalancer
            finish_time = None
        changed = (
            rebalancer.status.observed_workloads != observed
            or rebalancer.status.observed_generation != rebalancer.meta.generation
            or rebalancer.status.finish_time != finish_time
            or status_digest != spec_digest
        )
        if changed:
            rebalancer.status.observed_workloads = observed
            rebalancer.status.observed_generation = rebalancer.meta.generation
            rebalancer.status.finish_time = finish_time
            rebalancer.status.observed_spec_digest = spec_digest
            self.store.apply(rebalancer)
        return DONE


# --- FederatedResourceQuota --------------------------------------------------


class FederatedResourceQuotaController:
    """Static assignment sync + LIVE usage accounting.

    Per-cluster ResourceQuota slices still ship as Works
    (federatedresourcequota/federated_resource_quota_sync_controller.go),
    but ``status.overall_used`` is now recomputed from bound
    ResourceBindings — the reference's FRQ status controller shape: one
    sweep over the namespace's bindings sums ``assigned replicas x
    per-replica request`` per tracked resource (each replica occupying one
    pod, mirroring the estimator's implicit pods request). The member-
    reported aggregation this replaces double-counted the very workloads
    the plane itself propagated and went stale between member status
    syncs; binding-derived usage moves in the same settle wave as the
    schedule, which is what the scheduler's admission plane keys on.

    Binding events enqueue only the namespaces that actually carry an FRQ
    (a 100k-binding storm in unquota'd namespaces never touches this
    worker), and the batched reconcile computes every dirty FRQ from ONE
    sweep over the binding list."""

    def __init__(self, store: Store, runtime: Runtime, members=None) -> None:
        self.store = store
        self.members = members  # kept for constructor compat (unused)
        self.worker = runtime.new_worker(
            "frq", self._reconcile, reconcile_batch=self._reconcile_batch
        )
        # namespace -> FRQ keys, maintained from watch events so the
        # per-binding-event check is one set lookup
        self._frq_by_ns: dict[str, set[str]] = {}
        for frq in store.list("FederatedResourceQuota"):
            self._frq_by_ns.setdefault(
                frq.meta.namespace, set()
            ).add(frq.meta.namespaced_name)
        store.watch("FederatedResourceQuota", self._on_quota_event)
        store.watch("Cluster", self._on_cluster_event)
        store.watch("ResourceBinding", self._on_binding_event)

    def _on_quota_event(self, event) -> None:
        frq = event.obj
        ns = frq.meta.namespace
        if event.type == "Deleted":
            keys = self._frq_by_ns.get(ns, set())
            keys.discard(frq.meta.namespaced_name)
            if keys:
                # surviving FRQs re-reconcile so the namespace's gauge
                # sweep drops the deleted quota's samples
                for key in keys:
                    self.worker.enqueue(key)
            else:
                # last FRQ of the namespace: retire its gauge samples, or
                # `quota status` reports the dead quota's limits forever
                from ..utils.metrics import quota_limit, quota_used

                quota_limit.remove_matching(namespace=ns)
                quota_used.remove_matching(namespace=ns)
        else:
            self._frq_by_ns.setdefault(ns, set()).add(
                frq.meta.namespaced_name
            )
            self.worker.enqueue(frq.meta.namespaced_name)

    def _on_cluster_event(self, event) -> None:
        for frq in self.store.list("FederatedResourceQuota"):
            self.worker.enqueue(frq.meta.namespaced_name)

    def _on_binding_event(self, event) -> None:
        keys = self._frq_by_ns.get(event.obj.meta.namespace)
        if keys:
            for key in keys:
                self.worker.enqueue(key)

    def _usage_by_namespace(self, namespaces: set) -> dict:
        """One sweep over the binding list: namespace -> {resource: used}
        for the requested namespaces. Delegates to the scheduler plane's
        single usage formula (scheduler.quota.usage_from_bindings) so the
        accounting the status controller writes and the demand math the
        admission kernel charges can never disagree."""
        from ..scheduler.quota import usage_from_bindings

        return usage_from_bindings(self.store, namespaces)

    def _reconcile(self, key: str) -> Optional[str]:
        return self._reconcile_batch([key]).get(key, DONE)

    def _reconcile_batch(self, keys) -> dict:
        out: dict = {}
        live: list = []
        for key in keys:
            frq = self.store.get("FederatedResourceQuota", key)
            out[key] = DONE
            if frq is not None:
                live.append((key, frq))
        if not live:
            return out
        namespaces = {frq.meta.namespace for _, frq in live}
        usage = self._usage_by_namespace(namespaces)
        for key, frq in live:
            self._reconcile_one(frq, usage.get(frq.meta.namespace, {}))
        # gauge exposition is a per-namespace CLEAR-then-SET sweep over
        # every live FRQ: a deleted quota, or a spec edit dropping a
        # resource, retires its stale samples instead of serving them
        # forever
        from ..utils.metrics import quota_limit, quota_used

        for ns in namespaces:
            quota_limit.remove_matching(namespace=ns)
            quota_used.remove_matching(namespace=ns)
            ns_usage = usage.get(ns, {})
            for key in self._frq_by_ns.get(ns, set()):
                frq = self.store.get("FederatedResourceQuota", key)
                if frq is None:
                    continue
                for res, limit in frq.spec.overall.items():
                    quota_limit.set(int(limit), namespace=ns, resource=res)
                    quota_used.set(
                        int(ns_usage.get(res, 0)), namespace=ns, resource=res
                    )
        return out

    def _reconcile_one(self, frq, ns_usage: dict) -> None:
        for assignment in frq.spec.static_assignments:
            cluster = self.store.get("Cluster", assignment.cluster_name)
            if cluster is None:
                continue
            quota = Resource(
                api_version="v1",
                kind="ResourceQuota",
                meta=ObjectMeta(name=frq.meta.name, namespace=frq.meta.namespace),
                spec={"hard": dict(assignment.hard)},
            )
            work_ns = execution_namespace(assignment.cluster_name)
            work_name = f"quota-{frq.meta.namespace}.{frq.meta.name}"
            wkey = f"{work_ns}/{work_name}"
            existing = self.store.get("Work", wkey)
            if existing is None or existing.spec.workload[0].spec != quota.spec:
                self.store.apply(
                    Work(
                        meta=ObjectMeta(name=work_name, namespace=work_ns),
                        spec=WorkSpec(workload=[quota]),
                    )
                )
        # live accounting: only the tracked resources are reported (the
        # reference reports used for spec.overall's resource set)
        overall_used = {
            res: int(ns_usage.get(res, 0)) for res in frq.spec.overall
        }
        changed = False
        if frq.status.overall != frq.spec.overall:
            frq.status.overall = dict(frq.spec.overall)
            changed = True
        if frq.status.overall_used != overall_used:
            frq.status.overall_used = overall_used
            changed = True
        if changed:
            self.store.apply(frq)
