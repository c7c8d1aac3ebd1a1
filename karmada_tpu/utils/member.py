"""Member-cluster clients: the boundary to each member's state.

Ref analogues: pkg/util/membercluster_client.go (per-cluster clients),
pkg/util/objectwatcher/objectwatcher.go:43-307 (versioned create/update/
delete of propagated objects), pkg/util/fedinformer (per-cluster informers —
here watch handlers on the member store).

A MemberCluster is an in-process stand-in for one member kube-apiserver:
resources keyed by (gvk, namespace, name), node state for estimators, and a
reachability flag for failure injection (the e2e trick of SURVEY.md
section 4.3 / failover tests). A real deployment replaces this class with a
REST client; the controller code above it is transport-agnostic.
"""

from __future__ import annotations

import copy
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

from ..api.core import Resource
from ..estimator.accurate import NodeState
from .clone import clone_resource
from .worker import WriteCount


class UnreachableError(Exception):
    pass


class ConflictError(Exception):
    """Propagation target already exists and is not managed by the control
    plane (ConflictResolution=Abort)."""


MANAGED_ANNOTATION = "karmada.io/managed"


@dataclass(frozen=True)
class MemberEvent:
    type: str  # Added | Modified | Deleted
    cluster: str
    gvk: str
    namespace: str
    name: str
    obj: Resource


class MemberCluster:
    """One member cluster's state."""

    def __init__(self, name: str):
        self.name = name
        self.reachable = True
        self.kubernetes_version = "v1.31.0"
        self.api_enablements: list[str] = [
            "apps/v1/Deployment",
            "apps/v1/StatefulSet",
            "batch/v1/Job",
            "v1/Pod",
            "v1/ConfigMap",
            "v1/Secret",
            "v1/Service",
            "v1/ServiceAccount",
        ]
        self.nodes: list[NodeState] = []
        self._resources: dict[tuple[str, str, str], Resource] = {}
        self._watchers: list[Callable[[MemberEvent], None]] = []
        self._lock = threading.RLock()
        # objects applied + deleted; the registry this member joins swaps
        # in the count its plane shares (utils.worker.WriteCount)
        self._write_count = WriteCount()
        # workload-key -> unschedulable replica count (descheduler input;
        # ref: estimator server/replica/replica.go)
        self.unschedulable_replicas: dict[str, int] = {}
        # workload-key -> metric sample {"pods", "ready_pods",
        # "cpu_utilization"} (metrics.k8s.io stand-in for the metrics adapter)
        self.pod_metrics: dict[str, dict] = {}
        # workload-key -> PER-POD sample set (the federated podList the
        # FederatedHPA replica calculator groups by readiness; field names
        # are controllers.replica_calculator.PodSample kwargs — request/
        # value in milli-units): [{"name", "phase", "ready", "request",
        # "value", ...}, ...]
        self.workload_pods: dict[str, list[dict]] = {}
        # metrics.k8s.io per-object surfaces (metricsadapter ResourceMetrics):
        # "namespace/pod" -> {"cpu": milli, "memory": bytes, "labels": {...}}
        self.pod_metrics_detail: dict[str, dict] = {}
        # node name -> {"cpu": milli, "memory": bytes, "labels": {...}}
        self.node_metrics: dict[str, dict] = {}
        # custom.metrics.k8s.io series (metricsadapter CustomMetrics): each
        # {"resource": "pods", "namespaced": bool, "namespace": str,
        #  "object": str, "metric": str, "value": float, "labels": {...}}
        self.custom_metric_series: list[dict] = []
        # external.metrics.k8s.io series: each {"namespace": str,
        #  "metric": str, "value": float, "labels": {...}}
        self.external_metric_series: list[dict] = []
        # pod runtime seam: log buffers + pluggable exec handler
        self._pod_logs: dict[tuple[str, str], list[str]] = {}
        self._log_arrived = threading.Condition(self._lock)
        self.exec_handler: Optional[Callable[[Resource, list], dict]] = None
        # streaming runtime seam: iterator[str] of live output lines
        # (SubprocessExecRuntime = a real OS subprocess end-to-end)
        self.exec_stream_handler: Optional[Callable] = None
        # proxy-passthrough audit: (path, impersonated user/groups) records
        self.proxy_audit: list[dict] = []

    # -- client surface ----------------------------------------------------

    def _check(self) -> None:
        if not self.reachable:
            raise UnreachableError(f"cluster {self.name} unreachable")

    def apply(self, obj: Resource) -> Resource:
        self._check()
        key = (f"{obj.api_version}/{obj.kind}", obj.meta.namespace, obj.meta.name)
        with self._lock:
            existed = key in self._resources
            obj.meta.resource_version += 1
            self._resources[key] = obj
            self._write_count.n += 1
        self._notify(
            MemberEvent(
                "Modified" if existed else "Added",
                self.name, key[0], key[1], key[2], obj,
            )
        )
        return obj

    def get(self, gvk: str, namespace: str, name: str) -> Optional[Resource]:
        self._check()
        with self._lock:
            return self._resources.get((gvk, namespace, name))

    def delete(self, gvk: str, namespace: str, name: str) -> Optional[Resource]:
        self._check()
        with self._lock:
            obj = self._resources.pop((gvk, namespace, name), None)
            if obj is not None:
                self._write_count.n += 1
        if obj is not None:
            self._notify(MemberEvent("Deleted", self.name, gvk, namespace, name, obj))
        return obj

    def list(self, gvk: Optional[str] = None) -> list[Resource]:
        self._check()
        with self._lock:
            return [
                o for (g, _, _), o in self._resources.items() if gvk is None or g == gvk
            ]

    def watch(self, handler: Callable[[MemberEvent], None]) -> None:
        self._watchers.append(handler)

    def _notify(self, event: MemberEvent) -> None:
        for h in list(self._watchers):
            h(event)

    # -- pod runtime seam (logs / exec / attach + unschedulable counting) --

    def add_pod(
        self,
        namespace: str,
        name: str,
        *,
        owner_key: str = "",
        conditions: Optional[list[dict]] = None,
        labels: Optional[dict[str, str]] = None,
    ) -> Resource:
        """Register a pod in the member state. Pods are ordinary "v1/Pod"
        resources; ``owner_key`` links the pod to its workload (the stand-in
        for the ownerRef/label-selector match in estimator
        server/replica/replica.go:43-77)."""
        from ..api.core import ObjectMeta

        pod = Resource(
            api_version="v1",
            kind="Pod",
            meta=ObjectMeta(namespace=namespace, name=name, labels=dict(labels or {})),
            spec={"owner_key": owner_key},
            status={"conditions": list(conditions or [])},
        )
        return self.apply(pod)

    def mark_pod_unschedulable(
        self, namespace: str, name: str, since: float
    ) -> None:
        """Set the PodScheduled=False/Unschedulable condition (the signal
        GetUnschedulableReplicas counts)."""
        pod = self.get("v1/Pod", namespace, name)
        if pod is None:
            return
        conds = [
            c
            for c in pod.status.setdefault("conditions", [])
            if c.get("type") != "PodScheduled"
        ]
        conds.append(
            {
                "type": "PodScheduled",
                "status": "False",
                "reason": "Unschedulable",
                "last_transition": since,
            }
        )
        pod.status["conditions"] = conds
        self.apply(pod)

    def count_unschedulable(
        self, now: float, threshold_seconds: float = 60.0
    ) -> dict[str, int]:
        """workload-key -> replicas stuck PodScheduled=False/Unschedulable
        for longer than the threshold (ref: server/replica/replica.go:43-77;
        the threshold mirrors --unschedulable-threshold). Explicit
        ``unschedulable_replicas`` entries (simulation overrides) are merged
        in, taking the max per workload."""
        counts: dict[str, int] = {}
        for pod in self.list("v1/Pod"):
            owner = (pod.spec or {}).get("owner_key", "")
            if not owner:
                continue
            for cond in (pod.status or {}).get("conditions", []):
                if (
                    cond.get("type") == "PodScheduled"
                    and cond.get("status") == "False"
                    and cond.get("reason") == "Unschedulable"
                    and now - cond.get("last_transition", now) >= threshold_seconds
                ):
                    counts[owner] = counts.get(owner, 0) + 1
                    break
        for key, n in self.unschedulable_replicas.items():
            counts[key] = max(counts.get(key, 0), n)
        return counts

    def append_pod_log(self, namespace: str, name: str, line: str) -> None:
        self._check()
        with self._lock:
            self._pod_logs.setdefault((namespace, name), []).append(line)
            self._log_arrived.notify_all()

    def wait_pod_logs(
        self, namespace: str, name: str, after: int, timeout: float = 1.0
    ) -> list[str]:
        """Block up to ``timeout`` for log lines beyond index ``after``
        (the log-follow seam the proxy passthrough streams from)."""
        self._check()
        deadline = time.monotonic() + timeout
        with self._lock:
            while True:
                lines = self._pod_logs.get((namespace, name), [])
                if len(lines) > after:
                    return list(lines[after:])
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return []
                self._log_arrived.wait(remaining)

    def record_proxy_request(self, path: str, headers: dict) -> None:
        """Audit seam: the unified-auth tests assert the member saw the
        impersonated identity, not the plane's own credentials."""
        self.proxy_audit.append(
            {
                "path": path,
                "user": headers.get("Impersonate-User", ""),
                "groups": list(headers.get("Impersonate-Group", []) or []),
            }
        )

    def pod_logs(
        self, namespace: str, name: str, tail: Optional[int] = None
    ) -> list[str]:
        """kubectl logs analogue (karmadactl logs reaches this through the
        clusters/{name}/proxy passthrough)."""
        self._check()
        if self.get("v1/Pod", namespace, name) is None:
            raise KeyError(f"pod {namespace}/{name} not found in {self.name}")
        with self._lock:
            lines = list(self._pod_logs.get((namespace, name), []))
        if tail is None:
            return lines
        return lines[-tail:] if tail > 0 else []

    def pod_exec(self, namespace: str, name: str, command: list[str]) -> dict:
        """kubectl exec/attach analogue. The runtime is pluggable via
        ``exec_handler(pod, command) -> {"stdout", "rc"}``; the default echoes
        (there is no container runtime in-proc)."""
        self._check()
        pod = self.get("v1/Pod", namespace, name)
        if pod is None:
            raise KeyError(f"pod {namespace}/{name} not found in {self.name}")
        if self.exec_handler is not None:
            return self.exec_handler(pod, command)
        if self.exec_stream_handler is not None:
            # collect the streaming runtime's lines (kubectl's exit-code
            # trailer becomes the rc)
            lines, rc = split_exec_trailer(
                list(self.exec_stream_handler(pod, command))
            )
            return {"stdout": "\n".join(lines), "rc": rc}
        return {"stdout": " ".join(command), "rc": 0}

    def pod_exec_stream(self, namespace: str, name: str, command: list[str]):
        """Streaming exec: yields output lines AS THEY APPEAR (the SPDY
        session the reference's karmadactl exec holds open through the
        proxy, pkg/karmadactl/exec/exec.go). Pluggable via
        ``exec_stream_handler(pod, command) -> iterator[str]`` —
        ``SubprocessExecRuntime`` wires a real OS subprocess; the default
        falls back to the one-shot ``pod_exec`` result."""
        self._check()
        pod = self.get("v1/Pod", namespace, name)
        if pod is None:
            raise KeyError(f"pod {namespace}/{name} not found in {self.name}")
        if self.exec_stream_handler is not None:
            yield from self.exec_stream_handler(pod, command)
            return
        res = (
            self.exec_handler(pod, command)
            if self.exec_handler is not None
            else {"stdout": " ".join(command), "rc": 0}
        )
        for line in str(res.get("stdout", "")).splitlines():
            yield line
        rc = int(res.get("rc", 0))
        if rc:
            yield f"{EXEC_EXIT_TRAILER}{rc}"

    # -- member-side simulation helpers (tests / failure injection) --------

    def set_workload_status(
        self, gvk: str, namespace: str, name: str, status: dict
    ) -> None:
        obj = self.get(gvk, namespace, name)
        if obj is not None:
            obj.status = dict(status)
            self.apply(obj)

    def summary_allocatable(self) -> dict[str, int]:
        total: dict[str, int] = {}
        for n in self.nodes:
            for k, v in n.allocatable.items():
                total[k] = total.get(k, 0) + v
        return total

    def summary_allocated(self) -> dict[str, int]:
        total: dict[str, int] = {}
        for n in self.nodes:
            for k, v in n.requested.items():
                total[k] = total.get(k, 0) + v
        return total


#: kubectl's exec failure trailer — the ONE definition every producer
#: (pod_exec, SubprocessExecRuntime) and parser (split_exec_trailer,
#: the remote CLI chain) shares, so the wire format cannot drift
EXEC_EXIT_TRAILER = "command terminated with exit code "


def split_exec_trailer(lines: list[str]) -> tuple[list[str], int]:
    """(output lines without the trailer, exit code) — rc 0 when no
    trailer is present."""
    if lines and lines[-1].startswith(EXEC_EXIT_TRAILER):
        return lines[:-1], int(lines[-1].rsplit(" ", 1)[1])
    return lines, 0


class SubprocessExecRuntime:
    """A real-process exec runtime for the streaming seam: runs the
    command as an OS subprocess and yields stdout lines as they appear —
    the end-to-end analogue of the reference's SPDY exec session
    (pkg/karmadactl/exec/exec.go streams a real container's TTY through
    the proxy; here the "container" is a subprocess, which is as real as
    an in-proc member gets). Wire it per member:
    ``member.exec_stream_handler = SubprocessExecRuntime()``. Intended
    for tests/e2e harnesses — it executes whatever command the caller
    sends, exactly like a kubectl-exec-able container would."""

    def __init__(self, timeout: float = 30.0):
        self.timeout = timeout

    def __call__(self, pod, command):
        import subprocess

        proc = subprocess.Popen(
            list(command), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        try:
            assert proc.stdout is not None
            for line in proc.stdout:
                yield line.rstrip("\n")
            try:
                rc = proc.wait(timeout=self.timeout)
            except subprocess.TimeoutExpired:
                # stdout closed but the process lingers: kill and report
                # (raising here would leave a chunked response
                # unterminated mid-stream)
                proc.kill()
                proc.wait(timeout=5)
                rc = proc.returncode
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=5)
        if rc:
            yield f"{EXEC_EXIT_TRAILER}{rc}"


class MemberClientRegistry:
    def __init__(self, write_count: Optional[WriteCount] = None) -> None:
        self._clients: dict[str, MemberCluster] = {}
        #: bumped by every object a registered member applies or deletes
        #: (a plane passes its store's, so one count covers all its state)
        self.write_count = write_count or WriteCount()

    def register(self, member: MemberCluster) -> None:
        self._clients[member.name] = member
        member._write_count = self.write_count

    def deregister(self, name: str) -> None:
        self._clients.pop(name, None)

    def get(self, name: str) -> Optional[MemberCluster]:
        return self._clients.get(name)

    def names(self) -> Iterable[str]:
        return list(self._clients)


class ObjectWatcher:
    """Versioned create/update/delete of propagated objects into members
    (objectwatcher.go:75-307): records the version it wrote so the status
    collector can tell member drift from control-plane intent, and runs the
    interpreter's Retain hook on update."""

    def __init__(self, members: MemberClientRegistry, interpreter) -> None:
        self.members = members
        self.interpreter = interpreter
        # (cluster, gvk, ns, name) -> (desired manifest pin, applied rv,
        # conflict_resolution): re-applying the SAME manifest object onto an
        # un-drifted member is a no-op. The execution controller no longer
        # comes back once per Work condition update (it drops status
        # writes by the Work's generation), but a key it re-enqueues
        # itself (REQUEUE, a rejected status write, a restart's replay of
        # Works already applied) presents the manifest it applied again,
        # and the pin (a strong ref, so the id cannot be recycled) makes
        # that free. Any member drift changes the observed
        # resource_version and misses the cache.
        self._applied: dict[tuple[str, str, str, str], tuple] = {}

    def create_or_update(
        self, cluster: str, desired: Resource, conflict_resolution: str = "Overwrite"
    ) -> Resource:
        member = self.members.get(cluster)
        if member is None:
            raise UnreachableError(f"no client for cluster {cluster}")
        gvk = f"{desired.api_version}/{desired.kind}"
        vkey = (cluster, gvk, desired.meta.namespace, desired.meta.name)
        observed = member.get(gvk, desired.meta.namespace, desired.meta.name)
        cached = self._applied.get(vkey)
        if (
            cached is not None
            and cached[0] is desired
            and observed is not None
            and observed.meta.resource_version == cached[1]
            and conflict_resolution == cached[2]
        ):
            return observed
        if observed is not None:
            # an unmanaged pre-existing object is a conflict
            # (execution_controller + objectwatcher ConflictResolution)
            if (
                observed.meta.annotations.get(MANAGED_ANNOTATION) != "true"
                and conflict_resolution == "Abort"
            ):
                raise ConflictError(
                    f"{gvk} {desired.meta.namespaced_name} already exists in "
                    f"{cluster} and is not managed"
                )
            # retain() tiers return a fresh object; clone only if a no-hook
            # tier passed `desired` straight through (one copy per apply,
            # not two — the copy chain was the storm's dominant cost)
            to_apply = self.interpreter.retain(desired, observed)
            if to_apply is desired:
                to_apply = clone_resource(desired)
            to_apply.meta.annotations[MANAGED_ANNOTATION] = "true"
            to_apply.meta.resource_version = observed.meta.resource_version
            # member status is owned by the member; never push it down
            to_apply.status = observed.status
        else:
            to_apply = clone_resource(desired)
            to_apply.meta.annotations[MANAGED_ANNOTATION] = "true"
        applied = member.apply(to_apply)
        self._applied[vkey] = (
            desired, applied.meta.resource_version, conflict_resolution,
        )
        return applied

    def delete(self, cluster: str, gvk: str, namespace: str, name: str) -> None:
        member = self.members.get(cluster)
        if member is None:
            return
        member.delete(gvk, namespace, name)
        self._applied.pop((cluster, gvk, namespace, name), None)

    def drifted(
        self, cluster: str, gvk: str, namespace: str, name: str,
        observed: Resource,
    ) -> bool:
        """Whether ``observed``, read from the member, is an object this
        watcher wrote and no longer at the version it wrote (objectwatcher
        NeedsUpdate): the member, or someone on it, has written since."""
        pin = self._applied.get((cluster, gvk, namespace, name))
        return pin is not None and pin[1] != observed.meta.resource_version
