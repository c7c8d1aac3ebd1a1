"""Work API: ResourceBinding (the scheduling unit) and Work (the per-cluster
manifest envelope).

Ref: pkg/apis/work/v1alpha2/binding_types.go — ResourceBinding (:58),
ReplicaRequirements (:193), TargetCluster (:229), GracefulEvictionTask (:238),
BindingSnapshot/RequiredBy (:309), status (:326-353);
pkg/apis/work/v1alpha1/work_types.go — Work.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

from .core import Condition, ObjectMeta, ObjectReference, Resource
from .policy import Placement

# Binding condition types (binding_types.go:355-371)
SCHEDULED = "Scheduled"
FULLY_APPLIED = "FullyApplied"

# Work condition types (work_types.go)
WORK_APPLIED = "Applied"
WORK_AVAILABLE = "Available"
WORK_DEGRADED = "Degraded"

# Eviction producers/reasons (binding_types.go well-knowns). The reason
# codes are registered in the REASONS taxonomy (utils/reasons.py — the
# API layer stays import-light, so the literals live here and tier-1
# asserts registry membership; graftlint GL010 guards emission sites)
EVICTION_PRODUCER_TAINT_MANAGER = "TaintManager"
EVICTION_REASON_TAINT_UNTOLERATED = "TaintUntolerated"
EVICTION_REASON_APPLICATION_FAILURE = "ApplicationFailure"
# scarcity plane (ISSUE 14): victim evictions produced by the batched
# preemption kernel; doubles as exclusion-mask stage bit 7 and the
# karmada_tpu_preemptions_total reason label
EVICTION_PRODUCER_PREEMPTION = "PreemptionKernel"
EVICTION_REASON_PREEMPTED = "PreemptedByHigherPriority"
# victim condition type (the reason codes live in utils/reasons.py)
PREEMPTED = "Preempted"
# PurgeMode
PURGE_IMMEDIATELY = "Immediately"
PURGE_GRACIOUSLY = "Graciously"
PURGE_NEVER = "Never"


@dataclass
class NodeClaim:
    """Node-level scheduling claim carried with replica requirements.
    Ref: binding_types.go NodeClaim (nodeSelector/tolerations/hard node
    affinity)."""

    node_selector: dict[str, str] = field(default_factory=dict)
    tolerations: list[Any] = field(default_factory=list)
    hard_node_affinity: Optional[dict] = None


@dataclass
class ReplicaRequirements:
    """Per-replica requirements (canonical int units).
    Ref: binding_types.go:193-213."""

    resource_request: dict[str, int] = field(default_factory=dict)
    node_claim: Optional[NodeClaim] = None
    namespace: str = ""
    priority_class_name: str = ""


@dataclass
class TargetCluster:
    """One schedule-result entry. Ref: binding_types.go:229-236."""

    name: str
    replicas: int = 0


@dataclass
class GracefulEvictionTask:
    """Ref: binding_types.go:238-307."""

    from_cluster: str
    replicas: int = 0
    reason: str = ""
    message: str = ""
    producer: str = ""
    purge_mode: str = PURGE_GRACIOUSLY
    grace_period_seconds: Optional[int] = None
    suppress_deletion: Optional[bool] = None
    creation_timestamp: float = 0.0
    # state carried over for stateful failover (PreservedLabelState)
    preserved_label_state: dict[str, str] = field(default_factory=dict)
    clusters_before_failover: list[str] = field(default_factory=list)


@dataclass
class BindingSnapshot:
    """Dependent-binding shadow of another binding's schedule result.
    Ref: binding_types.go:309-324 (RequiredBy)."""

    namespace: str = ""
    name: str = ""
    clusters: list[TargetCluster] = field(default_factory=list)


@dataclass
class AggregatedStatusItem:
    """Per-cluster aggregated status. Ref: binding_types.go:326-353."""

    cluster_name: str
    status: Optional[dict] = None
    applied: bool = False
    health: str = "Unknown"  # Healthy | Unhealthy | Unknown
    applied_message: str = ""


@dataclass
class ResourceBindingSpec:
    """Ref: binding_types.go:58-148."""

    resource: ObjectReference = field(default_factory=ObjectReference)
    replicas: int = 0
    replica_requirements: Optional[ReplicaRequirements] = None
    placement: Optional[Placement] = None
    # scheduling priority class (ISSUE 14): plumbed from the matched
    # PropagationPolicy's spec.priority by the detector so the scheduler
    # can order waves and the preemption kernel can rank victims. 0 is
    # the back-compat default — pre-priority bindings (and checkpoints
    # restored from them) schedule exactly as before.
    priority: int = 0
    clusters: list[TargetCluster] = field(default_factory=list)
    graceful_eviction_tasks: list[GracefulEvictionTask] = field(default_factory=list)
    required_by: list[BindingSnapshot] = field(default_factory=list)
    reschedule_triggered_at: Optional[float] = None
    conflict_resolution: str = "Abort"
    failover: Optional[Any] = None  # FailoverBehavior snapshot from policy
    propagate_deps: bool = False
    suspend_dispatching: bool = False
    # per-cluster dispatch suspension (Suspension.DispatchingOnClusters,
    # binding_types.go:150-153)
    suspend_dispatching_on_clusters: Optional[list[str]] = None
    preserve_resources_on_deletion: bool = False
    scheduler_name: str = "default-scheduler"


@dataclass
class ResourceBindingStatus:
    """Ref: binding_types.go:326-353."""

    scheduler_observed_generation: int = 0
    scheduler_observed_affinity_name: str = ""
    last_scheduled_time: Optional[float] = None
    conditions: list[Condition] = field(default_factory=list)
    aggregated_status: list[AggregatedStatusItem] = field(default_factory=list)


@dataclass
class ResourceBinding:
    KIND = "ResourceBinding"

    meta: ObjectMeta = field(default_factory=ObjectMeta)
    spec: ResourceBindingSpec = field(default_factory=ResourceBindingSpec)
    status: ResourceBindingStatus = field(default_factory=ResourceBindingStatus)

    @property
    def cluster_scoped(self) -> bool:
        return False


@dataclass
class ClusterResourceBinding(ResourceBinding):
    KIND = "ClusterResourceBinding"

    @property
    def cluster_scoped(self) -> bool:
        return True


# ---------------------------------------------------------------------------
# Work (ref: pkg/apis/work/v1alpha1/work_types.go)
# ---------------------------------------------------------------------------


@dataclass
class ManifestStatus:
    identifier: ObjectReference = field(default_factory=ObjectReference)
    status: Optional[dict] = None
    health: str = "Unknown"


@dataclass
class WorkloadTemplateRef:
    """Template-delta Work rendering (ISSUE 11 tentpole c): instead of a
    full manifest clone per target cluster, a Work may reference ONE
    content-addressed ``WorkloadTemplate`` (shared by every Work of the
    workload family) plus a small per-cluster ``patch`` of spec fields —
    the replica revision the binding controller would have applied.
    Consumers rehydrate via ``controllers.propagation.work_manifests``;
    identity fields ride here so indexes and status routing never need
    the template body."""

    digest: str = ""
    api_version: str = ""
    kind: str = ""
    namespace: str = ""
    name: str = ""
    patch: dict[str, Any] = field(default_factory=dict)


@dataclass
class WorkloadTemplate:
    """One rendered manifest per workload family, stored content-addressed
    (``meta.name`` == digest) and shipped over the bus ONCE instead of
    inside each of N Works. ``manifest`` is the pruned jsonable Resource
    document (the shape ``utils.codec.to_jsonable`` emits)."""

    KIND = "WorkloadTemplate"

    meta: ObjectMeta = field(default_factory=ObjectMeta)
    manifest: dict[str, Any] = field(default_factory=dict)


@dataclass
class WorkSpec:
    workload: list[Resource] = field(default_factory=list)
    # template-delta rendering: when set (and workload is empty) the
    # manifest is template + patch; full-object ``workload`` remains the
    # fallback for non-templatable workloads (custom revise hooks,
    # override-transformed targets) and the kill-switch path
    workload_template: Optional[WorkloadTemplateRef] = None
    suspend_dispatching: bool = False
    preserve_resources_on_deletion: bool = False
    conflict_resolution: str = "Overwrite"  # Overwrite | Abort


@dataclass
class WorkStatus:
    conditions: list[Condition] = field(default_factory=list)
    manifest_statuses: list[ManifestStatus] = field(default_factory=list)


@dataclass
class Work:
    KIND = "Work"
    #: meta.generation is the store's (utils.store.Store.apply): it moves
    #: on every write of a Work but a status-only one, so its watchers can
    #: tell a spec change from a status write
    STORE_GENERATION = True

    meta: ObjectMeta = field(default_factory=ObjectMeta)
    spec: WorkSpec = field(default_factory=WorkSpec)
    status: WorkStatus = field(default_factory=WorkStatus)
