"""Karmada CR + operator reconciler over the workflow engine.

Ref: operator/pkg/apis/operator/v1alpha1/type.go:32 (Karmada CR with
per-component CommonSettings: image/version, replicas, featureGates,
extraArgs), operator/pkg/controller/karmada (reconciler),
operator/pkg/tasks/init (cert -> namespace -> etcd -> apiserver -> upload
-> karmadaresource -> rbac -> component -> wait pipeline) and tasks/deinit.
In-process the heavyweight phases collapse to component wiring, but the
task graph, phases, skip gates, status conditions, version-skew validation
and the UPGRADE reconcile (spec drift re-runs the pipeline with live
rewiring) keep the reference's shape so a remote installer can reuse the
engine.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Optional

from ..api.core import Condition, ObjectMeta, set_condition
from .workflow import Job, Task, WorkflowError

OPERATOR_VERSION = "1.11.0"  # the control-plane version this build ships


@dataclass
class ComponentSpec:
    """Per-component settings (ref: CommonSettings — image/tag, replicas,
    featureGates, extraArgs; type.go:99-150).

    ``enabled``/``version``/``feature_gates`` are enforced by the in-proc
    reconciler (skew validation, component wiring, gate application);
    ``replicas`` and ``extra_args`` are deployment-shape fields a remote
    installer consumes when rendering real component Deployments — the
    in-proc runtime has no pods to scale or flags to pass."""

    enabled: bool = True
    version: str = OPERATOR_VERSION
    replicas: int = 1
    feature_gates: dict[str, bool] = field(default_factory=dict)
    extra_args: dict[str, str] = field(default_factory=dict)
    # JAX_PLATFORMS of the component's process ("cpu" | "tpu"). Only the
    # solver sidecar should ever be non-cpu: one process owns a chip at a
    # time, and dedicating it to the Score/Assign engine is the deployment
    # shape docs/OPERATIONS.md describes. Enforced by the process operator
    # at spawn time.
    platform: str = "cpu"


@dataclass
class KarmadaComponents:
    scheduler: ComponentSpec = field(default_factory=ComponentSpec)
    # the solver sidecar (karmada_tpu.solver) — the component the
    # accelerator platform policy applies to
    solver: ComponentSpec = field(default_factory=ComponentSpec)
    controller_manager: ComponentSpec = field(default_factory=ComponentSpec)
    webhook: ComponentSpec = field(default_factory=ComponentSpec)
    descheduler: ComponentSpec = field(
        default_factory=lambda: ComponentSpec(enabled=False)
    )
    search: ComponentSpec = field(default_factory=ComponentSpec)
    metrics_adapter: ComponentSpec = field(default_factory=ComponentSpec)
    estimators: ComponentSpec = field(
        default_factory=lambda: ComponentSpec(enabled=False)
    )


@dataclass
class KarmadaSpec:
    version: str = OPERATOR_VERSION  # control-plane version (upgrade axis)
    components: KarmadaComponents = field(default_factory=KarmadaComponents)
    member_clusters: list[str] = field(default_factory=list)
    # pull-mode members whose agents run OUT of process (the process
    # operator spawns one karmada_tpu.bus.agent per name)
    pull_members: list[str] = field(default_factory=list)
    feature_gates: dict[str, bool] = field(default_factory=dict)


@dataclass
class KarmadaStatus:
    conditions: list[Condition] = field(default_factory=list)
    completed_tasks: list[str] = field(default_factory=list)
    failed_task: str = ""
    observed_generation: int = 0
    installed_version: str = ""
    # per-component lifetime restart counts from the process supervisor
    # (crash-loop visibility; the ComponentsHealthy condition carries the
    # CrashLoopBackOff reason + backoff detail)
    component_restarts: dict[str, int] = field(default_factory=dict)


@dataclass
class Karmada:
    KIND = "Karmada"

    meta: ObjectMeta = field(default_factory=ObjectMeta)
    spec: KarmadaSpec = field(default_factory=KarmadaSpec)
    status: KarmadaStatus = field(default_factory=KarmadaStatus)


def _minor(version: str) -> tuple[int, int]:
    parts = (version.split("-")[0].lstrip("v").split(".") + ["0", "0"])[:2]
    try:
        return int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(f"unparseable version {version!r}")


def validate_version_skew(plane_version: str, components: KarmadaComponents) -> None:
    """Components may trail the control plane by at most one minor (the
    kube/karmada upgrade contract the reference's upgrade path enforces)."""
    pmaj, pmin = _minor(plane_version)
    for name in vars(components):
        comp: ComponentSpec = getattr(components, name)
        if not comp.enabled:
            continue
        cmaj, cmin = _minor(comp.version)
        if cmaj != pmaj or not (0 <= pmin - cmin <= 1):
            raise ValueError(
                f"component {name} version {comp.version} violates the "
                f"one-minor skew window against control plane {plane_version}"
            )


class KarmadaOperator:
    """Reconciles Karmada CRs into running ControlPlane instances.

    First reconcile runs the full init pipeline; subsequent reconciles
    diff the spec and apply the delta LIVE (component enable/disable,
    feature gates, member join/unjoin, version bump) — the reference's
    upgrade reconcile re-runs its init tasks idempotently the same way."""

    def __init__(self) -> None:
        self.instances: dict[str, object] = {}
        self._applied_specs: dict[str, KarmadaSpec] = {}

    # -- public ------------------------------------------------------------

    def reconcile(self, karmada: Karmada):
        name = karmada.meta.name
        fresh = name not in self.instances
        job = self._init_job(karmada) if fresh else self._upgrade_job(karmada)
        karmada.status.failed_task = ""
        try:
            job.run()
            set_condition(
                karmada.status.conditions,
                Condition(type="Ready", status=True, reason="Completed"),
            )
            karmada.status.installed_version = karmada.spec.version
            karmada.status.observed_generation = karmada.meta.generation
            self._applied_specs[name] = _spec_copy(karmada.spec)
        except WorkflowError as e:
            karmada.status.failed_task = e.task_name
            set_condition(
                karmada.status.conditions,
                Condition(type="Ready", status=False, reason="TaskFailed",
                          message=str(e)),
            )
            if fresh:
                # a half-built install must not masquerade as an upgradable
                # instance: the retry re-runs the init pipeline from scratch
                self.instances.pop(name, None)
            raise
        finally:
            karmada.status.completed_tasks = list(job.completed)
        return self.instances[karmada.meta.name]

    def deinit(self, karmada: Karmada) -> None:
        """tasks/deinit: tear the instance down (members unjoined first so
        their execution spaces drain, then the plane is dropped)."""
        cp = self.instances.pop(karmada.meta.name, None)
        prev = self._applied_specs.pop(karmada.meta.name, None)
        if prev is not None:
            # applied gates revert to defaults with the plane
            from ..utils.features import DEFAULTS, feature_gate

            reverts = dict(prev.feature_gates)
            for comp_name in vars(prev.components):
                reverts.update(getattr(prev.components, comp_name).feature_gates)
            for gate in reverts:
                if gate in DEFAULTS:
                    feature_gate.set(gate, DEFAULTS[gate])
        if cp is not None:
            for name in list(cp.members.names()):
                cp.unjoin_cluster(name)
        set_condition(
            karmada.status.conditions,
            Condition(type="Ready", status=False, reason="Removed"),
        )

    # -- init pipeline (ref: operator/pkg/tasks/init ordering) -------------

    def _init_job(self, karmada: Karmada) -> Job:
        comps = karmada.spec.components
        job = Job(data={"karmada": karmada, "operator": self})
        job.append_task(Task(name="validate", run=self._validate))
        job.append_task(Task(name="prepare-certs", run=self._prepare_certs))
        job.append_task(Task(name="state-store", run=self._state_store))
        job.append_task(
            Task(
                name="control-plane-components",
                run=self._components,
                tasks=[
                    Task(
                        name="descheduler",
                        skip=lambda d: not comps.descheduler.enabled,
                        run=self._enable_descheduler,
                    ),
                    Task(
                        name="estimators",
                        skip=lambda d: not comps.estimators.enabled,
                        run=self._enable_estimators,
                    ),
                ],
            )
        )
        job.append_task(Task(name="feature-gates", run=self._feature_gates))
        job.append_task(Task(name="join-members", run=self._join_members))
        job.append_task(Task(name="wait-ready", run=self._wait_ready))
        return job

    # -- upgrade pipeline (spec drift -> live delta) -----------------------

    def _upgrade_job(self, karmada: Karmada) -> Job:
        prev = self._applied_specs.get(karmada.meta.name)
        job = Job(data={"karmada": karmada, "operator": self,
                        "control_plane": self.instances[karmada.meta.name],
                        "previous": prev})
        job.append_task(Task(name="validate", run=self._validate))
        job.append_task(
            Task(
                name="upgrade-version",
                skip=lambda d: prev is not None
                and prev.version == karmada.spec.version,
                run=self._upgrade_version,
            )
        )
        job.append_task(
            Task(name="reconcile-components", run=self._reconcile_components)
        )
        job.append_task(Task(name="feature-gates", run=self._feature_gates))
        job.append_task(Task(name="reconcile-members", run=self._reconcile_members))
        job.append_task(Task(name="wait-ready", run=self._wait_ready))
        return job

    # -- tasks -------------------------------------------------------------

    def _validate(self, data: dict) -> None:
        karmada: Karmada = data["karmada"]
        validate_version_skew(karmada.spec.version, karmada.spec.components)

    def _prepare_certs(self, data: dict) -> None:
        # in-proc transport needs no PKI; record the intent for parity with
        # the reference's cert task (operator/pkg/tasks/init/cert.go)
        data["certs"] = {"ca": "in-process", "issued_at": time.time()}

    def _state_store(self, data: dict) -> None:
        from ..controlplane import ControlPlane

        karmada: Karmada = data["karmada"]
        cp = ControlPlane(
            enable_descheduler=False,
            enable_accurate_estimator=karmada.spec.components.estimators.enabled,
        )
        data["control_plane"] = cp
        self.instances[karmada.meta.name] = cp

    def _components(self, data: dict) -> None:
        # controllers are wired by ControlPlane construction; nothing extra
        pass

    def _enable_descheduler(self, data: dict) -> None:
        from ..controllers import Descheduler

        cp = data["control_plane"]
        if getattr(cp, "descheduler", None) is None:
            cp.descheduler = Descheduler(
                cp.store, cp.runtime, cp.members, clock=cp.clock
            )
        # the ticker registration is permanent: re-enable must flip the
        # in-place instance, never construct a second one (double ticks)
        cp.descheduler.active = True

    def _disable_descheduler(self, cp) -> None:
        desch = getattr(cp, "descheduler", None)
        if desch is not None:
            # deactivate in place (cli.cmd_addons pattern): dropping the
            # reference alone would leave the registered ticker reclaiming
            desch.active = False

    def _enable_estimators(self, data: dict) -> None:
        cp = data["control_plane"]
        if hasattr(cp, "enable_accurate_estimators"):
            cp.enable_accurate_estimators()

    def _feature_gates(self, data: dict) -> None:
        """Apply the spec's gates and REVERT gates dropped from the spec to
        their defaults (a removed key must not stay latched). NOTE the gate
        registry is process-global (utils/features singleton): in-proc
        planes under one operator share it, matching the one-process
        deployment shape; a multi-plane operator host runs planes in
        separate processes (the reference's one-binary-set-per-plane)."""
        from ..utils.features import DEFAULTS, feature_gate

        karmada: Karmada = data["karmada"]
        prev: Optional[KarmadaSpec] = data.get("previous")
        def gates_of(spec: KarmadaSpec) -> dict[str, bool]:
            # plane-level gates, overridden by per-component gates (the
            # per-binary --feature-gates flags of the reference collapse
            # onto one in-proc registry; component-specific values win)
            merged = dict(spec.feature_gates)
            for comp_name in vars(spec.components):
                merged.update(getattr(spec.components, comp_name).feature_gates)
            return merged

        want = gates_of(karmada.spec)
        for gate in (gates_of(prev) if prev else {}):
            if gate not in want and gate in DEFAULTS:
                feature_gate.set(gate, DEFAULTS[gate])
        for gate, value in want.items():
            feature_gate.set(gate, value)

    def _join_members(self, data: dict) -> None:
        from ..utils.builders import new_cluster

        karmada: Karmada = data["karmada"]
        cp = data["control_plane"]
        for name in karmada.spec.member_clusters:
            cp.join_cluster(new_cluster(name))

    def _upgrade_version(self, data: dict) -> None:
        """Version bump: the in-proc analogue of rolling the component
        deployments to the new image — the skew window was validated, so
        unpinned components (those that tracked the old plane version)
        follow the plane to the new one."""
        karmada: Karmada = data["karmada"]
        prev: Optional[KarmadaSpec] = data.get("previous")
        for name in vars(karmada.spec.components):
            comp: ComponentSpec = getattr(karmada.spec.components, name)
            if prev is not None:
                prev_comp = getattr(prev.components, name)
                if comp.version == prev_comp.version == prev.version:
                    comp.version = karmada.spec.version

    def _reconcile_components(self, data: dict) -> None:
        karmada: Karmada = data["karmada"]
        prev: Optional[KarmadaSpec] = data.get("previous")
        cp = data["control_plane"]
        comps = karmada.spec.components
        prev_comps = prev.components if prev else KarmadaComponents()
        if comps.descheduler.enabled and not prev_comps.descheduler.enabled:
            self._enable_descheduler(data)
        elif not comps.descheduler.enabled and prev_comps.descheduler.enabled:
            self._disable_descheduler(cp)
        if comps.estimators.enabled and not prev_comps.estimators.enabled:
            self._enable_estimators(data)

    def _reconcile_members(self, data: dict) -> None:
        from ..utils.builders import new_cluster

        karmada: Karmada = data["karmada"]
        cp = data["control_plane"]
        want = set(karmada.spec.member_clusters)
        have = set(cp.members.names())
        for name in sorted(want - have):
            cp.join_cluster(new_cluster(name))
        for name in sorted(have - want):
            cp.unjoin_cluster(name)

    def _wait_ready(self, data: dict) -> None:
        cp = data["control_plane"]
        cp.settle()
        karmada: Karmada = data["karmada"]
        for name in karmada.spec.member_clusters:
            cluster = cp.store.get("Cluster", name)
            ready = cluster is not None and any(
                c.type == "Ready" and c.status for c in cluster.status.conditions
            )
            if not ready:
                raise RuntimeError(f"cluster {name} not ready")


def _spec_copy(spec: KarmadaSpec) -> KarmadaSpec:
    comps = KarmadaComponents(
        **{
            name: replace(
                getattr(spec.components, name),
                feature_gates=dict(getattr(spec.components, name).feature_gates),
                extra_args=dict(getattr(spec.components, name).extra_args),
            )
            for name in vars(spec.components)
        }
    )
    return KarmadaSpec(
        version=spec.version,
        components=comps,
        member_clusters=list(spec.member_clusters),
        pull_members=list(spec.pull_members),
        feature_gates=dict(spec.feature_gates),
    )
