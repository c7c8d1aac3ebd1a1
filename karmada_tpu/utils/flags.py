"""Per-binary flag surfaces with the reference's flag names.

Ref: cmd/*/app/options/options.go — each reference process exposes its
configuration as pflag surfaces (--plugins, --feature-gates,
--enable-scheduler-estimator, --descheduling-interval, ...). The in-proc
runtime collapses nine binaries into constructor kwargs; these parsers keep
the FLAG CONTRACT: an operator's existing launch args parse here and map
onto the corresponding in-proc configuration, so deployment manifests carry
over. Each ``parse_*`` returns the kwargs dict its component constructor
accepts (plus a ``settings`` section for flags that configure live
behavior such as feature gates, applied by ``apply_common``).

Semantics preserved from the reference:
- ``--plugins`` (scheduler, options.go:163): '*' enables all in-tree
  plugins; '*,-Foo' disables Foo; an explicit list enables only those.
- ``--controllers`` (controller-manager, options.go:165): same grammar
  over controller names.
- ``--feature-gates``: key=bool pairs applied to the feature registry.
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass
from typing import Optional, Sequence

# -- environment-flag registry ----------------------------------------------
#
# Every KARMADA_TPU_* environment variable any process in this repo reads
# MUST be declared here (graftlint rule GL003 enforces it) and is rendered
# into the docs/OPERATIONS.md env table by ``render_env_table()``
# (tools/docs_from_bench.py regenerates the table and fails loudly on
# drift). The read sites stay where they are — this registry is the
# DECLARATION surface, the analogue of the reference's pflag definitions
# for knobs that configure processes below the flag parser (backend
# selection, cache policy) or from test/bench drivers.


@dataclass(frozen=True)
class EnvFlag:
    name: str
    default: str
    description: str
    #: read outside the package tree (test/bench drivers): exempt from
    #: graftlint's registered-but-never-read staleness check
    external: bool = False


ENV_FLAGS: dict[str, EnvFlag] = {
    f.name: f
    for f in (
        EnvFlag(
            "KARMADA_TPU_TRACE_MANIFEST", "<cache dir>/trace_manifest.json",
            "Trace-signature manifest path (scheduler.prewarm."
            "TraceManifest): fleet engines record fresh solve-family "
            "traces into it and AOT prewarm replays it at boot. Empty "
            "string disables recording and restoring.",
        ),
        EnvFlag(
            "KARMADA_TPU_CACHE_MIN_COMPILE_SECS", "1.0",
            "Persistent XLA compile-cache threshold (utils.compilecache): "
            "compiles faster than this are not persisted. Prewarm drops "
            "it to 0 so every warmed trace survives the process.",
        ),
        EnvFlag(
            "KARMADA_TPU_PREWARM_ON_REBUILD", "0",
            "Set to 1/true to replay the trace manifest on a daemon "
            "thread whenever a fleet table is (re)built, compiling the "
            "rebuilt table's upcoming shapes off the serving path.",
        ),
        EnvFlag(
            "KARMADA_TPU_NO_NATIVE", "0",
            "Set to 1 to skip building/loading the ctypes native decode "
            "helpers and always use the numpy fallback path.",
        ),
        EnvFlag(
            "KARMADA_TPU_ESTIMATOR_BATCH", "1",
            "Batched estimator wire protocol (estimator.accurate): set to "
            "0 to force every connection onto the per-profile unary "
            "fallback — the mixed-version escape hatch; servers that "
            "answer UNIMPLEMENTED negotiate the fallback per connection "
            "automatically.",
        ),
        EnvFlag(
            "KARMADA_TPU_BUS_BATCH", "4096",
            "Columnar bus channel (bus.service): max write-through ops "
            "per ApplyBatch RPC and watch events per WatchBatch frame. "
            "0 forces every connection onto the per-object unary "
            "fallback — the mixed-version escape hatch; servers that "
            "answer UNIMPLEMENTED negotiate the fallback per connection "
            "automatically.",
        ),
        EnvFlag(
            "KARMADA_TPU_BUS_FLUSH_MS", "2",
            "Watch-frame coalescing window (ms): after the first queued "
            "event a WatchBatch stream waits this long for more before "
            "flushing the frame — the latency bound of event batching "
            "(count bound: KARMADA_TPU_BUS_BATCH).",
        ),
        EnvFlag(
            "KARMADA_TPU_BUS_TEMPLATE_DELTA", "1",
            "Template-delta Work rendering kill switch (controllers."
            "propagation): 0 renders every Work as a full manifest "
            "clone instead of one content-addressed WorkloadTemplate "
            "plus per-cluster replica patches. Targets with custom "
            "ReviseReplica hooks or matching override rules full-render "
            "either way.",
        ),
        EnvFlag(
            "KARMADA_TPU_ESTIMATOR_PING_SECONDS", "0",
            "Seconds a cluster's snapshot-generation confirmation stays "
            "trusted across EstimatorRegistry.invalidate(); 0 re-pings "
            "the estimator servers (one GetGenerations per server) on "
            "every invalidated pass.",
        ),
        EnvFlag(
            "KARMADA_TPU_ESTIMATOR_FALLBACK_WIDTH", "4",
            "In-flight MaxAvailableReplicas calls per server CHANNEL when "
            "the unary fallback is negotiated: the per-profile queries "
            "pipeline over each channel via grpc futures (bounded, so the "
            "HTTP/2 stream limit is never flooded) instead of blocking "
            "sequentially per cluster. 1 disables pipelining.",
        ),
        EnvFlag(
            "KARMADA_TPU_METRICS_PORT", "",
            "Default /metrics + /healthz (+ /debug/traces) port (or "
            "HOST:PORT — loopback unless a host is given) for the "
            "standalone process entrypoints (solver sidecar, estimator "
            "servers, store bus) when --metrics-port is not given "
            "(utils.metrics.serve_process_metrics). Empty disables the "
            "endpoint; 0 binds an ephemeral port (printed at startup).",
        ),
        EnvFlag(
            "KARMADA_TPU_FAULT_SPEC", "",
            "Deterministic fault-injection spec (utils.faultinject): "
            "semicolon-separated `point=action[,rate=][,count=][,after=]"
            "[,match=][,delay=]` rules armed at process boot by the "
            "entrypoints (localup serve, solver sidecar, estimator "
            "__main__, bus agent). Empty (the default) leaves injection "
            "disarmed — one `is None` check per injection point, zero "
            "overhead. Actions: error/drop/delay/sever/down.",
        ),
        EnvFlag(
            "KARMADA_TPU_FAULT_SEED", "0",
            "Seed for the fault-injection firing decisions: rules with "
            "rate < 1 derive every decision from blake2b(seed, point, "
            "invocation index), so a chaos run replays bit-identically "
            "from (spec, seed) and the fired-event log doubles as the "
            "numpy oracle's replay script.",
        ),
        EnvFlag(
            "KARMADA_TPU_BACKOFF_BASE", "0.05",
            "First decorrelated-jitter retry sleep (seconds) of the "
            "unified channel policy (utils.backoff.default_policy); "
            "every retried RPC on the solver/estimator/bus channels "
            "sleeps within [base, 3x previous], capped.",
        ),
        EnvFlag(
            "KARMADA_TPU_BACKOFF_CAP", "2.0",
            "Cap (seconds) on one decorrelated-jitter retry sleep of the "
            "unified channel policy.",
        ),
        EnvFlag(
            "KARMADA_TPU_BREAKER_RESET_SECONDS", "5.0",
            "Seconds an open circuit breaker waits before admitting the "
            "single half-open probe; the probe's success closes the "
            "breaker without operator action (karmada_tpu_circuit_state "
            "tracks the transitions).",
        ),
        EnvFlag(
            "KARMADA_TPU_MESH_DEVICES", "",
            "Device count of the scheduling-grid mesh "
            "(parallel.mesh.resolve_mesh): engines shard the fleet solve "
            "along the bindings axis over the first N visible devices. "
            "Empty/0/1 = single-device (mesh off); 'auto' = every visible "
            "device. CPU CI dry-runs combine it with "
            "--xla_force_host_platform_device_count=N in XLA_FLAGS. A "
            "value the backend cannot host fails engine construction "
            "loudly instead of silently running single-device.",
        ),
        EnvFlag(
            "KARMADA_TPU_MESH_CLUSTER_AXIS", "1",
            "Cluster-axis extent of the scheduling mesh (the 'c' axis): "
            "1 = pure binding-parallel; >1 additionally shards the "
            "cluster axis (the dispense sorts ride c-axis collectives). "
            "Must divide KARMADA_TPU_MESH_DEVICES.",
        ),
        EnvFlag(
            "KARMADA_TPU_TRACE_CAPACITY", "32768",
            "Span capacity of the wave-trace ring "
            "(utils.tracing.WaveTracer): 1M-tier storms outgrow the "
            "default and spans silently aging off the ring degrade "
            "wave_summary coverage — evictions are counted "
            "(karmada_tpu_trace_spans_dropped_total + the `dropped` "
            "field of /debug/traces) so the operator sees when to raise "
            "it. Read once at tracer construction.",
        ),
        EnvFlag(
            "KARMADA_TPU_TRACE_SLO_SECONDS", "",
            "Arms the slow-wave flight recorder (utils.tracing): a "
            "closing wave whose wall exceeds this many seconds — or "
            "during which a breaker transition, degraded pass or "
            "QuotaExceeded denial fired — persists its stitched trace + "
            "metrics delta + fired-fault log as one JSONL record under "
            "KARMADA_TPU_FLIGHT_DIR. Empty (the default) disarms the "
            "recorder entirely: one env read per wave boundary, nothing "
            "per span.",
        ),
        EnvFlag(
            "KARMADA_TPU_FLIGHT_DIR", "<tmp>/karmada_tpu_flight",
            "Directory the flight recorder appends flight.jsonl under "
            "(ring-capped on disk; `karmadactl-tpu trace analyze` "
            "re-renders a record's attribution offline).",
        ),
        EnvFlag(
            "KARMADA_TPU_FLIGHT_CAP", "64",
            "Maximum flight-recorder records kept in flight.jsonl "
            "(oldest dropped first).",
        ),
        EnvFlag(
            "KARMADA_TPU_HISTORY_CAP", "512",
            "Wave capacity of the per-process telemetry-history ring "
            "(utils.history.WaveHistory): every end_wave() samples one "
            "structured wave row (per-phase self seconds, engine pass "
            "stats, per-channel RPC counts, device bytes) served as "
            "/debug/history and aggregated by `karmadactl-tpu top`. "
            "0 disables sampling entirely; evictions past the cap are "
            "counted, never silent. Read once at history construction.",
        ),
        EnvFlag(
            "KARMADA_TPU_HISTORY_STITCH", "1",
            "Per-wave stitched history sampling: when trace peers are "
            "registered (KARMADA_TPU_TRACE_PEERS), each closing wave's "
            "history row takes its phase attribution from the "
            "cross-process stitched summary — one narrowed "
            "/debug/traces?wave=N fetch per peer per wave close. 0 keeps "
            "sampling local-only (rows still record every local series).",
        ),
        EnvFlag(
            "KARMADA_TPU_EXPLAIN", "",
            "Placement-provenance arm switch (utils.explainstore): set "
            "to 1 and every engine pass runs ONE extra batched explain "
            "dispatch (ops.explain.explain_pass) capturing per-binding x "
            "per-cluster stage-exclusion masks + top-k candidate "
            "summaries into the /debug/explain ring. Unset/0 — the "
            "default — costs one `is None` check per pass.",
        ),
        EnvFlag(
            "KARMADA_TPU_EXPLAIN_CAP", "8",
            "Explain-capture ring cap in WAVES (utils.explainstore."
            "ExplainStore): older waves' captures evict (counted, never "
            "silent) once more than this many waves are retained; 0 "
            "disables the store even when armed.",
        ),
        EnvFlag(
            "KARMADA_TPU_TRACE_PEERS", "",
            "Comma-separated `name=host:port` metrics endpoints of the "
            "plane's peer processes (solver sidecar, estimator servers, "
            "store bus) for the cross-process trace stitcher; parsed at "
            "process boot by utils.tracing.register_peers_from_env. "
            "`trace dump --stitch`, wave_summary(stitched=True) and the "
            "flight recorder pull /debug/traces from every entry.",
        ),
        EnvFlag(
            "KARMADA_TPU_QUOTA_ENFORCEMENT", "1",
            "FederatedResourceQuota admission in the scheduler "
            "(controllers.scheduler_controller): set to 0 to disable the "
            "quota plane entirely — no QuotaSnapshot is built and the "
            "engine's admission hook stays a single `is None` check. "
            "Member-side static-assignment Works still sync either way.",
        ),
        EnvFlag(
            "KARMADA_TPU_PREEMPTION", "1",
            "Scarcity-plane kill switch (scheduler controller + engine): "
            "0 disarms the batched preemption kernel — high-priority "
            "waves that cannot fit stay unschedulable instead of "
            "selecting victims. Disarmed costs one `is None` check per "
            "engine pass (the quota/fault-injection pattern).",
        ),
        EnvFlag(
            "KARMADA_TPU_DESCHEDULE_MAX_DISRUPTION", "64",
            "Continuous-descheduler disruption budget: the maximum "
            "bindings one drift-rebalance round may stamp "
            "RescheduleTriggeredAt on (highest-drift first; FIFO ties). "
            "0 disables the tier entirely. Published per round as "
            "karmada_tpu_desched_disruption_budget.",
        ),
        EnvFlag(
            "KARMADA_TPU_ADMISSION_TIMEOUT", "5",
            "Per-request read deadline (seconds) for the external "
            "admission webhook channel (webhook.server.RemoteAdmission). "
            "Each request gets ONE bounded retry on an unreachable/"
            "timed-out webhook before admission fails — the webhook-boot "
            "window under full-machine load is the case this absorbs; "
            "raise it on oversubscribed CI rigs.",
        ),
    )
}


def render_env_table() -> str:
    """The docs/OPERATIONS.md environment-variable table, generated from
    ``ENV_FLAGS`` so prose can never drift from the declaration surface
    (tools/docs_from_bench.py writes it between the envflags markers and
    fails loudly when the committed table differs)."""
    lines = [
        "| variable | default | what it does |",
        "|---|---|---|",
    ]
    for name in sorted(ENV_FLAGS):
        f = ENV_FLAGS[name]
        default = f.default if f.default else '""'
        lines.append(f"| `{name}` | `{default}` | {f.description} |")
    return "\n".join(lines)


#: the in-tree scheduler plugin set (framework/plugins/registry.go:30-39)
IN_TREE_PLUGINS = (
    "APIEnablement",
    "ClusterAffinity",
    "ClusterEviction",
    "ClusterLocality",
    "SpreadConstraint",
    "TaintToleration",
)

#: controllers the manager can toggle (controller-manager options.go:165)
CONTROLLERS = (
    "binding", "cluster", "clusterStatus", "execution", "workStatus",
    "namespace", "gracefulEviction", "applicationFailover", "remedy",
    "workloadRebalancer", "federatedResourceQuota", "unifiedAuth",
    "serviceExport", "multiclusterservice", "federatedHorizontalPodAutoscaler",
    "cronFederatedHorizontalPodAutoscaler", "dependenciesDistributor",
)


def parse_star_list(values: Sequence[str], universe: Sequence[str], what: str):
    """'*' / '*,-Foo' / explicit-list grammar shared by --plugins and
    --controllers. Returns (enabled set, disabled set)."""
    items = [v.strip() for v in values for v in v.split(",") if v.strip()]
    if not items:
        return set(universe), set()
    has_star = "*" in items
    disabled = {v[1:] for v in items if v.startswith("-")}
    explicit = {v for v in items if v != "*" and not v.startswith("-")}
    unknown = (disabled | explicit) - set(universe)
    if unknown:
        raise ValueError(f"unknown {what}: {sorted(unknown)}")
    if has_star:
        return set(universe) - disabled, disabled
    if disabled and not explicit:
        return set(universe) - disabled, disabled
    return explicit, set(universe) - explicit


def _feature_gates(value: str) -> dict[str, bool]:
    out: dict[str, bool] = {}
    for pair in value.split(","):
        pair = pair.strip()
        if not pair:
            continue
        key, _, raw = pair.partition("=")
        if raw.lower() not in ("true", "false"):
            raise ValueError(f"feature gate {pair!r} must be key=true|false")
        out[key] = raw.lower() == "true"
    return out


def _common(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--kubeconfig", default="")
    parser.add_argument("--master", default="")
    parser.add_argument("--metrics-bind-address", default=":8080")
    parser.add_argument("--health-probe-bind-address", default=":10351")
    parser.add_argument("--feature-gates", type=_feature_gates, default={})
    parser.add_argument("--leader-elect", default="true")


def apply_common(ns: argparse.Namespace) -> None:
    """Apply process-wide settings (feature gates) from parsed flags."""
    from .features import feature_gate

    for gate, value in (ns.feature_gates or {}).items():
        feature_gate.set(gate, value)


# -- karmada-scheduler (cmd/scheduler/app/options/options.go) ---------------


def scheduler_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="karmada-scheduler", add_help=False)
    _common(p)
    p.add_argument("--scheduler-name", default="default-scheduler")
    p.add_argument("--plugins", action="append", default=[])
    p.add_argument("--enable-scheduler-estimator", default="false")
    p.add_argument("--disable-scheduler-estimator-in-pull-mode", default="false")
    p.add_argument("--scheduler-estimator-timeout", default="3s")
    p.add_argument("--scheduler-estimator-port", type=int, default=10352)
    p.add_argument("--enable-empty-workload-propagation", default="false")
    return p


def parse_scheduler_flags(argv: Sequence[str]) -> dict:
    ns = scheduler_parser().parse_args(argv)
    apply_common(ns)
    enabled, disabled = parse_star_list(
        ns.plugins or ["*"], IN_TREE_PLUGINS, "plugins"
    )
    return {
        "scheduler_name": ns.scheduler_name,
        "disabled_plugins": tuple(sorted(disabled)),
        "enable_scheduler_estimator": ns.enable_scheduler_estimator == "true",
        "scheduler_estimator_timeout_seconds": _duration(
            ns.scheduler_estimator_timeout
        ),
        "scheduler_estimator_port": ns.scheduler_estimator_port,
    }


# -- karmada-controller-manager ---------------------------------------------


def controller_manager_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="karmada-controller-manager", add_help=False
    )
    _common(p)
    p.add_argument("--controllers", action="append", default=[])
    p.add_argument("--cluster-monitor-period", default="5m")
    p.add_argument("--cluster-monitor-grace-period", default="40s")
    p.add_argument("--failover-eviction-timeout", default="5m")
    p.add_argument("--graceful-eviction-timeout", default="10m")
    p.add_argument("--concurrent-work-syncs", type=int, default=5)
    return p


def parse_controller_manager_flags(argv: Sequence[str]) -> dict:
    ns = controller_manager_parser().parse_args(argv)
    apply_common(ns)
    enabled, disabled = parse_star_list(
        ns.controllers or ["*"], CONTROLLERS, "controllers"
    )
    return {
        "enabled_controllers": tuple(sorted(enabled)),
        "disabled_controllers": tuple(sorted(disabled)),
        "eviction_timeout": _duration(ns.failover_eviction_timeout),
        "cluster_monitor_grace_period": _duration(
            ns.cluster_monitor_grace_period
        ),
    }


# -- karmada-descheduler -----------------------------------------------------


def descheduler_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="karmada-descheduler", add_help=False)
    _common(p)
    p.add_argument("--descheduling-interval", default="2m")
    p.add_argument("--unschedulable-threshold", default="5m")
    return p


def parse_descheduler_flags(argv: Sequence[str]) -> dict:
    ns = descheduler_parser().parse_args(argv)
    apply_common(ns)
    return {
        "descheduling_interval": _duration(ns.descheduling_interval),
        "unschedulable_threshold": _duration(ns.unschedulable_threshold),
    }


# -- karmada-agent (cmd/agent/app/options/options.go) ------------------------


def agent_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="karmada-agent", add_help=False)
    _common(p)
    p.add_argument("--cluster-name", required=True)
    p.add_argument("--cluster-namespace", default="karmada-cluster")
    p.add_argument("--cluster-status-update-frequency", default="10s")
    p.add_argument("--report-secrets", action="append",
                   default=["KubeCredentials", "KubeImpersonator"])
    return p


def parse_agent_flags(argv: Sequence[str]) -> dict:
    ns = agent_parser().parse_args(argv)
    apply_common(ns)
    return {
        "cluster_name": ns.cluster_name,
        "cluster_namespace": ns.cluster_namespace,
        "status_update_frequency": _duration(
            ns.cluster_status_update_frequency
        ),
    }


# -- helpers -----------------------------------------------------------------


_UNITS = {"ms": 0.001, "s": 1.0, "m": 60.0, "h": 3600.0}


def _duration(value: str) -> float:
    """Go duration strings ('3s', '5m', '1h30m', '500ms') -> seconds."""
    value = value.strip()
    total = 0.0
    num = ""
    i = 0
    while i < len(value):
        ch = value[i]
        if ch.isdigit() or ch == ".":
            num += ch
            i += 1
            continue
        unit = ch
        if value[i:i + 2] == "ms":
            unit = "ms"
        if unit not in _UNITS or not num:
            raise ValueError(f"unparseable duration {value!r}")
        total += float(num) * _UNITS[unit]
        num = ""
        i += len(unit)
    if num:  # bare number = seconds
        total += float(num)
    return total
