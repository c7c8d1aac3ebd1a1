"""Observability: counters/gauges/histograms with a Prometheus registry.

Ref: pkg/scheduler/metrics/metrics.go:61-115 (schedule_attempts_total,
e2e_scheduling_duration_seconds, scheduling_algorithm_duration_seconds
{schedule_step=Filter|Score|Select|AssignReplicas}, per-plugin timers) and
pkg/metrics (controller metrics). Text exposition follows the Prometheus
format so a scraper can consume ``render()`` directly: ``# HELP`` before
``# TYPE``, cumulative histogram buckets, label values escaped per the
text-format rules.

Every long-running process (plane, solver sidecar, estimator servers, the
store bus) serves this registry at ``/metrics`` (+ ``/healthz`` and the
``/debug/traces`` wave-trace dump) through ``MetricsServer``; the shared
``--metrics-port`` flag semantics live in ``serve_process_metrics``.

Thread-safety contract: ``inc()``/``set()``/``observe()`` mutate under the
per-metric lock, and every READ path (``value()``, ``summary()``, both
``render()`` paths) snapshots the sample dicts under that same lock before
iterating — a scrape racing a storm of observes must never see a bucket
list mid-update or die on a dict that grew mid-iteration. (This is the
GL004 invariant stated in code rather than carried by a single-writer
pragma: there IS no single writer here, so the lock is load-bearing on
both sides.)
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Iterable, Optional

_DEFAULT_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0
)

#: end-to-end bucket set for whole-wave / settle-pass latencies: a 1M-tier
#: settle pass legitimately runs 14-15 s and a cold wave minutes — with the
#: default buckets every such observation landed in +Inf and the histogram
#: said nothing (ISSUE 6 satellite). Scrapers still get sub-second
#: resolution at the fast end.
E2E_BUCKETS = (
    0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 15.0, 30.0, 60.0,
    120.0, 300.0,
)


def _label_key(labels: dict[str, str]) -> tuple:
    return tuple(sorted(labels.items()))


def _escape_label_value(value) -> str:
    """Prometheus text-format label escaping: backslash, double-quote and
    newline must be escaped inside the quoted label value."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _label_str(key: tuple) -> str:
    return ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in key)


def _help_line(name: str, help_: str) -> str:
    # HELP text escaping: backslash and newline (the text format's rules
    # for HELP differ from label values — no quote escaping)
    escaped = help_.replace("\\", "\\\\").replace("\n", "\\n")
    return f"# HELP {name} {escaped}"


class Tally:
    """One label set of a Counter, bound once (``Counter.labels``) by an
    owner that counts on a hot path: ``inc`` is a plain add, with no lock
    and no label sort (a watch handler that turns away thousands of events
    a wave pays for nothing else). Exact under the single-threaded
    reconcile runtime; writers racing on threads may lose an increment, as
    with the workers' own counts (utils.worker)."""

    __slots__ = ("n",)

    def __init__(self) -> None:
        self.n = 0.0

    def inc(self, amount: float = 1.0) -> None:
        self.n += amount


class Counter:
    kind = "counter"

    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._values: dict[tuple, float] = defaultdict(float)
        self._tallies: dict[tuple, Tally] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels) -> None:
        with self._lock:
            self._values[_label_key(labels)] += amount

    def labels(self, **labels) -> Tally:
        """The Tally of this label set (one a label set, shared by every
        caller): its count is added to what ``inc`` counted there."""
        key = _label_key(labels)
        with self._lock:
            tally = self._tallies.get(key)
            if tally is None:
                tally = self._tallies[key] = Tally()
            return tally

    def value(self, **labels) -> float:
        key = _label_key(labels)
        with self._lock:
            tally = self._tallies.get(key)
            return self._values.get(key, 0.0) + (tally.n if tally else 0.0)

    def samples(self) -> dict[tuple, float]:
        """Label-set -> value snapshot (bench records enumerate these)."""
        with self._lock:
            out = dict(self._values)
            for key, tally in self._tallies.items():
                out[key] = out.get(key, 0.0) + tally.n
            return out

    def snapshot(self) -> dict[str, float]:
        """JSON-stable samples (label string -> value) — the flight
        recorder's metrics-delta surface."""
        return {_label_str(k): v for k, v in self.samples().items()}

    def render(self) -> Iterable[str]:
        if self.help:
            yield _help_line(self.name, self.help)
        yield f"# TYPE {self.name} counter"
        for key, v in sorted(self.samples().items()):
            label_s = _label_str(key)
            yield f"{self.name}{{{label_s}}} {v}" if label_s else f"{self.name} {v}"


class SampledCounter(Counter):
    """A counter the registry only READS: its owner counts in plain
    numbers and ``read()`` answers ``{label-set: value}`` when asked. For a
    source that may take no lock where it counts — the collector's callback
    (utils.tracing.GcWatch) fires at any allocation, also one made under
    this registry's own locks."""

    def __init__(self, name: str, help_: str, read):
        super().__init__(name, help_)
        self._read = read

    def inc(self, amount: float = 1.0, **labels) -> None:
        raise TypeError(f"{self.name} is read from its source, not incremented")

    def labels(self, **labels) -> Tally:
        raise TypeError(f"{self.name} is read from its source, not incremented")

    def value(self, **labels) -> float:
        return self.samples().get(_label_key(labels), 0.0)

    def samples(self) -> dict[tuple, float]:
        return dict(self._read())


class Gauge:
    """A settable sample (queue depth, subscriber count). Same lock
    contract as Counter: set/add mutate and every read snapshots under the
    lock."""

    kind = "gauge"

    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._values[_label_key(labels)] = float(value)

    def add(self, amount: float = 1.0, **labels) -> None:
        with self._lock:
            key = _label_key(labels)
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def samples(self) -> dict[tuple, float]:
        """Label-set -> value snapshot (the Counter contract; the
        history sampler and bench records enumerate these)."""
        with self._lock:
            return dict(self._values)

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            return {_label_str(k): v for k, v in self._values.items()}

    def remove_matching(self, **labels) -> None:
        """Drop every sample whose label set CONTAINS these pairs — the
        cleanup hook for gauges keyed by a deleted object (e.g. a removed
        FederatedResourceQuota's per-resource limit/used samples)."""
        match = set(labels.items())
        with self._lock:
            for key in [k for k in self._values if match <= set(k)]:
                del self._values[key]

    def render(self) -> Iterable[str]:
        if self.help:
            yield _help_line(self.name, self.help)
        yield f"# TYPE {self.name} gauge"
        with self._lock:
            items = sorted(self._values.items())
        for key, v in items:
            label_s = _label_str(key)
            yield f"{self.name}{{{label_s}}} {v}" if label_s else f"{self.name} {v}"


class Timed:
    """One interval ``Histogram.time`` observed: ``start`` (a perf_counter
    stamp) and ``duration`` in seconds, set when the block ends."""

    __slots__ = ("start", "duration")

    def __init__(self) -> None:
        self.start = 0.0
        self.duration = 0.0


class Histogram:
    kind = "histogram"

    def __init__(self, name: str, help_: str = "", buckets=_DEFAULT_BUCKETS):
        self.name = name
        self.help = help_
        self.buckets = tuple(buckets)
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = defaultdict(float)
        self._totals: dict[tuple, int] = defaultdict(int)
        self._lock = threading.Lock()

    def observe(self, value: float, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    counts[i] += 1
            self._sums[key] += value
            self._totals[key] += 1

    @contextmanager
    def time(self, **labels):
        """Observe the block's wall time. Yields the ``Timed`` interval it
        observed (filled in on exit), so a caller can record the same
        stretch as a span without reading the clock again."""
        timed = Timed()
        timed.start = time.perf_counter()
        try:
            yield timed
        finally:
            timed.duration = time.perf_counter() - timed.start
            self.observe(timed.duration, **labels)

    def snapshot(self) -> dict[str, dict]:
        """{label string: {count, sum}} — buckets are derivable and the
        flight recorder's delta only needs the two scalars."""
        with self._lock:
            return {
                _label_str(k): {
                    "count": self._totals[k], "sum": self._sums[k]
                }
                for k in self._totals
            }

    def quantile(self, q: float, **labels) -> Optional[float]:
        """Bucket-interpolated quantile of one label set (ISSUE 12
        satellite): the Prometheus ``histogram_quantile`` estimate over
        the cumulative bucket counts, so CLIs stop eyeballing raw
        buckets. None when the label set has no observations."""
        key = _label_key(labels)
        with self._lock:
            total = self._totals.get(key, 0)
            if not total:
                return None
            counts = list(self._counts[key])
        return bucket_quantile(q, self.buckets, counts, total)

    def summary(self, **labels) -> Optional[dict]:
        key = _label_key(labels)
        with self._lock:
            if key not in self._totals:
                return None
            total = self._totals[key]
            s = self._sums[key]
        return {"count": total, "sum": s, "avg": s / max(total, 1)}

    def render(self) -> Iterable[str]:
        if self.help:
            yield _help_line(self.name, self.help)
        yield f"# TYPE {self.name} histogram"
        with self._lock:
            # consistent snapshot of all three sample dicts: counts lists
            # are copied so a concurrent observe cannot mutate a row
            # mid-render (the totals/sums pair for a key stays coherent
            # because both are written under this same lock)
            keys = sorted(self._totals)
            counts_snap = {k: list(self._counts[k]) for k in keys}
            sums_snap = {k: self._sums[k] for k in keys}
            totals_snap = {k: self._totals[k] for k in keys}
        for key in keys:
            label_s = _label_str(key)
            prefix = f"{self.name}_bucket{{{label_s}" if label_s else f"{self.name}_bucket{{"
            counts = counts_snap[key]  # already cumulative (observe adds to
            # every bucket whose bound covers the value)
            sep = "," if label_s else ""
            for i, bound in enumerate(self.buckets):
                yield f'{prefix}{sep}le="{bound}"}} {counts[i]}'
            yield f'{prefix}{sep}le="+Inf"}} {totals_snap[key]}'
            base = f"{self.name}_sum{{{label_s}}}" if label_s else f"{self.name}_sum"
            yield f"{base} {sums_snap[key]}"
            base = f"{self.name}_count{{{label_s}}}" if label_s else f"{self.name}_count"
            yield f"{base} {totals_snap[key]}"


def bucket_quantile(
    q: float, bounds, cumulative_counts, total: int
) -> Optional[float]:
    """THE bucket-interpolation core (Prometheus ``histogram_quantile``
    semantics): ``bounds`` are the finite upper bounds, ``cumulative_
    counts`` the cumulative observation counts per bound, ``total`` the
    +Inf count. Linear interpolation inside the landing bucket (the
    first bucket interpolates from 0); a rank landing in +Inf answers
    the highest finite bound — the estimate cannot exceed what the
    buckets resolve. Shared by ``Histogram.quantile`` and the CLI
    exposition parsers (karmadactl-tpu quota status / top), so the two
    sides can never drift."""
    if total <= 0 or not bounds:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile q={q} outside [0, 1]")
    rank = q * total
    prev_bound = 0.0
    prev_count = 0
    for bound, count in zip(bounds, cumulative_counts):
        if count >= rank:
            in_bucket = count - prev_count
            if in_bucket <= 0:
                return float(bound)
            frac = (rank - prev_count) / in_bucket
            return float(prev_bound + (bound - prev_bound) * frac)
        prev_bound, prev_count = float(bound), count
    return float(bounds[-1])


class Registry:
    def __init__(self) -> None:
        self._metrics: list = []

    def counter(self, name: str, help_: str = "", *, read=None) -> Counter:
        """``read``: the counter's owner keeps the numbers (SampledCounter)."""
        c = Counter(name, help_) if read is None else SampledCounter(
            name, help_, read
        )
        self._metrics.append(c)
        return c

    def gauge(self, name: str, help_: str = "") -> Gauge:
        g = Gauge(name, help_)
        self._metrics.append(g)
        return g

    def histogram(self, name: str, help_: str = "", buckets=_DEFAULT_BUCKETS) -> Histogram:
        h = Histogram(name, help_, buckets)
        self._metrics.append(h)
        return h

    def snapshot(self) -> dict[str, dict]:
        """family name -> {label string: value | {count, sum}} across the
        whole registry — the flight recorder snapshots it at wave open
        and deltas it at wave close (utils.tracing.maybe_flight_record)."""
        return {m.name: m.snapshot() for m in self._metrics}

    def families(self) -> list:
        """(name, type, help) per registered metric — the docs metric
        table and its drift guard (tools/docs_from_bench.py) read this."""
        return [(m.name, m.kind, m.help) for m in self._metrics]

    def render(self) -> str:
        lines: list[str] = []
        for m in self._metrics:
            lines.extend(m.render())
        return "\n".join(lines) + "\n"


# global registry + the scheduler metric set (metrics.go:61-115)
registry = Registry()

schedule_attempts = registry.counter(
    "karmada_scheduler_schedule_attempts_total",
    "scheduling attempts by result and type",
)
e2e_scheduling_duration = registry.histogram(
    "karmada_scheduler_e2e_scheduling_duration_seconds",
    "end-to-end schedule latency",
    buckets=E2E_BUCKETS,
)
scheduling_algorithm_duration = registry.histogram(
    "karmada_scheduler_scheduling_algorithm_duration_seconds",
    "per-step scheduling latency",
)
queue_incoming_bindings = registry.counter(
    "karmada_scheduler_queue_incoming_bindings_total",
    "queue pressure by event",
)

# -- plane-wide families (ISSUE 6) ------------------------------------------
#
# Defined centrally so EVERY process that imports utils.metrics exposes the
# full family set on /metrics (a family with no samples still renders its
# HELP/TYPE header — scrapers and the docs drift guard see the complete
# catalogue regardless of which subsystem ran yet).

scheduler_pass_seconds = registry.histogram(
    "karmada_tpu_scheduler_pass_seconds",
    "one engine pass over a queued binding batch (batched drain of the "
    "scheduler worker)",
    buckets=E2E_BUCKETS,
)
settle_seconds = registry.histogram(
    "karmada_tpu_settle_seconds",
    "one run_until_settled drain of the whole controller fleet (a storm "
    "wave is one settle)",
    buckets=E2E_BUCKETS,
)
kernel_compiles = registry.counter(
    "karmada_tpu_kernel_compiles_total",
    "fresh XLA trace signatures dispatched by the fleet engine, by kernel "
    "family (each is one compile, on or off the serving path)",
)
kernel_prewarmed = registry.counter(
    "karmada_tpu_kernel_prewarmed_total",
    "trace-manifest records AOT-compiled by prewarm (off the serving "
    "path), by outcome",
)
kernel_phase_seconds = registry.histogram(
    "karmada_tpu_kernel_phase_seconds",
    "fleet kernel hot-path wall time split by phase: host (pack/upsert/"
    "sync/decode), dispatch, device (fenced execute, compile included "
    "when the pass minted a fresh trace), fetch",
)
spread_selections = registry.counter(
    "karmada_tpu_spread_selections_total",
    "spread-constrained rows seen by the engine's Select stage, by "
    "outcome: device (selected by the fleet table's kernel from its "
    "resident state), hit (host path, answered from the row cache: same "
    "row content, same snapshot generation), computed (SelectClusters "
    "ran on the host), fit_error (the constraints cannot be met: on the "
    "device the row reports no candidate; a host-selected row leaves the "
    "fleet path and the host path reports it)",
)
spread_host_selected_rows = registry.gauge(
    "karmada_tpu_spread_host_selected_rows",
    "spread-constrained rows of the last such batch whose SelectClusters "
    "stage ran on the host because the snapshot holds more regions than "
    "the fleet table's select kernel takes (scheduler.select.R_CAP = 8); 0 "
    "when the kernel took them all. Above 0 each of those rows costs host "
    "time in every wave whose snapshot generation moved",
)
affinity_term_choices = registry.counter(
    "karmada_tpu_affinity_term_choices_total",
    "multi-term (ordered clusterAffinities) rows the fleet table's term "
    "kernel chose a term for, by outcome: first (the first term fits), "
    "fallback (a later term is the first that fits: the row failed over), "
    "unfit (no term fits: the row keeps its last term, whose division "
    "reports the failure)",
)
eviction_masked_rows = registry.counter(
    "karmada_tpu_eviction_masked_rows_total",
    "rows of fleet passes that held at least one graceful-eviction task "
    "(the ClusterEviction filter masked those members on the device)",
)
fleet_host_path_rows = registry.gauge(
    "karmada_tpu_fleet_host_path_rows",
    "rows of the last batch that left the fleet table for the general host "
    "path: more affinity terms than the table's term slots "
    "(scheduler.fleet.T_CAP = 4), more eviction tasks than its task sites "
    "(K_EVICT = 8), a Divided row of more replicas than a cell holds, or "
    "several terms together with spread constraints. Above 0 each of "
    "those rows is packed and solved on the host in every wave",
)
fleet_host_path_rows_total = registry.counter(
    "karmada_tpu_fleet_host_path_rows_total",
    "rows of engine passes that left the fleet table for the general host "
    "path, by the first bound each row passed (counted once): terms (more "
    "affinity terms than T_CAP = 4), evict_tasks (more eviction tasks "
    "than K_EVICT = 8), terms_spread (several terms together with spread "
    "constraints), replicas (a Divided row past what a cell of the fleet "
    "table holds: MAX_REPLICAS_FAST = 65535, or 255 at 16,384 members or "
    "more), selection (a spread-constrained row given no selection); added "
    "once a pass that has such rows",
)
fleet_placement_slots = registry.gauge(
    "karmada_tpu_fleet_placement_slots",
    "placement slots the fleet table holds (one an affinity term of a user "
    "placement; a spread selection is row state and takes none), set after "
    "every pass",
)
fleet_slots_minted = registry.counter(
    "karmada_tpu_fleet_slots_minted_total",
    "placement slots added to a fleet table (one an affinity term at a "
    "placement's first row, or every live placement again after a table "
    "rebuild); flat once each user placement has its slots",
)
fleet_upsert_rows = registry.counter(
    "karmada_tpu_fleet_upsert_rows_total",
    "rows of fleet passes by what the table's upsert phase made of them: "
    "same (the row holds that very object: the batch-identity path, a "
    "position the diff of a swapped batch did not visit, a replayed "
    "position of a delta pass), equal (another object of equal content: "
    "pinned, not repacked), packed (new to the table or content moved: row "
    "state rewritten and uploaded); added once a pass",
)
fleet_wide_rows = registry.counter(
    "karmada_tpu_fleet_wide_rows_total",
    "rows of fleet passes that ride in the table's wide form: a previous "
    "result of more than K_PREV = 32 sites kept in a slot of the wide "
    "table, or a Divided row of more replicas than a one-byte cell holds "
    "(the table's cells two bytes wide); added once a pass",
)
fleet_batch_derived = registry.counter(
    "karmada_tpu_fleet_batch_derived_total",
    "fleet passes by what became of the values a pass derives from its "
    "batch's row state (the affinity names, the largest replicas and "
    "previous count that pick the kernel variant, whether a row divides "
    "Aggregated or answers by bitset): kept (the resident batch's row "
    "vector came again and no row of the table was packed since), built "
    "(another row vector, or a pack, a compaction or a growth since: "
    "derived again over every row of the batch); added once a pass",
)
fleet_changed_rows = registry.counter(
    "karmada_tpu_fleet_changed_rows_total",
    "changed rows of dispatched fleet passes by the wire that brought "
    "their answer home: delta (the cell-delta wire, a row of at most 62 "
    "changed cells) or entries (phase B, _fleet_entries: rows past 62 "
    "changed cells, or every changed row where the delta buffer overflowed "
    "or the pass had no delta wire); added once a pass",
)
fleet_delta_cells = registry.counter(
    "karmada_tpu_fleet_delta_cells_total",
    "cells the cell-delta wire of fleet passes carried home (each a site "
    "and its new count); added once a pass",
)
fleet_entry_speculations = registry.counter(
    "karmada_tpu_fleet_entry_speculations_total",
    "speculative phase-B dispatches of fleet passes (dispatched behind the "
    "pass where the last pass churned off the delta wire) by outcome: used "
    "(its entry wire brought the changed rows home) or drained (the pass "
    "folded another way, and the dispatch was waited out)",
)
scheduler_prologue_rows = registry.counter(
    "karmada_tpu_scheduler_prologue_rows_total",
    "positions of full-path engine passes by what the host prologue made "
    "of them: kept (a batch of the armed batch's length under a moved "
    "mask_token, diffed by object identity: the position holds the armed "
    "object, so its placement and its fleet eligibility stand and only "
    "the distinct placements are compiled anew), visited (compiled and "
    "held to the fleet-eligibility predicate: a moved position of such a "
    "batch, every position of a batch that is walked); added once a pass",
)
fleet_table_rebuilds = registry.counter(
    "karmada_tpu_fleet_table_rebuilds_total",
    "fleet tables dropped and rebuilt because their LIVE rows reference "
    "more placements, GVKs or request profiles than the slot budget "
    "holds (a full repack and re-upload each)",
)
estimator_rpcs = registry.counter(
    "karmada_tpu_estimator_rpcs_total",
    "scheduler-side estimator wire traffic by kind (batch matrix RPCs, "
    "per-profile unary fallback calls, generation pings)",
)
estimator_delta_requeries = registry.counter(
    "karmada_tpu_estimator_delta_requery_total",
    "clusters whose availability was re-fetched after a generation "
    "movement (the delta half of the generation-gated refresh)",
)
estimator_refresh_seconds = registry.histogram(
    "karmada_tpu_estimator_refresh_seconds",
    "wall time of one registry refresh (pings + grouped fan-out)",
)
estimator_nodes_estimated = registry.counter(
    "karmada_tpu_estimator_nodes_estimated_total",
    "member nodes summed by node_sum_table dispatches (every in-process "
    "member's nodes, once a dispatch)",
)
estimator_upload_bytes = registry.counter(
    "karmada_tpu_estimator_upload_bytes_total",
    "bytes of node state uploaded to the device-resident node table "
    "(the slices of the members whose generation moved)",
)
estimator_server_requests = registry.counter(
    "karmada_tpu_estimator_server_requests_total",
    "estimator-server RPCs served, by method",
)
solver_requests = registry.counter(
    "karmada_tpu_solver_requests_total",
    "solver-sidecar RPCs served, by method",
)
bus_events = registry.counter(
    "karmada_tpu_bus_events_total",
    "store-bus watch events fanned out to subscribers (dropped = a slow "
    "subscriber's stream was closed for re-list)",
)
bus_subscribers = registry.gauge(
    "karmada_tpu_bus_subscribers",
    "live store-bus watch subscribers",
)
bus_queue_depth = registry.gauge(
    "karmada_tpu_bus_queue_depth",
    "deepest subscriber queue at the last fan-out (backpressure signal)",
)
bus_event_age_seconds = registry.histogram(
    "karmada_tpu_bus_event_age_seconds",
    "time a watch event waited in a subscriber queue before the stream "
    "picked it up (recorded PER EVENT even under frame coalescing, so "
    "batching cannot fake a low queue age)",
)
bus_batch_size = registry.histogram(
    "karmada_tpu_bus_batch_size",
    "items per batched bus message: ops per ApplyBatch RPC served and "
    "events per WatchBatch frame flushed (count histogram — a value of "
    "1 means the channel is effectively unary)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
)
works_rendered = registry.counter(
    "karmada_tpu_controller_works_rendered_total",
    "Work objects created or updated by the binding controller (the "
    "work-render throughput ROADMAP item 3 optimizes)",
)
work_status_events_skipped = registry.counter(
    "karmada_tpu_work_status_events_skipped_total",
    "Work Modified events a consumer of Work specs turned away because the "
    "Work's meta.generation was the one it had already acted on (a status "
    "or condition write: the store moves a Work's generation on every "
    "other write), by consumer: execution (no apply reconcile enqueued), "
    "work-index (entries left as they are)",
)
work_manifest_renders = registry.counter(
    "karmada_tpu_work_manifest_renders_total",
    "template-delta Work manifests rendered (clone of the decoded "
    "WorkloadTemplate + the Work's patch), by consumer: execution (once a "
    "Work generation), work-status (only to recreate an object deleted on "
    "its member), agent (Pull mode)",
)
worker_reconciles = registry.counter(
    "karmada_tpu_worker_reconciles_total",
    "reconciles drained, by worker queue",
)
worker_noop_reconciles = registry.counter(
    "karmada_tpu_worker_noop_reconciles_total",
    "keys whose reconcile finished and changed nothing (returned DONE with "
    "no store write, or turned away by a batch reconciler's own gate), by "
    "worker queue; beside karmada_tpu_worker_reconciles_total it is the "
    "useful-to-attempted ratio of a controller (counted once a drain)",
)
cluster_fanout_keys = registry.counter(
    "karmada_tpu_scheduler_cluster_fanout_keys_total",
    "binding keys the scheduler enqueued in answer to Cluster events: the "
    "bindings a member's change can move (unschedulable, quota-parked, "
    "Duplicated / non-workload, scheduled since the last Cluster event), "
    "never the settled Divided ones (added once an event, by the size of "
    "the set)",
)
worker_queue_depth = registry.gauge(
    "karmada_tpu_worker_queue_depth",
    "keys still queued per worker after its last drain",
)
circuit_state = registry.gauge(
    "karmada_tpu_circuit_state",
    "per-channel circuit-breaker state (0 closed, 1 open, 2 half-open) — "
    "the unified resilience policy of utils.backoff; an open estimator/"
    "solver/bus breaker marks every pass it shadows as degraded",
)
channel_retries = registry.counter(
    "karmada_tpu_channel_retries_total",
    "RPC attempts retried under the unified backoff policy, by channel "
    "(each is one decorrelated-jitter sleep inside one deadline budget)",
)
degraded_passes = registry.counter(
    "karmada_tpu_degraded_passes_total",
    "passes served on a channel's degraded path, by channel: solver = "
    "in-proc fallback solve, estimator = at least one registered cluster "
    "answered UnauthenticReplica (such a pass never arms batch-identity "
    "replay)",
)
unschedulable_total = registry.counter(
    "karmada_tpu_unschedulable_total",
    "bindings transitioning to Scheduled=False, by REASONS-taxonomy "
    "code (QuotaExceeded, NoClusterFit, InsufficientReplicas, ...) — "
    "one increment per (binding, reason, generation) transition; a "
    "parked binding re-enqueued within one generation never "
    "double-counts (utils.reasons.TransitionDedup)",
)
preemptions_total = registry.counter(
    "karmada_tpu_preemptions_total",
    "bindings displaced by the scarcity plane, by REASONS-taxonomy code "
    "(PreemptedByHigherPriority = victim of the batched preemption "
    "kernel, RebalanceTriggered = continuous-descheduler drift "
    "re-placement) — one increment per (binding, reason, generation) "
    "transition via utils.reasons.TransitionDedup, so a twice-displaced "
    "binding re-enqueued within one generation never double-counts",
)
desched_disruption_budget = registry.gauge(
    "karmada_tpu_desched_disruption_budget",
    "the continuous descheduler's per-wave disruption budget "
    "(KARMADA_TPU_DESCHEDULE_MAX_DISRUPTION): the maximum bindings one "
    "drift-rebalance round may stamp RescheduleTriggeredAt on; 0 = tier "
    "disabled (published once per rebalance round beside the per-round "
    "used level)",
)
desched_disruption_used = registry.gauge(
    "karmada_tpu_desched_disruption_used",
    "bindings the LAST drift-rebalance round actually re-placed (always "
    "<= the published budget — the bench asserts the bound exactly)",
)
quota_denied = registry.counter(
    "karmada_tpu_quota_denied_total",
    "bindings newly denied admission by FederatedResourceQuota "
    "enforcement, by namespace (incremented when the QuotaExceeded "
    "condition lands on the binding; a denied binding retries on the "
    "next quota generation, not every pass)",
)
quota_admission_rows = registry.counter(
    "karmada_tpu_quota_admission_rows_total",
    "rows of engine passes under a QuotaSnapshot by what admission made of "
    "them: admitted (in a quota'd namespace, inside its remaining budget), "
    "denied (past it: the row answers 'namespace quota exceeded' and keeps "
    "its previous placement), unquotad (a namespace without a "
    "FederatedResourceQuota); added once a pass",
)
quota_admission_passes = registry.counter(
    "karmada_tpu_quota_admission_passes_total",
    "engine passes under a QuotaSnapshot by the route admission took: "
    "resident (one kernel over the fleet table's row state, the batch "
    "keeps its length), partition (the host derives every row's namespace "
    "and demand and hands the solve the admitted sub-list: a batch with "
    "rows off the fleet table, a tiny batch, more rows than one admission "
    "takes), replayed (the same rows at the same quota generation: the "
    "last verdict stands, nothing is dispatched)",
)
quota_limit = registry.gauge(
    "karmada_tpu_quota_limit",
    "FederatedResourceQuota spec.overall limit by namespace and resource "
    "(canonical integer units; set by the FRQ status controller)",
)
quota_used = registry.gauge(
    "karmada_tpu_quota_used",
    "FederatedResourceQuota status.overall_used by namespace and "
    "resource, recomputed live from bound ResourceBindings",
)
trace_spans_dropped = registry.counter(
    "karmada_tpu_trace_spans_dropped_total",
    "wave-trace spans evicted off the tracer ring (one inc per "
    "overwrite) — nonzero means wave_summary coverage is undercounting; "
    "raise KARMADA_TPU_TRACE_CAPACITY for 1M-tier storms",
)


def _gc_samples(field: str):
    """Reader over the collector watch of utils.tracing — sys.modules-gated:
    a process that never made a tracer has no watch, and a scrape must not
    install one."""
    import sys

    def read() -> dict:
        tracing = sys.modules.get("karmada_tpu.utils.tracing")
        return tracing.gc_watch.samples(field) if tracing is not None else {}

    return read


gc_collections = registry.counter(
    "karmada_tpu_gc_collections_total",
    "collections of the CPython heap, by generation (the program's own "
    "gc.callbacks entry; go_gc_duration_seconds_count in the reference)",
    read=_gc_samples("runs"),
)
gc_pause_seconds = registry.counter(
    "karmada_tpu_gc_pause_seconds_total",
    "seconds the collector held the interpreter, by generation; a full "
    "(generation-2) collection is also a runtime.gc span in the wave trace",
    read=_gc_samples("seconds"),
)
device_bytes = registry.gauge(
    "karmada_tpu_device_bytes",
    "resident device bytes by ledger kind and table bucket (exact "
    "nbytes of the arrays the fleet table / engine hold: slot tables, "
    "packed grid, donated residents, quota cap tensors) — the platform "
    "label says WHOSE memory (cpu = forced-host bytes, never HBM); "
    "published once per engine pass",
)
kernel_memory_bytes = registry.gauge(
    "karmada_tpu_kernel_memory_bytes",
    "per-compiled-kernel XLA memory_analysis footprint by kind (temp = "
    "transient scratch, output, argument) — recorded when prewarm "
    "AOT-compiles a manifest trace, so an operator can budget HBM "
    "before putting a resident grid on real devices",
)


def render_families_table() -> str:
    """The docs/OPERATIONS.md metric-families table, generated from the
    live registry so prose can never drift from the exposition
    (tools/docs_from_bench.py writes it between the metricfamilies
    markers and fails loudly on drift — the env-table pattern)."""
    lines = [
        "| family | type | what it measures |",
        "|---|---|---|",
    ]
    for name, type_, help_ in sorted(registry.families()):
        lines.append(f"| `{name}` | {type_} | {help_} |")
    return "\n".join(lines)


class MetricsServer:
    """Prometheus text exposition over HTTP: every reference binary serves
    /metrics on --metrics-bind-address (cmd/scheduler/app/options/
    options.go:148); this is that endpoint for the TPU-native processes.
    Also answers /healthz (the readiness probe the reference wires via
    healthz.InstallHandler), /debug/traces (the wave-trace ring as
    JSON — utils.tracing.tracer.dump()), /debug/history (the per-wave
    telemetry ring + sliding-window digests — utils.history) and
    /debug/explain (the placement-provenance capture ring —
    utils.explainstore)."""

    def __init__(
        self,
        reg: Registry | None = None,
        address: tuple[str, int] = ("127.0.0.1", 0),
    ):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        import threading

        self.registry = reg or registry
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def do_GET(self):
                if self.path == "/metrics":
                    body = outer.registry.render().encode()
                    ctype = "text/plain; version=0.0.4"
                elif self.path == "/healthz":
                    body = b"ok\n"
                    ctype = "text/plain"
                elif self.path.startswith("/debug/history"):
                    import json
                    from urllib.parse import parse_qs, urlsplit

                    from .history import history_for
                    from .tracing import tracer

                    # query contract: ?window=N paginates to the last N
                    # rows (digests cover the same window), ?wave=N
                    # narrows to one wave, ?digests=0 drops the digest
                    # block. Malformed values answer 400 — `top` must
                    # never mistake a mis-filtered full dump for a page
                    qs = parse_qs(urlsplit(self.path).query)
                    try:
                        raw_window = (qs.get("window") or [None])[0]
                        window = (
                            int(raw_window) if raw_window is not None
                            else None
                        )
                        raw_wave = (qs.get("wave") or [None])[0]
                        wave = (
                            int(raw_wave) if raw_wave is not None else None
                        )
                        with_digests = (qs.get("digests") or ["1"])[0] in (
                            "1", "true", "yes",
                        )
                    except ValueError:
                        body = json.dumps(
                            {"error": f"bad history query {self.path!r}"}
                        ).encode()
                        self.send_response(400)
                        self.send_header(
                            "Content-Type", "application/json"
                        )
                        self.send_header(
                            "Content-Length", str(len(body))
                        )
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    body = json.dumps(
                        history_for(tracer).debug_doc(
                            window=window, wave=wave,
                            with_digests=with_digests, proc=tracer.proc,
                        )
                    ).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/explain"):
                    import json
                    from urllib.parse import parse_qs, urlsplit

                    from .explainstore import store as explain_store
                    from .tracing import tracer

                    # query contract: ?binding=<ns>/<name> answers one
                    # binding's decision chain, ?wave=N pins/narrows to
                    # one wave; no binding = the wave's verdict summary
                    # + worst bindings. Malformed wave answers 400.
                    qs = parse_qs(urlsplit(self.path).query)
                    raw_wave = (qs.get("wave") or [None])[0]
                    try:
                        wave = (
                            int(raw_wave) if raw_wave is not None else None
                        )
                    except ValueError:
                        body = json.dumps(
                            {"error": f"bad wave={raw_wave!r}"}
                        ).encode()
                        self.send_response(400)
                        self.send_header(
                            "Content-Type", "application/json"
                        )
                        self.send_header(
                            "Content-Length", str(len(body))
                        )
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    binding = (qs.get("binding") or [None])[0]
                    body = json.dumps(
                        explain_store().debug_doc(
                            binding=binding, wave=wave, proc=tracer.proc
                        )
                    ).encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/traces"):
                    import json
                    from urllib.parse import parse_qs, urlsplit

                    from .tracing import trace_debug_doc

                    # query contract: ?wave=N restricts to one wave,
                    # ?summary=1 drops the raw span list. Malformed
                    # values answer 400 — the stitcher must never
                    # mistake a mis-filtered full dump for a wave dump
                    qs = parse_qs(urlsplit(self.path).query)
                    wave = None
                    raw_wave = (qs.get("wave") or [None])[0]
                    try:
                        if raw_wave is not None:
                            wave = int(raw_wave)
                        summary = (qs.get("summary") or ["0"])[0] in (
                            "1", "true", "yes",
                        )
                    except ValueError:
                        body = json.dumps(
                            {"error": f"bad wave={raw_wave!r}"}
                        ).encode()
                        self.send_response(400)
                        self.send_header(
                            "Content-Type", "application/json"
                        )
                        self.send_header(
                            "Content-Length", str(len(body))
                        )
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    body = json.dumps(
                        trace_debug_doc(wave, summary=summary)
                    ).encode()
                    ctype = "application/json"
                else:
                    self.send_response(404)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._httpd = ThreadingHTTPServer(address, Handler)
        self.port = self._httpd.server_address[1]
        self._threading = threading
        self._thread = None

    def start(self) -> int:
        self._thread = self._threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self.port

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


def serve_process_metrics(port: Optional[str]) -> Optional[MetricsServer]:
    """THE shared ``--metrics-port`` semantics for the standalone process
    entrypoints (solver sidecar, estimator servers, store bus; the plane
    has its own --metrics-address): flag value wins, an absent flag falls
    back to $KARMADA_TPU_METRICS_PORT, and an empty value means disabled.
    The value is a port (``0`` = ephemeral, loopback bind) or
    ``HOST:PORT`` (``0.0.0.0:9090`` for an off-host scraper — loopback
    stays the DEFAULT so an operator opts in to exposure explicitly).
    Returns the STARTED server (caller prints/exports ``server.port``)
    or None when disabled."""
    import os

    if port is None:
        port = os.environ.get("KARMADA_TPU_METRICS_PORT", "")
    port = str(port).strip()
    if port == "":
        return None
    host = "127.0.0.1"
    if ":" in port:
        host, _, port = port.rpartition(":")
        host = host or "127.0.0.1"
    server = MetricsServer(address=(host, int(port)))
    server.start()
    return server
