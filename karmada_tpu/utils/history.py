"""Per-wave telemetry history: the plane's memory ACROSS waves.

The tracing plane (ISSUE 6 + 10) can explain any single wave; this module
is the third observability layer — history. At every ``end_wave()`` a
sampler captures ONE structured wave row from surfaces that already
exist: the wave's per-phase self seconds from ``wave_summary()``
(stitched across ``KARMADA_TPU_TRACE_PEERS`` when peers are registered),
engine pass stats off the wave's span attributes (rows packed vs
replayed, batched solves, upload/fetch megabytes — the churn-attribution
series the incremental-1M work regresses against), per-channel RPC
counts off the span taxonomy, and compile/queue-depth/device-byte levels
off the metrics registry. Rows live in a lock-disciplined ring
(``KARMADA_TPU_HISTORY_CAP``, default 512 waves; 0 disables sampling
entirely), served as ``/debug/history`` by every ``MetricsServer`` and
aggregated plane-wide by ``karmadactl-tpu top [--watch]``.

Every row field that is a time series is DECLARED in ``HISTORY_SERIES``
with the surface that backs it (``span:<name>`` — a SPAN_NAMES taxonomy
entry — or ``metric:<family>`` — a registered metric family). graftlint
GL009 machine-checks those references and the generated wave-row schema
table in docs/OPERATIONS.md is rendered from the same registry
(``tools/docs_from_bench.py check_history_schema`` fails every doc regen
on drift), so a series can never silently detach from the surface it
claims to read.

Sliding-window digests: the ring IS the window — ``digests(window=N)``
computes p50/p95/p99 per numeric series over the last N rows on demand
(bucket-free: exact quantiles over at most ``cap`` scalars). The
slow-wave flight recorder attaches the breaching wave's row plus the
recent-window digests to its record (``breach_context``), so
``karmadactl-tpu trace analyze`` renders breach-vs-recent-baseline in
one view, offline.

Thread-safety: a row is built COMPLETELY before it enters the ring, and
ring append/eviction/read all run under one lock — a reader can never
observe a torn row, and evictions are counted, never silent (the
tracer-ring discipline). Sampling is telemetry: any failure is logged
and the wave closes normally.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Optional

log = logging.getLogger("karmada_tpu.history")

#: env knobs (registered in utils.flags ENV_FLAGS)
HISTORY_CAP_ENV = "KARMADA_TPU_HISTORY_CAP"
HISTORY_STITCH_ENV = "KARMADA_TPU_HISTORY_STITCH"

_DEFAULT_CAP = 512


@dataclass(frozen=True)
class HistorySeries:
    """One declared wave-row series: ``source`` names the surface the
    value is derived from — ``span:<name>`` (a SPAN_NAMES taxonomy entry;
    the value sums that span family's durations, counts or attributes
    within the wave) or ``metric:<family>`` (a registered metric family;
    the value is a level or per-wave delta of its samples). graftlint
    GL009 validates every reference."""

    name: str
    #: "gauge" = a per-wave level, "counter" = a per-wave count/delta
    kind: str
    source: str
    description: str


#: THE wave-row series registry: every time-series field of a history row
#: must be declared here (identity fields — wave, trace_id, at, proc,
#: stitched — are row keys, not series). The docs wave-row schema table
#: and graftlint GL009 both key on this dict.
HISTORY_SERIES: dict[str, HistorySeries] = {
    s.name: s
    for s in (
        HistorySeries(
            "wall_s", "gauge", "span:settle",
            "wave wall seconds: summed root (settle) span durations — "
            "wave_summary total_s",
        ),
        HistorySeries(
            "coverage", "gauge", "span:settle",
            "fraction of the wave wall attributed to named spans",
        ),
        HistorySeries(
            "coverage_degraded", "gauge", "span:settle",
            "1 when ring evictions dropped spans of this wave (coverage "
            "undercounts; raise KARMADA_TPU_TRACE_CAPACITY)",
        ),
        HistorySeries(
            "spans", "counter", "span:settle",
            "spans recorded for the wave (stitched: across processes)",
        ),
        HistorySeries(
            "dropped", "counter",
            "metric:karmada_tpu_trace_spans_dropped_total",
            "spans of this wave evicted off the tracer ring",
        ),
        HistorySeries(
            "bindings", "counter", "span:scheduler.pass",
            "bindings scheduled: summed `bindings` attrs over the wave's "
            "scheduler.pass spans",
        ),
        HistorySeries(
            "bindings_s", "gauge", "span:scheduler.pass",
            "bindings / wall_s — the wave's scheduling throughput",
        ),
        HistorySeries(
            "solve_batches", "counter", "span:scheduler.solve",
            "batched fleet solves dispatched (scheduler.solve spans + "
            "host-path chunk spans)",
        ),
        HistorySeries(
            "rows_packed", "counter", "span:scheduler.solve",
            "fleet-table rows (re)packed this wave — the churn-"
            "attribution series (summed rows_packed attrs)",
        ),
        HistorySeries(
            "rows_replayed", "counter", "span:scheduler.solve",
            "fleet-table rows served without re-packing (equal content "
            "or batch-identity replay)",
        ),
        HistorySeries(
            "dirty_rows", "counter", "span:scheduler.solve",
            "rows the wave's delta passes dispatched as dirty (summed "
            "dirty_rows attrs; 0 = every pass was full or pure replay)",
        ),
        HistorySeries(
            "upload_mb", "counter", "span:kernel.host",
            "host->device megabytes shipped (state scatter/upload + row "
            "indices; summed upload_mb attrs)",
        ),
        HistorySeries(
            "fetch_mb", "counter", "span:kernel.fetch",
            "device->host megabytes fetched (summed fetch_mb attrs)",
        ),
        HistorySeries(
            "device_s", "gauge", "span:kernel.device",
            "fenced on-device execute seconds within the wave",
        ),
        HistorySeries(
            "compile_s", "gauge", "span:kernel.device",
            "seconds of compile-flagged spans (fresh XLA traces)",
        ),
        HistorySeries(
            "kernel_compiles", "counter",
            "metric:karmada_tpu_kernel_compiles_total",
            "fresh XLA trace signatures dispatched since the previous "
            "sampled wave",
        ),
        HistorySeries(
            "rpc_estimator", "counter", "span:estimator.rpc",
            "estimator-channel client RPCs issued during the wave",
        ),
        HistorySeries(
            "rpc_solver", "counter", "span:solver.rpc",
            "solver-channel client RPCs issued during the wave",
        ),
        HistorySeries(
            "rpc_bus", "counter", "span:bus.rpc",
            "bus-channel client RPC attempts issued during the wave",
        ),
        HistorySeries(
            "queue_depth", "gauge",
            "metric:karmada_tpu_worker_queue_depth",
            "deepest per-worker queue at wave close (work left behind)",
        ),
        HistorySeries(
            "device_bytes", "gauge", "metric:karmada_tpu_device_bytes",
            "resident device bytes at wave close, summed over every "
            "{kind,bucket} ledger sample",
        ),
        HistorySeries(
            "quota_denied", "counter",
            "metric:karmada_tpu_quota_denied_total",
            "bindings newly denied by quota admission since the previous "
            "sampled wave",
        ),
        HistorySeries(
            "unschedulable", "counter",
            "metric:karmada_tpu_unschedulable_total",
            "bindings transitioning to Scheduled=False (any REASONS "
            "code) since the previous sampled wave — the `top` "
            "unschedulable/denied column",
        ),
        HistorySeries(
            "preemptions", "counter",
            "metric:karmada_tpu_preemptions_total",
            "bindings displaced by the scarcity plane since the "
            "previous sampled wave (victims of the preemption kernel + "
            "descheduler drift triggers) — the `top` preempt column",
        ),
        HistorySeries(
            "disruption_budget", "gauge",
            "metric:karmada_tpu_desched_disruption_budget",
            "the continuous descheduler's per-round trigger cap at wave "
            "close (0 = tier disabled)",
        ),
        HistorySeries(
            "disruption_used", "gauge",
            "metric:karmada_tpu_desched_disruption_used",
            "bindings the last drift-rebalance round re-placed (always "
            "<= disruption_budget)",
        ),
        HistorySeries(
            "phases", "gauge", "span:settle",
            "per-phase SELF seconds dict — keys are SPAN_NAMES entries "
            "(digested as phases.<name> sub-series)",
        ),
        HistorySeries(
            "device_bytes_kinds", "gauge",
            "metric:karmada_tpu_device_bytes",
            "resident device bytes by ledger kind dict (slot tables, "
            "packed grid, donated residents, quota caps, ...)",
        ),
    )
}

#: row keys that are identity/context, not series (rendered first in the
#: schema table)
ROW_IDENTITY_FIELDS: tuple = (
    ("wave", "the closed wave id the row describes"),
    ("trace_id", "the wave's plane-unique trace id"),
    ("at", "unix time the row was sampled (wave close)"),
    ("proc", "the sampling process's name (plane/solver/estimator/bus)"),
    ("stitched", "true when the row's phases came from the cross-process "
                 "stitched summary with more than one process actually "
                 "contributing (peers registered AND reachable)"),
)


def _env_cap() -> int:
    raw = os.environ.get(HISTORY_CAP_ENV, "").strip()
    if not raw:
        return _DEFAULT_CAP
    try:
        return max(int(raw), 0)
    except ValueError:
        log.warning("bad %s=%r; using %d", HISTORY_CAP_ENV, raw,
                    _DEFAULT_CAP)
        return _DEFAULT_CAP


def _stitch_enabled() -> bool:
    """Stitched sampling (default on): when peers are registered, each
    wave row's phases come from the cross-process stitched summary —
    one narrowed ``/debug/traces?wave=N`` fetch per peer per wave close.
    ``KARMADA_TPU_HISTORY_STITCH=0`` keeps sampling local-only."""
    return os.environ.get(HISTORY_STITCH_ENV, "1").strip() not in (
        "0", "false", "no",
    )


def _quantile(sorted_vals: list, q: float) -> float:
    """Exact linear-interpolation quantile over a sorted list."""
    n = len(sorted_vals)
    if n == 0:
        return 0.0
    if n == 1:
        return float(sorted_vals[0])
    pos = q * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return float(sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac)


class WaveHistory:
    """Ring-capped per-wave telemetry store. One instance rides each
    ``WaveTracer`` (``tracer.history``); the process-wide tracer's
    instance is what ``/debug/history`` and ``karmadactl-tpu top``
    read."""

    def __init__(self, cap: Optional[int] = None):
        self.cap = _env_cap() if cap is None else cap
        self._lock = threading.Lock()
        self._rows: deque = deque()
        self._evicted = 0
        self._sampled = 0
        # cumulative metric totals at the previous sample — counter
        # series sourced from metric families delta against these
        self._last_counters: dict[str, float] = {}

    @property
    def enabled(self) -> bool:
        return self.cap > 0

    # -- sampling ----------------------------------------------------------

    def sample(self, tracer_obj, wave: int) -> Optional[dict]:
        """The ``end_wave()`` hook: build one wave row and append it.
        Telemetry discipline: any failure logs and returns None — the
        wave close must never be aborted by its own history."""
        if not self.enabled:
            return None
        try:
            row = self._build_row(tracer_obj, wave)
        except Exception as exc:  # noqa: BLE001 — telemetry never kills
            # a settle; a broken sampler loses the row, not the wave
            log.warning("history sample of wave %s failed: %s", wave, exc)
            return None
        with self._lock:
            self._rows.append(row)
            self._sampled += 1
            while len(self._rows) > self.cap:
                self._rows.popleft()
                self._evicted += 1
        return row

    def _build_row(self, tr, wave: int) -> dict:
        from .metrics import (
            desched_disruption_budget,
            desched_disruption_used,
            device_bytes as device_bytes_gauge,
            kernel_compiles,
            preemptions_total,
            quota_denied,
            trace_spans_dropped,
            unschedulable_total,
            worker_queue_depth,
        )

        summary = None
        stitched = False
        from .tracing import peers

        if peers() and _stitch_enabled():
            try:
                # falls back to the LOCAL summary internally when the
                # stitch comes back empty — either way the returned
                # summary is usable, never recomputed here. The row's
                # stitched flag demands actual cross-process content
                # (>1 contributing process), not merely the stitched
                # SHAPE: peers all down/skipped must read local-only.
                summary = tr.wave_summary(wave, stitched=True)
                stitched = bool(summary.get("stitched")) and (
                    len(summary.get("procs", [])) > 1
                )
            except Exception as exc:  # noqa: BLE001 — peers unreachable:
                # the local summary still makes an honest row
                log.debug("stitched history sample failed: %s", exc)
        if summary is None:
            summary = tr.wave_summary(wave)

        # span-attribute aggregation over the LOCAL ring (engine pass
        # stats ride local span attrs; remote handler spans carry none)
        packed = replayed = bindings = dirty = 0
        upload_mb = fetch_mb = 0.0
        for sp in tr.spans_for(wave):
            if sp.name == "scheduler.pass":
                bindings += int(sp.attrs.get("bindings", 0) or 0)
            elif sp.name == "scheduler.solve":
                packed += int(sp.attrs.get("rows_packed", 0) or 0)
                replayed += int(sp.attrs.get("rows_replayed", 0) or 0)
                dirty += int(sp.attrs.get("dirty_rows", 0) or 0)
            elif sp.name == "kernel.host":
                upload_mb += float(sp.attrs.get("upload_mb", 0.0) or 0.0)
            elif sp.name == "kernel.fetch":
                fetch_mb += float(sp.attrs.get("fetch_mb", 0.0) or 0.0)

        counts = summary.get("span_counts", {})
        wall = float(summary.get("total_s", 0.0))

        def _counter_delta(name: str, metric) -> float:
            # the FIRST observation seeds the baseline and answers 0:
            # process-lifetime totals accrued before sampling started
            # (prewarm compiles, pre-clear() counts) must not land on
            # one row and skew every digest it feeds
            total = sum(metric.samples().values())
            with self._lock:
                prev = self._last_counters.get(name)
                self._last_counters[name] = total
            return max(total - prev, 0.0) if prev is not None else 0.0

        dev_samples = device_bytes_gauge.samples()
        by_kind: dict[str, float] = {}
        for key, v in dev_samples.items():
            kind = dict(key).get("kind", "?")
            by_kind[kind] = by_kind.get(kind, 0.0) + v
        depth_samples = worker_queue_depth.samples()

        row = {
            "wave": wave,
            "trace_id": summary.get("trace_id", ""),
            "at": time.time(),
            "proc": tr.proc,
            "stitched": stitched,
            "wall_s": round(wall, 6),
            "coverage": summary.get("coverage", 0.0),
            "coverage_degraded": bool(summary.get("coverage_degraded")),
            "spans": int(summary.get("spans", 0)),
            "dropped": int(summary.get("dropped", 0) or 0),
            "bindings": bindings,
            "bindings_s": round(bindings / wall, 1) if wall else 0.0,
            "solve_batches": int(
                counts.get("scheduler.solve", 0)
                + counts.get("scheduler.host", 0)
            ),
            "rows_packed": packed,
            "rows_replayed": replayed,
            "dirty_rows": dirty,
            "upload_mb": round(upload_mb, 6),
            "fetch_mb": round(fetch_mb, 6),
            "device_s": float(summary.get("device_s", 0.0)),
            "compile_s": float(summary.get("compile_s", 0.0)),
            "kernel_compiles": int(
                _counter_delta("kernel_compiles", kernel_compiles)
            ),
            "rpc_estimator": int(counts.get("estimator.rpc", 0)),
            "rpc_solver": int(counts.get("solver.rpc", 0)),
            "rpc_bus": int(counts.get("bus.rpc", 0)),
            "queue_depth": int(max(depth_samples.values(), default=0)),
            "device_bytes": int(sum(dev_samples.values())),
            "device_bytes_kinds": {
                k: int(v) for k, v in sorted(by_kind.items())
            },
            "quota_denied": int(
                _counter_delta("quota_denied", quota_denied)
            ),
            "unschedulable": int(
                _counter_delta("unschedulable", unschedulable_total)
            ),
            "preemptions": int(
                _counter_delta("preemptions", preemptions_total)
            ),
            "disruption_budget": int(
                sum(desched_disruption_budget.samples().values())
            ),
            "disruption_used": int(
                sum(desched_disruption_used.samples().values())
            ),
            "phases": dict(summary.get("phases", {})),
        }
        # keep the dropped counter's cumulative bookkeeping moving even
        # though the row carries the per-wave figure from the summary
        _counter_delta("trace_spans_dropped", trace_spans_dropped)
        return row

    # -- reads -------------------------------------------------------------

    def rows(
        self, window: Optional[int] = None, wave: Optional[int] = None
    ) -> list[dict]:
        """Snapshot of the last ``window`` rows (None = all), newest
        last; ``wave`` narrows to one wave id."""
        with self._lock:
            rows = list(self._rows)
        if wave is not None:
            rows = [r for r in rows if r.get("wave") == wave]
        if window is not None and window >= 0:
            rows = rows[-window:] if window else []
        return [dict(r) for r in rows]

    def row_for(self, wave: int) -> Optional[dict]:
        with self._lock:
            for r in reversed(self._rows):
                if r.get("wave") == wave:
                    return dict(r)
        return None

    @property
    def evicted(self) -> int:
        with self._lock:
            return self._evicted

    @property
    def sampled(self) -> int:
        with self._lock:
            return self._sampled

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()
            self._evicted = 0
            self._sampled = 0
            self._last_counters.clear()

    def digests(
        self,
        window: Optional[int] = None,
        *,
        rows: Optional[list] = None,
    ) -> dict:
        """p50/p95/p99 per numeric series over the last ``window`` rows
        (the ring is the sliding window — exact quantiles over at most
        ``cap`` scalars, no buckets). ``phases`` digests as
        ``phases.<name>`` sub-series. ``rows`` overrides the window (the
        breach context digests the window EXCLUDING the breaching
        row)."""
        if rows is None:
            rows = self.rows(window)
        values: dict[str, list] = {}
        for r in rows:
            for name, spec in HISTORY_SERIES.items():
                v = r.get(name)
                if isinstance(v, bool):
                    v = int(v)
                if isinstance(v, (int, float)):
                    values.setdefault(name, []).append(float(v))
            for ph, v in (r.get("phases") or {}).items():
                values.setdefault(f"phases.{ph}", []).append(float(v))
        out: dict[str, dict] = {}
        for name, vals in sorted(values.items()):
            vals.sort()
            out[name] = {
                "n": len(vals),
                "p50": round(_quantile(vals, 0.50), 6),
                "p95": round(_quantile(vals, 0.95), 6),
                "p99": round(_quantile(vals, 0.99), 6),
            }
        return {"window": len(rows), "series": out}

    # -- documents ---------------------------------------------------------

    def debug_doc(
        self,
        window: Optional[int] = None,
        wave: Optional[int] = None,
        *,
        with_digests: bool = True,
        proc: str = "",
    ) -> dict:
        """THE ``/debug/history`` document (one builder so the HTTP
        endpoint, the CLI and the bench can never drift on shape).
        ``?window=N`` paginates to the last N rows; digests cover the
        same window."""
        from .tracing import peers

        rows = self.rows(window=window, wave=wave)
        doc = {
            "proc": proc,
            "cap": self.cap,
            "sampled": self.sampled,
            "evicted": self.evicted,
            # peer endpoints ride along so `top` pointed at ONE process
            # can discover the rest of the plane
            "peers": peers(),
            "rows": rows,
        }
        if with_digests:
            doc["digests"] = self.digests(rows=rows)
        return doc

    def breach_context(self, wave: int) -> Optional[dict]:
        """The flight recorder's history attachment: the breaching
        wave's row plus digests over the recent window EXCLUDING it —
        breach-vs-recent-baseline in one object."""
        row = self.row_for(wave)
        if row is None:
            return None
        recent = [r for r in self.rows() if r.get("wave") != wave]
        return {
            "row": row,
            "recent": self.digests(rows=recent),
        }


def history_for(tracer_obj=None) -> WaveHistory:
    """The history ring of ``tracer_obj`` (default: the process-wide
    tracer) — the instance ``/debug/history`` serves."""
    if tracer_obj is None:
        from .tracing import tracer as tracer_obj
    return tracer_obj.history


# --------------------------------------------------------------------------
# rendering (karmadactl-tpu top, trace analyze, the bench table)
# --------------------------------------------------------------------------


def render_history_table(rows: list[dict], proc: str = "") -> str:
    """The per-wave table ``karmadactl-tpu top`` and the observability
    bench print (the JSON row stays the machine surface)."""
    head = (
        f"{'proc':<10} {'wave':>5} {'wall_s':>8} {'cover':>6} "
        f"{'bind/s':>8} {'packed':>7} {'replay':>7} {'dirty':>7} "
        f"{'cmpl':>4} "
        f"{'up/fetch MB':>12} {'rpc e/s/b':>11} {'devMB':>8} "
        f"{'uns/den':>8} {'pre':>4} {'dis u/b':>8} {'q':>4}"
    )
    lines = [head]
    for r in rows:
        cov = f"{r.get('coverage', 0.0) * 100:.1f}"
        if r.get("coverage_degraded"):
            cov += "!"
        lines.append(
            f"{(r.get('proc') or proc):<10} {r.get('wave', 0):>5} "
            f"{r.get('wall_s', 0.0):>8.3f} {cov:>6} "
            f"{r.get('bindings_s', 0.0):>8.1f} "
            f"{r.get('rows_packed', 0):>7} {r.get('rows_replayed', 0):>7} "
            f"{r.get('dirty_rows', 0):>7} "
            f"{r.get('kernel_compiles', 0):>4} "
            f"{r.get('upload_mb', 0.0):>5.1f}/{r.get('fetch_mb', 0.0):<6.1f} "
            f"{r.get('rpc_estimator', 0)}/{r.get('rpc_solver', 0)}"
            f"/{r.get('rpc_bus', 0):<5} "
            f"{r.get('device_bytes', 0) / 1e6:>8.2f} "
            f"{r.get('unschedulable', 0)}/{r.get('quota_denied', 0):<4} "
            f"{r.get('preemptions', 0):>4} "
            f"{r.get('disruption_used', 0)}/{r.get('disruption_budget', 0):<4} "
            f"{r.get('queue_depth', 0):>4}"
        )
    return "\n".join(lines)


#: the breach table's headline series (phases are appended dynamically)
_BREACH_SERIES = (
    "wall_s", "bindings_s", "coverage", "kernel_compiles", "upload_mb",
    "fetch_mb", "device_bytes", "rpc_bus", "rpc_estimator", "rpc_solver",
)


def render_breach_table(ctx: dict) -> str:
    """Breach-vs-recent-baseline: the breaching wave's row against the
    recent window's p50/p95 — what ``trace analyze`` appends under the
    attribution table when the flight record carries history context."""
    row = ctx.get("row") or {}
    recent = (ctx.get("recent") or {}).get("series", {})
    window = (ctx.get("recent") or {}).get("window", 0)
    lines = [
        f"history: wave {row.get('wave')} vs last {window} wave(s)",
        f"{'series':<28} {'breach':>12} {'p50':>12} {'p95':>12}",
    ]
    phases = sorted(
        (row.get("phases") or {}).items(), key=lambda kv: -kv[1]
    )[:5]
    names = list(_BREACH_SERIES) + [f"phases.{k}" for k, _ in phases]
    for name in names:
        if name.startswith("phases."):
            val = (row.get("phases") or {}).get(name[len("phases."):], 0.0)
        else:
            val = row.get(name, 0.0)
        if isinstance(val, bool):
            val = int(val)
        if not isinstance(val, (int, float)):
            continue
        d = recent.get(name, {})
        lines.append(
            f"{name:<28} {val:>12.3f} {d.get('p50', 0.0):>12.3f} "
            f"{d.get('p95', 0.0):>12.3f}"
        )
    return "\n".join(lines)


def render_history_schema_table() -> str:
    """The docs/OPERATIONS.md wave-row schema table, generated from
    ``ROW_IDENTITY_FIELDS`` + ``HISTORY_SERIES`` so prose can never drift
    from the sampler (tools/docs_from_bench.py writes it between the
    historyschema markers and fails loudly on drift — the env-table
    pattern; graftlint GL009 keeps the ``source`` references honest)."""
    lines = [
        "| field | kind | source | what it carries |",
        "|---|---|---|---|",
    ]
    for name, desc in ROW_IDENTITY_FIELDS:
        lines.append(f"| `{name}` | identity | — | {desc} |")
    for name in sorted(HISTORY_SERIES):
        s = HISTORY_SERIES[name]
        lines.append(
            f"| `{name}` | {s.kind} | `{s.source}` | {s.description} |"
        )
    return "\n".join(lines)
