"""Wave-scoped span tracing, event recording.

Ref: the EventRecorder pattern (scheduler.go:921-967 — events recorded on
both binding and template).

The wave tracer (ISSUE 6 tentpole): a monotonic WAVE id is stamped when
new work enters the plane (the detector's template events, or any settle
that finds work queued), and
every instrumented region — controller drains, scheduler passes, fleet
kernel phases, estimator refreshes — records a ``Span`` carrying that
wave id plus a parent span id, so one storm wave reconstructs as a single
tree attributing pack/solve/dispatch/render/status time. Spans live in a
bounded ring, are exported as JSON by ``MetricsServer``'s
``/debug/traces`` endpoint and ``karmadactl-tpu trace dump``, and are
summarized per-phase by ``wave_summary`` (the bench observability tier's
record format).

Cross-process propagation (ISSUE 10 tentpole): every wave mints a
plane-unique ``trace_id``; the three transport seams (estimator, solver,
bus) stamp ``(wave, trace_id, client span id, caller process)`` into gRPC
metadata on each RPC, and the serving process records its handler spans
(``estimator.serve``, ``solver.solve``, ``bus.apply``...) under the
CALLER's wave/trace with the caller's span id as ``remote_parent`` — so a
storm wave's trace no longer dies at a process boundary. The stitcher
(``stitch_dumps`` / ``karmadactl-tpu trace dump --stitch``) pulls
``/debug/traces`` from every registered peer's metrics port, merges by
``(trace_id, wave)``, re-parents each remote root under its originating
client span, and computes per-process and per-channel self-time columns —
``client span − remote root`` per RPC is the network/serialization time
no single-process view can produce.

The slow-wave flight recorder rides ``end_wave()``: armed by
``KARMADA_TPU_TRACE_SLO_SECONDS``, a closing wave whose wall exceeds the
SLO — or during which a breaker transition, degraded pass or QuotaExceeded
denial fired — persists the full stitched trace + a metrics-registry delta
+ the fired fault-injection log as one JSONL record under
``KARMADA_TPU_FLIGHT_DIR`` (ring-capped on disk);
``karmadactl-tpu trace analyze`` re-renders the attribution offline.

Thread-safety: the completed-span ring, wave bookkeeping and summaries
mutate/read under one lock; the OPEN-span parent chain is thread-local
(each thread nests its own spans — a span never migrates threads), and an
*ambient* thread-local context carries the wave/trace/parent triple onto
executor threads (fan-out pools) and into server handlers.

The collector from inside (ISSUE 25): one ``gc.callbacks`` entry, installed
beside the process-wide tracer, counts every collection and its pause by
generation (``karmada_tpu_gc_*``) and records each FULL collection as a
``runtime.gc`` span under whatever span its thread had open — so a ~2 s
generation-2 pause is a child of the drain it interrupted, not that
drain's self time. The callback can fire at any allocation, under any lock
its thread holds (this tracer's own included), so it takes none: it keeps
plain numbers and parks the span, and the next ring access files it.
"""

from __future__ import annotations

import gc
import itertools
import json
import logging
import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional

log = logging.getLogger("karmada_tpu.trace")

#: env knobs (registered in utils.flags ENV_FLAGS)
TRACE_CAPACITY_ENV = "KARMADA_TPU_TRACE_CAPACITY"
TRACE_SLO_ENV = "KARMADA_TPU_TRACE_SLO_SECONDS"
FLIGHT_DIR_ENV = "KARMADA_TPU_FLIGHT_DIR"
FLIGHT_CAP_ENV = "KARMADA_TPU_FLIGHT_CAP"
TRACE_PEERS_ENV = "KARMADA_TPU_TRACE_PEERS"

# holds the busiest traced benchmark window more than twice over (the
# node-churn cell: 15 spans a wave, 8,778 a window); ~425 bytes a span
_DEFAULT_CAPACITY = 32768
_DEFAULT_FLIGHT_CAP = 64


# --------------------------------------------------------------------------
# span-name registry (graftlint GL008 + the docs span-taxonomy table)
# --------------------------------------------------------------------------

#: THE span taxonomy: every span name recorded anywhere in the import
#: graph must appear here (graftlint GL008 enforces it — the stitcher's
#: channel attribution and the generated docs table key on these names).
#: A ``*`` suffix registers a dynamic family (``controller.<worker>``).
SPAN_NAMES: dict[str, str] = {
    "settle": "one run_until_settled drain — the wave's root span",
    "controller.*": "one contiguous drain of one controller worker",
    "scheduler.pass": "one engine pass over a queued binding batch",
    "scheduler.schedule": (
        "one TensorScheduler.schedule call, entry to return: the root of "
        "an engine wave (rows; path = identity, the armed batch came "
        "again; delta, a minority of its positions moved and the fleet "
        "table replayed the rest; full, the fleet table dispatched every "
        "row; host, no fleet pass)"
    ),
    "scheduler.identity": (
        "the one diff of a batch against the armed one (ResidentBatch."
        "diff): the id() sweep, the compare, the dirty keys, the majority "
        "rule (rows / hit / moved attrs); at most one a pass, under "
        "scheduler.pack where generation and mask_token both moved"
    ),
    "scheduler.pack": (
        "host prologue of a pass: placement compile + spread selection + "
        "eligibility partition (where a minority moved: the moved "
        "positions' compile and eligibility check); rows = positions "
        "visited, kept = positions the diff against the armed batch "
        "spared it"
    ),
    "scheduler.compile": (
        "under scheduler.pack: the compiled-placement look-up of every "
        "position visited (a diffed batch: the armed batch's distinct "
        "placements compiled anew and the take that lists them by "
        "position, or under a standing mask_token their look-ups and the "
        "armed list copied; rows / placements attrs)"
    ),
    "scheduler.spread": (
        "under scheduler.pack: which rows are spread-constrained and who "
        "selects for them (rows / on_device attrs); a host selection is "
        "its scheduler.select child"
    ),
    "scheduler.eligible": (
        "under scheduler.pack: the fleet-eligibility partition of the "
        "batch (a diffed batch: the moved positions' look-up and predicate; "
        "rows = positions visited / fleet_rows attrs; where rows leave "
        "for the host path, wide_rows = those past the replica bound)"
    ),
    "scheduler.handoff": (
        "from scheduler.pack's end to the fleet table's door: the fleet "
        "rows' lists, a table rebuild, the selected rows' positions"
    ),
    "scheduler.rearm": (
        "from the fleet table's answer to the engine's: arming the "
        "table's record of the batch (with the ids of the pass's diff; a "
        "walk no diff came before sweeps here), or the merge with the host "
        "path's rows (scheduler.host its child; rows / host_rows attrs)"
    ),
    "scheduler.select": (
        "only when the batch holds spread-constrained rows: the host's "
        "share of the Select stage. Under scheduler.solve: the dispatch of "
        "the fleet table's select kernel (device = rows it selected); "
        "under scheduler.spread: SelectClusters on the host for the rows "
        "the kernel does not take (rows / device / hits / computed / "
        "fit_errors / moved attrs)"
    ),
    "scheduler.terms": (
        "only when the pass holds multi-term (ordered clusterAffinities) "
        "rows, under scheduler.solve: the host's share of the fleet "
        "table's term kernel, its row vector and dispatch (rows / "
        "fallback / unfit / evicted_rows attrs)"
    ),
    "scheduler.quota": (
        "only in a pass under an active QuotaSnapshot: the admission "
        "stretch. Under scheduler.solve where the whole batch rides the "
        "fleet table (the dispatch of its admission kernel over the row "
        "state, or the replay of the last verdict: host_rows = 0); under "
        "scheduler.schedule where the engine partitions the batch on the "
        "host (host_rows = the batch: the host derives every row's "
        "namespace and demand). rows / quota_rows / denied / host_rows / "
        "dispatched / generation attrs"
    ),
    "scheduler.host": (
        "host-path (non-fleet) scheduling of a batch (rows / replicas = "
        "their sum / prev_max = the most previous sites of a row / chunks "
        "attrs); each chunk's stages are its children"
    ),
    "scheduler.host.pack": (
        "under scheduler.host: one chunk's Filter stage, the dense (B x C) "
        "inputs packed on the host (rows attr)"
    ),
    "scheduler.host.estimate": (
        "under scheduler.host: one chunk's Score stage, the availability "
        "estimate (rows attr)"
    ),
    "scheduler.host.select": (
        "under scheduler.host: one chunk's Select stage, the spread "
        "selection narrowing its candidates (rows attr)"
    ),
    "scheduler.host.assign": (
        "under scheduler.host: one chunk's AssignReplicas stage, the "
        "division (the dense divide_replicas kernel, or the numpy divider "
        "for a small chunk) and its fetch (rows attr)"
    ),
    "scheduler.host.unpack": (
        "under scheduler.host: one chunk's answers built from the "
        "division (rows attr)"
    ),
    "scheduler.solve": (
        "one fleet-table solve pass (host_rows = rows of the batch that "
        "left it for the host path; rows_visited = positions of the batch "
        "its upsert phase looked at: 0 when the same lists come again, the "
        "positions holding another object when a swapped batch is diffed, "
        "every position when it is walked; rows_packed of them rewrote "
        "their row state; derived = kept where the pass read what the last "
        "one derived from the batch's row state, built where it derived it "
        "anew: another row vector, or a row packed since; wide_rows = "
        "rows of the pass in the wide form: more previous sites than a "
        "row's columns hold, in a slot of the wide table, or Divided past "
        "a one-byte cell; cell_bytes = the dense resident's cell width, 1 "
        "or 2)"
    ),
    "scheduler.explain": (
        "armed-only provenance capture of a pass: per-stage mask "
        "composition + the batched explain dispatch (ISSUE 13)"
    ),
    "scheduler.preempt": (
        "armed-only preemption round of a pass: plane-wide victim "
        "selection + the boosted same-pass re-solve (ISSUE 14)"
    ),
    "kernel.host": (
        "one host stretch of a fleet pass, at its true interval: "
        "phase=upsert|sync|prep before the dispatch, post after the fetch "
        "(phase=upsert carries rows_visited / rows_packed; phase=sync "
        "quota_profiles / quota_cap_rows where the static-assignment cap "
        "kernel was dispatched for the profile table; phase=prep derived "
        "= kept|built, wide_rows and cell_bytes, as scheduler.solve has "
        "them)"
    ),
    "kernel.dispatch": (
        "kernel dispatch window (sync backends execute inside it; "
        "compile=true on a fresh-trace pass)"
    ),
    "kernel.device": (
        "fenced on-device execute window (compile=true when the pass "
        "minted a fresh XLA trace)"
    ),
    "kernel.fetch": (
        "post-device wire transfer + decode + entry folds (fetch_mb, "
        "changed_rows; delta_rows = changed rows whose answer the "
        "cell-delta wire carried, delta_cells = the cells it carried, "
        "entry_rows = changed rows phase B brought home: delta_rows + "
        "entry_rows = changed_rows but where a pass without the delta wire "
        "changed only rows that lost every placement, cleared from the "
        "meta alone)"
    ),
    "kernel.entries": (
        "under kernel.fetch, at its true interval: one phase-B stretch of "
        "a fleet pass, the entry wire (_fleet_entries): an exact dispatch, "
        "fetch and fold (route=overflow for rows past 62 changed cells, "
        "exact for every changed row), a speculative dispatch's fetch and "
        "fold (route=speculative) or an unused speculation's drain "
        "(route=drained, rows 0); rows / entries / fetch_mb attrs"
    ),
    "kernel.bits": (
        "the lazy feasibility-bitset pass of a batch (_fleet_bits), when "
        "the first Duplicated or zero-replica result is read: dispatch + "
        "fence + fetch (rows / fetch_mb / dispatch_s / device_s attrs)"
    ),
    "estimator.refresh": (
        "one estimator-registry refresh: generation pings + grouped "
        "profile fan-out"
    ),
    "estimator.sync": (
        "under estimator.refresh: which in-process members moved, their "
        "node arrays stacked and uploaded to the device-resident node "
        "table (members / nodes / upload_mb attrs)"
    ),
    "estimator.dispatch": (
        "under estimator.refresh: the one node_sum_table dispatch that "
        "answers every in-process member"
    ),
    "estimator.fold": (
        "the fleet table's min-merge of the estimators' [P, C] answers "
        "into its resident profile table (profiles / clusters attrs)"
    ),
    "estimator.rpc": (
        "client side of one estimator-channel RPC (remote=true; "
        "peer/method attrs)"
    ),
    "estimator.serve": (
        "server side of one estimator RPC, recorded in the estimator "
        "process under the CALLER's wave"
    ),
    "solver.rpc": "client side of one solver-sidecar RPC (remote=true)",
    "solver.solve": (
        "server side of ScoreAndAssign, recorded in the sidecar under "
        "the caller's wave"
    ),
    "solver.sync": (
        "server side of SyncClusters, recorded in the sidecar under the "
        "caller's wave"
    ),
    "bus.rpc": (
        "client side of one store-bus write-through RPC attempt (batched "
        "calls carry a batch=N attribute — the channel table's "
        "events-per-message column)"
    ),
    "bus.apply": (
        "server side of one bus Apply, recorded in the bus process under "
        "the caller's wave"
    ),
    "bus.apply_batch": (
        "server side of one bus ApplyBatch (ops=N write set committed as "
        "one batched store sweep)"
    ),
    "bus.delete": "server side of one bus Delete",
    "bus.watch": (
        "server side of one Watch replay (list-then-watch initial sync), "
        "up to the bookmark"
    ),
    "channel.breaker": (
        "a circuit-breaker state transition (zero-duration marker span)"
    ),
    "runtime.gc": (
        "one FULL (generation-2) collection of the CPython heap, at its "
        "true interval under the span its thread had open (generation / "
        "collected attrs); younger generations are counted, not spanned"
    ),
}


def span_name_registered(name: str) -> bool:
    """True when ``name`` is in the taxonomy, directly or via a ``*``
    family (``controller.scheduler`` matches ``controller.*``)."""
    if name in SPAN_NAMES:
        return True
    return any(
        name.startswith(k[:-1])
        for k in SPAN_NAMES
        if k.endswith("*")
    )


def render_span_table() -> str:
    """The docs/OPERATIONS.md span-taxonomy table, generated from
    ``SPAN_NAMES`` so prose can never drift from the registry the linter
    and the stitcher enforce (tools/docs_from_bench.py writes it between
    the spantaxonomy markers and fails loudly on drift)."""
    lines = [
        "| span | what it times |",
        "|---|---|",
    ]
    for name in sorted(SPAN_NAMES):
        lines.append(f"| `{name}` | {SPAN_NAMES[name]} |")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# trace context + wire metadata
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class TraceContext:
    """The propagated triple + the caller's process name: what crosses a
    channel so the remote ``WaveTracer`` records under the caller's wave."""

    wave: int
    trace_id: str
    span_id: Optional[int]
    proc: str


#: gRPC metadata keys carrying the context (lowercase per gRPC rules)
MD_WAVE = "karmada-tpu-wave"
MD_TRACE = "karmada-tpu-trace"
MD_SPAN = "karmada-tpu-span"
MD_PROC = "karmada-tpu-proc"


def trace_metadata(ctx: Optional[TraceContext]) -> tuple:
    """``ctx`` as gRPC invocation metadata pairs (empty when no context —
    callers splice this into the stub call unconditionally)."""
    if ctx is None or not ctx.trace_id:
        return ()
    return (
        (MD_WAVE, str(ctx.wave)),
        (MD_TRACE, ctx.trace_id),
        (MD_SPAN, "" if ctx.span_id is None else str(ctx.span_id)),
        (MD_PROC, ctx.proc),
    )


def decode_trace_metadata(pairs) -> Optional[TraceContext]:
    """Decode a server handler's invocation metadata back to a context.
    Tolerant: absent or malformed values answer None (an untraced caller
    must never fail the RPC)."""
    if not pairs:
        return None
    md = {}
    try:
        for k, v in pairs:
            md[str(k).lower()] = v
    except (TypeError, ValueError):
        return None
    trace_id = md.get(MD_TRACE, "")
    if not trace_id:
        return None
    try:
        wave = int(md.get(MD_WAVE, "0") or 0)
    except ValueError:
        return None
    raw_span = md.get(MD_SPAN, "")
    span_id: Optional[int] = None
    if raw_span:
        try:
            span_id = int(raw_span)
        except ValueError:
            return None
    return TraceContext(
        wave=wave, trace_id=str(trace_id), span_id=span_id,
        proc=str(md.get(MD_PROC, "") or "peer"),
    )


# --------------------------------------------------------------------------
# wave-scoped span tracing
# --------------------------------------------------------------------------


@dataclass
class Span:
    """One timed region of one wave. ``attrs`` may be filled while the
    span is open (the fleet path stamps device/compile attribution onto
    its kernel spans); everything is frozen into the ring at close."""

    name: str
    wave: int
    span_id: int
    parent_id: Optional[int]
    start: float  # perf_counter
    wall: float  # time.time at open (absolute anchor for exports)
    end: Optional[float] = None
    attrs: dict = field(default_factory=dict)
    trace_id: str = ""

    @property
    def duration(self) -> float:
        return (self.end if self.end is not None else time.perf_counter()) - self.start

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "wave": self.wave,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "trace_id": self.trace_id,
            "start": round(self.start, 6),
            "wall": round(self.wall, 6),
            "duration_s": round(self.duration, 6),
            "attrs": dict(self.attrs),
        }


def _env_capacity() -> int:
    raw = os.environ.get(TRACE_CAPACITY_ENV, "").strip()
    if not raw:
        return _DEFAULT_CAPACITY
    try:
        return max(int(raw), 16)
    except ValueError:
        log.warning("bad %s=%r; using %d", TRACE_CAPACITY_ENV, raw,
                    _DEFAULT_CAPACITY)
        return _DEFAULT_CAPACITY


class WaveTracer:
    """Ring-buffered, thread-safe, nestable span recorder keyed by wave.

    Wave lifecycle: ``ensure_wave(reason)`` opens a wave if none is open
    (the detector stamps one per user-event burst; ``run_until_settled``
    stamps one for any other work source) and ``end_wave()`` closes it
    when the plane reaches quiescence — so one storm, however triggered,
    is one wave id across every controller it touches. Every wave mints a
    plane-unique ``trace_id``; spans stamp (wave, trace_id) ONCE at open,
    under the lock — a span opened before ``end_wave()`` but closed after
    stays attributed to the wave it opened under, never to a since-reused
    id."""

    def __init__(self, capacity: Optional[int] = None):
        # capacity: explicit argument wins; else KARMADA_TPU_TRACE_CAPACITY
        # (the 1M-tier storms outgrow the default — evictions are
        # counted, never silent)
        self.capacity = _env_capacity() if capacity is None else capacity
        self._lock = threading.Lock()
        self._spans: deque[Span] = deque()
        # spans completed where no lock may be taken (record_late), filed
        # into the ring by the next access that holds the lock
        self._late: list[Span] = []
        self._wave_seq = itertools.count(1)
        self._span_seq = itertools.count(1)
        self._local = threading.local()
        self.current_wave = 0
        self._wave_open = False
        self._wave_reason = ""
        self._wave_started = 0.0
        #: process name stamped on exports + propagated in metadata (the
        #: stitcher keys processes on it); entrypoints override via
        #: set_process ("solver", "estimator", "bus", "agent")
        self.proc = "plane"
        # wave -> trace_id (bounded: old waves age out with the ring)
        self._trace_ids: dict[int, str] = {}
        # ring-eviction accounting (ISSUE 10 satellite): total + per-wave
        self._dropped_total = 0
        self._dropped_by_wave: dict[int, int] = {}
        self._dropped_counter = None  # lazy karmada_tpu_trace_spans_dropped
        # flight-recorder baseline captured at begin_wave when armed
        self._flight_baseline: Optional[dict] = None
        # per-tracer wave-history ring (utils.history), built lazily so
        # the tracer stays importable without the sampler
        self._history = None
        # one-shot (wave, stitched doc) handoff: the history sampler
        # stitches at wave close, and a flight record firing for the
        # SAME close consumes the result instead of re-fetching every
        # peer — a breaching wave pays the stitch once
        self._stitch_handoff = None

    def set_process(self, name: str) -> None:
        with self._lock:
            self.proc = name

    # -- waves -------------------------------------------------------------

    # called-with-lock-held helper (the *_locked naming convention):
    # begin_wave/ensure_wave hold self._lock around it
    def _begin_wave_locked(self, reason: str) -> int:  # graftlint: disable=GL004
        self.current_wave = next(self._wave_seq)
        self._wave_open = True
        self._wave_reason = reason
        self._wave_started = time.perf_counter()
        self._trace_ids[self.current_wave] = uuid.uuid4().hex[:16]
        if len(self._trace_ids) > 512:
            for w in sorted(self._trace_ids)[:-256]:
                del self._trace_ids[w]
                self._dropped_by_wave.pop(w, None)
        return self.current_wave

    def begin_wave(self, reason: str = "") -> int:
        with self._lock:
            wave = self._begin_wave_locked(reason)
        self._flight_begin(wave)
        return wave

    def ensure_wave(self, reason: str = "") -> int:
        # ONE critical section for check-and-open: two threads racing
        # (detector event on the bus watch thread vs the serve loop's
        # settle) must agree on a single wave id for one burst
        with self._lock:
            if self._wave_open:
                return self.current_wave
            wave = self._begin_wave_locked(reason)
        self._flight_begin(wave)
        return wave

    def open_wave(self) -> Optional[int]:
        """The wave currently open, or None. Measurement harnesses use
        this to anchor a window: work they trigger joins the OPEN wave
        when a previous burst's tail kept it open, so a wave-id diff
        alone would miss it."""
        with self._lock:
            return self.current_wave if self._wave_open else None

    def end_wave(self) -> int:
        """Close the open wave and return its id — the flight recorder
        (and tests) key on the CLOSED id, not on whatever wave is current
        by the time they run. The history sampler runs FIRST so a flight
        record of the same close can attach the freshly sampled row
        (utils.history.breach_context)."""
        with self._lock:
            closed = self.current_wave
            was_open = self._wave_open
            self._wave_open = False
        if was_open:
            self.history.sample(self, closed)
            try:
                maybe_flight_record(self, closed)
            except Exception as exc:  # noqa: BLE001 — the recorder must
                # never abort a settle; a broken disk loses the record,
                # not the wave
                log.warning("flight recorder failed: %s", exc)
        return closed

    @property
    def history(self):
        """This tracer's per-wave telemetry ring (utils.history.
        WaveHistory) — the process-wide tracer's instance backs
        ``/debug/history`` and ``karmadactl-tpu top``."""
        # double-checked locking: the unlocked fast-path read is the
        # point (every span close consults the ring); the locked
        # re-check makes the one-time publication race-free
        if self._history is None:  # graftlint: disable=GL011
            from .history import WaveHistory

            fresh = WaveHistory()
            with self._lock:
                if self._history is None:
                    self._history = fresh
        return self._history  # graftlint: disable=GL011

    def wave_trace_id(self, wave: Optional[int] = None) -> str:
        with self._lock:
            if wave is None:
                wave = self.current_wave
            return self._trace_ids.get(wave, "")

    # -- flight-recorder baseline -----------------------------------------

    def _flight_begin(self, wave: int) -> None:
        """Capture the metrics/fault baseline for ``wave`` when the flight
        recorder is armed (KARMADA_TPU_TRACE_SLO_SECONDS set). Disarmed —
        the default — this is one env read per WAVE, nothing per span."""
        if flight_slo() is None:
            return
        baseline = flight_baseline(wave)
        with self._lock:
            self._flight_baseline = baseline

    def flight_baseline_for(self, wave: int) -> Optional[dict]:
        with self._lock:
            b = self._flight_baseline
        return b if (b is not None and b.get("wave") == wave) else None

    def consume_stitch_handoff(self, wave: int) -> Optional[dict]:
        """Take (one-shot) the stitched doc the history sampler built
        for ``wave`` at this close — None when sampling was local-only
        or the handoff belongs to another wave."""
        with self._lock:
            handoff = self._stitch_handoff
            self._stitch_handoff = None
        if handoff is not None and handoff[0] == wave:
            return handoff[1]
        return None

    # -- spans -------------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = []
            self._local.stack = stack
        return stack

    def _open_ctx(
        self, *, lock_free: bool = False
    ) -> tuple[int, str, Optional[int]]:
        """(wave, trace_id, parent span id) for a span opening NOW on this
        thread: innermost open span wins, then the thread's ambient
        context (executor tasks / server handlers), then the process-wide
        current wave — read under the lock, stamped exactly once
        (``lock_free``: read as it stands, for ``record_late``)."""
        stack = self._stack()
        if stack:
            top = stack[-1]
            return top.wave, top.trace_id, top.span_id
        amb = getattr(self._local, "ambient", None)
        if amb is not None:
            return amb.wave, amb.trace_id, amb.span_id
        if lock_free:
            wave = self.current_wave  # graftlint: disable=GL011
            return wave, self._trace_ids.get(wave, ""), None  # graftlint: disable=GL011
        with self._lock:
            return (
                self.current_wave,
                self._trace_ids.get(self.current_wave, ""),
                None,
            )

    def current_context(self) -> TraceContext:
        """The context a CLIENT seam propagates: the innermost open span
        (or ambient context) of this thread, else the current wave."""
        wave, trace_id, parent = self._open_ctx()
        # self.proc is set once at entrypoint boot (set_process) before
        # any span flows; the client-seam read stays deliberately
        # lock-free on the span hot path
        return TraceContext(
            wave=wave, trace_id=trace_id, span_id=parent,
            proc=self.proc,  # graftlint: disable=GL011
        )

    @contextmanager
    def activate(self, ctx: Optional[TraceContext]):
        """Install ``ctx`` as this thread's ambient context: spans opened
        with no local parent nest under ``ctx.span_id``'s wave/trace.
        THE cross-thread propagation primitive — fan-out executors capture
        ``current_context()`` before submit and activate it in the task."""
        if ctx is None:
            yield
            return
        prev = getattr(self._local, "ambient", None)
        self._local.ambient = ctx
        try:
            yield
        finally:
            self._local.ambient = prev

    # called-with-lock-held helper (the *_locked naming convention)
    def _push_locked(self, sp: Span) -> int:  # graftlint: disable=GL004,GL011
        """Ring push with counted eviction; returns the evictions (0/1)."""
        dropped = 0
        if len(self._spans) >= self.capacity:
            old = self._spans.popleft()
            dropped = 1
            self._dropped_total += 1
            self._dropped_by_wave[old.wave] = (
                self._dropped_by_wave.get(old.wave, 0) + 1
            )
        self._spans.append(sp)
        return dropped

    def _file_late_locked(self) -> int:  # graftlint: disable=GL004,GL011
        """File the parked ``record_late`` spans into the ring. pop(0) and
        the callback's append are each atomic, so a collection that lands
        in here only lengthens the list being drained."""
        dropped = 0
        while self._late:
            dropped += self._push_locked(self._late.pop(0))
        return dropped

    def _append(self, sp: Span) -> None:
        """Ring append with counted eviction (called with the lock NOT
        held)."""
        with self._lock:
            dropped = self._file_late_locked() + self._push_locked(sp)
        if dropped:
            counter = self._dropped_counter
            if counter is None:
                # lazy: utils.metrics is stdlib-only but the tracer must
                # stay importable before/without the registry
                from .metrics import trace_spans_dropped as counter

                self._dropped_counter = counter
            counter.inc(dropped)

    def _new_span(
        self,
        name: str,
        wave: int,
        trace_id: str,
        parent_id: Optional[int],
        attrs: dict,
        *,
        start: Optional[float] = None,
        end: Optional[float] = None,
    ) -> Span:
        now = time.perf_counter()
        start = now if start is None else start
        return Span(
            name=name,
            wave=wave,
            span_id=next(self._span_seq),
            parent_id=parent_id,
            start=start,
            wall=time.time() - (now - start),
            end=end,
            attrs=attrs,
            trace_id=trace_id,
        )

    @contextmanager
    def span(self, name: str, **attrs):
        """Record a span under the current wave, nested under this
        thread's innermost open span (or ambient context). Yields the
        ``Span`` so callers can stamp attrs (``kind="device"``,
        ``compile=True``) mid-flight."""
        wave, trace_id, parent = self._open_ctx()
        with self._span_at(name, wave, trace_id, parent, dict(attrs)) as sp:
            yield sp

    @contextmanager
    def server_span(self, name: str, ctx: Optional[TraceContext], **attrs):
        """The SERVER half of context propagation: record a handler span
        under the CALLER's wave/trace. A remote caller's span id cannot be
        a local parent (ids are per-process), so it lands in
        ``remote_parent`` (+ ``caller``) for the stitcher to re-parent;
        an in-process caller (same ``proc``) just nests naturally."""
        # set-once proc read (see current_context), lock-free by design
        if ctx is None or ctx.proc == self.proc:  # graftlint: disable=GL011
            with self.span(name, **attrs) as sp:
                yield sp
            return
        attrs = dict(attrs)
        attrs["remote_parent"] = ctx.span_id
        attrs["caller"] = ctx.proc
        with self._span_at(name, ctx.wave, ctx.trace_id, None, attrs) as sp:
            yield sp

    @contextmanager
    def _span_at(
        self,
        name: str,
        wave: int,
        trace_id: str,
        parent: Optional[int],
        attrs: dict,
    ):
        sp = self._new_span(name, wave, trace_id, parent, attrs)
        stack = self._stack()
        stack.append(sp)
        try:
            yield sp
        finally:
            sp.end = time.perf_counter()
            stack.pop()
            # a span the caller marked _discard never reaches the ring
            # (speculative spans around drains that turned out empty)
            if not sp.attrs.pop("_discard", False):
                self._append(sp)

    def record(
        self, name: str, duration: float, *,
        start: Optional[float] = None, parent: Optional[Span] = None,
        **attrs
    ) -> Span:
        """Append an already-measured region as a COMPLETED span, nested
        under this thread's innermost open span, or under ``parent`` (a
        span recorded before it) — for code that times its phases with
        perf_counter stamps (the fleet pass breakdown) rather than nesting
        context managers. With ``start`` (a perf_counter stamp) the span
        lies at its true interval ``[start, start + duration]``; without,
        it ends now."""
        if parent is None:
            wave, trace_id, parent_id = self._open_ctx()
        else:
            wave, trace_id = parent.wave, parent.trace_id
            parent_id = parent.span_id
        if start is None:
            start = time.perf_counter() - duration
        sp = self._new_span(
            name, wave, trace_id, parent_id, dict(attrs),
            start=start, end=start + duration,
        )
        self._append(sp)
        return sp

    def record_late(
        self, name: str, duration: float, *, start: float, **attrs
    ) -> None:
        """``record`` for a caller that may hold ANY lock, this tracer's
        included (the collector's callback runs wherever an allocation
        lands): takes none. The span is parked and reaches the ring with
        the next access under the lock."""
        # list.append is atomic; the drain pops under the lock
        self._late.append(  # graftlint: disable=GL004
            self._new_span(
                name, *self._open_ctx(lock_free=True), dict(attrs),
                start=start, end=start + duration,
            )
        )

    def open_manual(
        self, name: str, ctx: Optional[TraceContext] = None, **attrs
    ) -> Span:
        """Allocate an OPEN span without pushing it on this thread's
        stack — for in-flight windows that close on another thread (the
        pipelined ``call_future`` seam closes its client span from the
        grpc done callback). Close with ``close_manual``; until then the
        span is not in the ring."""
        if ctx is None:
            wave, trace_id, parent = self._open_ctx()
        else:
            wave, trace_id, parent = ctx.wave, ctx.trace_id, ctx.span_id
        return self._new_span(name, wave, trace_id, parent, dict(attrs))

    def server_open_manual(
        self, name: str, ctx: Optional[TraceContext] = None, **attrs
    ) -> Span:
        """``server_span``'s manual-close variant — the same re-parenting
        contract (a remote caller's span id lands in ``remote_parent`` +
        ``caller`` with the span parentless locally; an in-process caller
        nests naturally) for handler windows that suspend across the
        handler thread (the bus Watch replay generator). Close with
        ``close_manual``."""
        # set-once proc read (see current_context), lock-free by design
        if ctx is not None and ctx.proc != self.proc:  # graftlint: disable=GL011
            attrs = dict(attrs)
            attrs["remote_parent"] = ctx.span_id
            attrs["caller"] = ctx.proc
            return self._new_span(name, ctx.wave, ctx.trace_id, None, attrs)
        return self.open_manual(name, ctx, **attrs)

    def close_manual(self, sp: Span) -> None:
        sp.end = time.perf_counter()
        self._append(sp)

    # -- export ------------------------------------------------------------

    def dump(self, wave: Optional[int] = None) -> list[dict]:
        with self._lock:
            self._file_late_locked()
            spans = list(self._spans)
        if wave is not None:
            spans = [s for s in spans if s.wave == wave]
        return [s.to_json() for s in spans]

    def spans_for(self, wave: int) -> list[Span]:
        """Completed spans of one wave (ring snapshot, no JSON) — the
        history sampler aggregates engine pass stats off their attrs."""
        with self._lock:
            self._file_late_locked()
            return [
                s for s in self._spans
                if s.wave == wave and s.end is not None
            ]

    def waves(self) -> list[int]:
        with self._lock:
            self._file_late_locked()
            return sorted({s.wave for s in self._spans})

    @property
    def dropped_total(self) -> int:
        with self._lock:
            return self._dropped_total

    def clear(self) -> None:
        with self._lock:
            del self._late[:]
            self._spans.clear()
            self._wave_open = False
            self._dropped_total = 0
            self._dropped_by_wave.clear()
            self._stitch_handoff = None
            hist = self._history
        if hist is not None:
            hist.clear()

    def wave_summary(
        self, wave: Optional[int] = None, *, stitched: bool = False
    ) -> dict:
        """Per-phase attribution of one wave (default: the latest one
        with spans): ``total_s`` sums the wave's ROOT spans (parentless —
        the settle drains), ``phases`` maps span name -> summed SELF time
        (duration minus direct children), and ``coverage`` is attributed/
        total. ``dropped`` counts spans of this wave evicted off the ring
        (coverage silently degrading at 1M-tier was the ISSUE 10
        satellite). ``stitched=True`` additionally pulls ``/debug/traces``
        from every registered peer and returns the cross-process summary
        (``stitch_dumps`` shape) instead of the local one."""
        if stitched:
            # narrowed both sides: the per-wave-close history sampler
            # rides this path, so the LOCAL doc must not pay the
            # full-ring JSON build either, and a black-holed peer gets
            # the flight recorder's short timeout, not urlopen's default
            local = trace_debug_doc(wave=wave, tracer_obj=self)
            peer_docs = fetch_peer_dumps(
                peers(), timeout=2.0, wave=wave, skip_unhealthy=True
            )
            doc = stitch_dumps(local, peer_docs, wave=wave)
            if wave is not None:
                with self._lock:
                    self._stitch_handoff = (wave, doc)
            waves = doc.get("waves", [])
            if not waves:
                return self.wave_summary(wave)
            return waves[-1]
        with self._lock:
            self._file_late_locked()
            spans = list(self._spans)
            dropped_by_wave = dict(self._dropped_by_wave)
            trace_ids = dict(self._trace_ids)
        if wave is None:
            wave = max((s.wave for s in spans), default=0)
        spans = [s for s in spans if s.wave == wave and s.end is not None]
        by_id = {s.span_id: s for s in spans}
        child_time: dict[int, float] = {}
        for s in spans:
            if s.parent_id is not None and s.parent_id in by_id:
                child_time[s.parent_id] = (
                    child_time.get(s.parent_id, 0.0) + s.duration
                )
        roots = [
            s for s in spans
            if s.parent_id is None or s.parent_id not in by_id
        ]
        total = sum(s.duration for s in roots)
        phases: dict[str, float] = {}
        counts: dict[str, int] = {}
        device = compile_s = 0.0
        for s in spans:
            self_time = max(s.duration - child_time.get(s.span_id, 0.0), 0.0)
            phases[s.name] = phases.get(s.name, 0.0) + self_time
            counts[s.name] = counts.get(s.name, 0) + 1
            if s.attrs.get("kind") == "device":
                device += s.duration
            # compile attribution is a FLAG, not a kind: a fresh trace's
            # compile runs inside the dispatch window or surfaces at the
            # device fence — the fleet marks both spans of a fresh-trace
            # pass, so compile_s upper-bounds the compile-bearing time
            if s.attrs.get("compile"):
                compile_s += s.duration
        attributed = sum(phases.values())
        trace_id = trace_ids.get(wave, "")
        if not trace_id and spans:
            trace_id = spans[0].trace_id
        dropped = dropped_by_wave.get(wave, 0)
        return {
            "wave": wave,
            "trace_id": trace_id,
            "total_s": round(total, 6),
            "coverage": round(attributed / total, 4) if total else 0.0,
            # ISSUE 12 satellite: coverage is computed against the FULL
            # wall even when ring evictions dropped this wave's spans —
            # flag the degradation instead of letting the ratio silently
            # undercount (raise KARMADA_TPU_TRACE_CAPACITY)
            "coverage_degraded": dropped > 0,
            "phases": {k: round(v, 6) for k, v in sorted(phases.items())},
            "span_counts": dict(sorted(counts.items())),
            "device_s": round(device, 6),
            "compile_s": round(compile_s, 6),
            "host_s": round(max(attributed - device, 0.0), 6),
            "spans": len(spans),
            "dropped": dropped,
        }

    def wave_summaries(self, last: int = 8) -> list[dict]:
        return [self.wave_summary(w) for w in self.waves()[-last:]]


#: the process-wide tracer (one ring per process, like the metrics
#: registry; MetricsServer and the CLI dump read THIS instance)
tracer = WaveTracer()


class GcWatch:
    """The program's own view of the CPython collector — the analogue of
    the Go reference's ``go_gc_duration_seconds``. One ``gc.callbacks``
    entry: every collection adds to ``runs`` / ``seconds`` by generation
    (the registry's ``karmada_tpu_gc_collections_total`` and
    ``karmada_tpu_gc_pause_seconds_total`` read them when scraped), and a
    generation-2 collection also becomes a ``runtime.gc`` span at its true
    interval. Only those: a busy plane runs thousands of young collections
    a minute, which would flood the ring, and the few full ones are nearly
    all of the pause time.

    Lock-free by necessity (see the module docstring). One start stamp is
    enough: the interpreter runs one collection at a time."""

    def __init__(self, tracer_obj: WaveTracer):
        self.tracer = tracer_obj
        self.runs = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._started: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._started = time.perf_counter()
            return
        t0, self._started = self._started, None
        if t0 is None:
            return  # installed while a collection was running
        pause = time.perf_counter() - t0
        gen = info["generation"]
        self.runs[gen] += 1
        self.seconds[gen] += pause
        if gen == 2:
            self.tracer.record_late(
                "runtime.gc", pause, start=t0,
                generation=gen, collected=info["collected"],
            )

    def samples(self, field: str) -> dict:
        """``runs`` or ``seconds`` as registry samples, by generation."""
        return {
            (("generation", str(g)),): float(v)
            for g, v in enumerate(getattr(self, field))
        }


#: installed once, with the tracer its spans go to
gc_watch = GcWatch(tracer)
gc.callbacks.append(gc_watch)


class ContextPropagatingExecutor:
    """Submit-side context propagation over any executor: each task runs
    under the SUBMITTER's trace context (innermost open span at submit
    time), so fan-out RPC spans land in the wave that fanned them out
    instead of wave 0. Wraps only ``submit`` — the estimator fan-out pools
    use nothing else — and delegates the rest."""

    def __init__(self, executor, tracer_obj: Optional[WaveTracer] = None):
        self._executor = executor
        self._tracer = tracer_obj or tracer

    def submit(self, fn, *args, **kwargs):
        tr = self._tracer
        ctx = tr.current_context()

        def run():
            with tr.activate(ctx):
                return fn(*args, **kwargs)

        return self._executor.submit(run)

    def shutdown(self, wait: bool = True) -> None:
        self._executor.shutdown(wait=wait)

    def __getattr__(self, name):
        return getattr(self._executor, name)


# --------------------------------------------------------------------------
# peer registry: where the stitcher finds the other processes' rings
# --------------------------------------------------------------------------

_PEERS: dict[str, str] = {}
_PEERS_LOCK = threading.Lock()


def register_peer(name: str, address: str) -> None:
    """Register a peer process's metrics endpoint (``host:port``) for the
    stitcher. The plane registers its solver sidecar / estimator servers /
    bus at boot (localup exports KARMADA_TPU_TRACE_PEERS to the serve
    process; benches register programmatically)."""
    with _PEERS_LOCK:
        _PEERS[name] = address


def unregister_peer(name: str) -> None:
    with _PEERS_LOCK:
        _PEERS.pop(name, None)


def peers() -> dict[str, str]:
    with _PEERS_LOCK:
        return dict(_PEERS)


def clear_peers() -> None:
    with _PEERS_LOCK:
        _PEERS.clear()
        _PEER_RETRY_AT.clear()


def register_peers_from_env() -> dict[str, str]:
    """Parse ``KARMADA_TPU_TRACE_PEERS`` (``name=host:port,...``) into the
    registry — the boot hook every long-running entrypoint calls."""
    raw = os.environ.get(TRACE_PEERS_ENV, "").strip()
    if not raw:
        return {}
    added: dict[str, str] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, addr = part.partition("=")
        if not sep or not name.strip() or not addr.strip():
            log.warning("bad %s entry %r (want name=host:port)",
                        TRACE_PEERS_ENV, part)
            continue
        register_peer(name.strip(), addr.strip())
        added[name.strip()] = addr.strip()
    return added


# --------------------------------------------------------------------------
# the /debug/traces document (shared by MetricsServer + the CLI dump)
# --------------------------------------------------------------------------


def trace_debug_doc(
    wave: Optional[int] = None,
    *,
    summary: bool = False,
    tracer_obj: Optional[WaveTracer] = None,
) -> dict:
    """THE ``/debug/traces`` document: built in one place so the HTTP
    endpoint, ``karmadactl-tpu trace dump`` and the stitcher can never
    drift on shape. The scheduling-mesh report is sys.modules-gated: a
    process that never imported the mesh module has no mesh, and importing
    it here would drag jax into lean processes (the bus)."""
    import sys as _sys

    tr = tracer_obj or tracer
    pm = _sys.modules.get("karmada_tpu.parallel.mesh")
    doc = {
        "proc": tr.proc,
        "mesh": pm.active_mesh_shape() if pm is not None else None,
        "dropped": tr.dropped_total,
        "peers": peers(),
    }
    if wave is not None:
        # narrowed fetch (?wave=N): filter BEFORE serializing and
        # summarize only the requested wave — per-wave history sampling
        # and the flight recorder hit this path once per wave close, so
        # it must not pay the full-ring JSON build
        doc["waves"] = [
            w for w in (tr.wave_summary(wave),) if w.get("spans")
        ]
        doc["spans"] = tr.dump(wave)
    else:
        doc["waves"] = tr.wave_summaries()
        doc["spans"] = tr.dump()
    if summary:
        doc.pop("spans", None)
    return doc


#: addr -> monotonic retry-at for peers that just failed a fetch: the
#: per-wave-close sampler must not pay a full timeout per close for a
#: persistently-down peer (skip window; guarded by _PEERS_LOCK)
_PEER_RETRY_AT: dict[str, float] = {}
_PEER_SKIP_SECONDS = 30.0


def fetch_peer_dumps(
    peer_map: dict[str, str], timeout: float = 5.0,
    wave: Optional[int] = None, *, skip_unhealthy: bool = False,
) -> dict[str, dict]:
    """Pull ``/debug/traces`` from every peer's metrics port,
    CONCURRENTLY (N black-holed peers cost one timeout, not N serial
    ones — the per-wave-close history sampler rides this path).
    Unreachable peers are skipped with a warning — a stitched dump of
    the reachable plane beats no dump. ``wave`` narrows each fetch
    server-side (``?wave=N`` — peers record under the CALLER's wave id):
    at 1M-tier capacities the full ring is tens of thousands of spans
    per peer, and both stitching call sites already know which wave they
    want. ``skip_unhealthy=True`` (the frequent-caller mode: per-wave
    sampling) additionally skips any peer that failed within the last
    30s, so a down sidecar costs one timeout per skip window instead of
    one per wave close; one-shot callers (flight recorder without a
    handoff, the CLI) keep the always-try default."""
    import urllib.request

    docs: dict[str, dict] = {}
    query = "" if wave is None else f"?wave={wave}"

    def fetch_one(addr: str) -> dict:
        with urllib.request.urlopen(
            f"http://{addr}/debug/traces{query}", timeout=timeout
        ) as resp:
            return json.loads(resp.read().decode())

    if skip_unhealthy:
        now = time.monotonic()
        with _PEERS_LOCK:
            peer_map = {
                name: addr for name, addr in peer_map.items()
                if _PEER_RETRY_AT.get(addr, 0.0) <= now
            }
    if not peer_map:
        return docs
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=min(len(peer_map), 8)) as pool:
        futures = {
            name: pool.submit(fetch_one, addr)
            for name, addr in sorted(peer_map.items())
        }
    for name, fut in futures.items():
        try:
            docs[name] = fut.result()
        except Exception as exc:  # noqa: BLE001 — peer down: stitch the rest
            with _PEERS_LOCK:
                _PEER_RETRY_AT[peer_map[name]] = (
                    time.monotonic() + _PEER_SKIP_SECONDS
                )
            log.warning("trace peer %s (%s) unreachable: %s", name,
                        peer_map[name], type(exc).__name__)
        else:
            with _PEERS_LOCK:
                _PEER_RETRY_AT.pop(peer_map[name], None)
    return docs


# --------------------------------------------------------------------------
# the stitcher: cross-process trace trees + per-channel attribution
# --------------------------------------------------------------------------


def _span_channel(name: str) -> Optional[str]:
    """The channel a client RPC span belongs to (its name's first dotted
    component: ``estimator.rpc`` -> ``estimator``)."""
    head, sep, _ = name.partition(".")
    return head if sep else None


def stitch_spans(
    spans: list[dict], wave: int, trace_id: str, *, dropped: int = 0
) -> dict:
    """Stitch ONE wave's spans (already tagged with ``proc``, merged from
    every process) into a cross-process summary: remote handler roots
    re-parent under their originating client spans (``remote_parent`` +
    ``caller`` attrs), self-times compute across the stitched tree, and
    each channel's network/serialization time falls out as
    ``client span − remote roots`` per RPC. Durations only — process
    clocks are never compared. ``dropped`` is INPUT data (ring evictions
    of this wave, summed across the contributing processes — the raw
    spans cannot carry it): nonzero flags the stitched coverage as
    degraded, same as the local summary."""
    sel = [
        s for s in spans
        if s.get("wave") == wave
        and (not trace_id or s.get("trace_id", "") == trace_id)
    ]
    by_key = {(s.get("proc", "?"), s["span_id"]): s for s in sel}

    def parent_key(s: dict) -> Optional[tuple]:
        attrs = s.get("attrs", {})
        rp, caller = attrs.get("remote_parent"), attrs.get("caller")
        if caller is not None:
            key = (caller, rp)
            return key if key in by_key else None
        if s.get("parent_id") is not None:
            key = (s.get("proc", "?"), s["parent_id"])
            return key if key in by_key else None
        return None

    child_time: dict[tuple, float] = {}
    remote_children: dict[tuple, list] = {}
    parents: dict[tuple, Optional[tuple]] = {}
    for s in sel:
        key = (s.get("proc", "?"), s["span_id"])
        pk = parent_key(s)
        parents[key] = pk
        if pk is not None:
            child_time[pk] = child_time.get(pk, 0.0) + s["duration_s"]
            if pk[0] != key[0]:
                remote_children.setdefault(pk, []).append(s)

    # roots: unparented spans that did NOT arrive over a channel. After
    # re-parenting, a remote handler span is never a root — total_s is
    # the caller-side wall, exactly what the local summary reports; a
    # handler span whose client span fell off the ring must not inflate
    # it either (hence the ``caller`` check, not just parent resolution)
    roots = [
        s for s in sel
        if parents[(s.get("proc", "?"), s["span_id"])] is None
        and "caller" not in s.get("attrs", {})
    ]
    total = sum(s["duration_s"] for s in roots)

    phases: dict[str, float] = {}
    counts: dict[str, int] = {}
    process_s: dict[str, float] = {}
    channels: dict[str, dict] = {}
    device = compile_s = 0.0
    for s in sel:
        key = (s.get("proc", "?"), s["span_id"])
        self_time = max(s["duration_s"] - child_time.get(key, 0.0), 0.0)
        phases[s["name"]] = phases.get(s["name"], 0.0) + self_time
        counts[s["name"]] = counts.get(s["name"], 0) + 1
        proc = s.get("proc", "?")
        process_s[proc] = process_s.get(proc, 0.0) + self_time
        # device/compile attribution, the local summary's rule: kind is
        # a span attr, compile a flag — stitched history rows must not
        # read zeros for series the local rows populate
        if s.get("attrs", {}).get("kind") == "device":
            device += s["duration_s"]
        if s.get("attrs", {}).get("compile"):
            compile_s += s["duration_s"]
        # per-channel columns from CLIENT rpc spans: server time is the
        # re-parented remote roots' wall; the remainder of the client
        # span is wire + serialization — the column no single-process
        # view can produce
        if s.get("attrs", {}).get("remote"):
            ch = _span_channel(s["name"])
            if ch is not None:
                slot = channels.setdefault(
                    ch, {"rpcs": 0, "client_s": 0.0, "server_s": 0.0,
                         "network_s": 0.0, "events": 0},
                )
                server = sum(
                    c["duration_s"] for c in remote_children.get(key, [])
                )
                slot["rpcs"] += 1
                # batching factor: a batched RPC carries batch=N items
                # per message (ISSUE 11); unary calls count 1
                slot["events"] += int(s["attrs"].get("batch") or 1)
                slot["client_s"] += s["duration_s"]
                slot["server_s"] += server
                slot["network_s"] += max(s["duration_s"] - server, 0.0)
    attributed = sum(phases.values())
    return {
        "wave": wave,
        "trace_id": trace_id,
        "stitched": True,
        "total_s": round(total, 6),
        "coverage": round(attributed / total, 4) if total else 0.0,
        "coverage_degraded": dropped > 0,
        "dropped": dropped,
        "device_s": round(device, 6),
        "compile_s": round(compile_s, 6),
        "phases": {k: round(v, 6) for k, v in sorted(phases.items())},
        "span_counts": dict(sorted(counts.items())),
        "process_s": {
            k: round(v, 6) for k, v in sorted(process_s.items())
        },
        "channels": {
            k: {
                "rpcs": v["rpcs"],
                "events": v["events"],
                "events_per_rpc": round(
                    v["events"] / v["rpcs"], 2
                ) if v["rpcs"] else 0.0,
                "client_s": round(v["client_s"], 6),
                "server_s": round(v["server_s"], 6),
                "network_s": round(v["network_s"], 6),
            }
            for k, v in sorted(channels.items())
        },
        "spans": len(sel),
        "procs": sorted({s.get("proc", "?") for s in sel}),
    }


def stitch_dumps(
    local: dict, peer_docs: dict[str, dict], wave: Optional[int] = None
) -> dict:
    """Merge the local ``/debug/traces`` doc with the peers' docs into one
    stitched document: every span tagged with its process, waves keyed by
    the LOCAL process's (trace_id, wave) and summarized across processes.
    ``wave`` restricts to one wave (default: every local wave)."""
    all_spans: list[dict] = []
    local_proc = local.get("proc", "plane")
    for s in local.get("spans", []):
        s = dict(s)
        s.setdefault("proc", local_proc)
        all_spans.append(s)
    dropped = {local_proc: local.get("dropped", 0)}
    for name, doc in sorted(peer_docs.items()):
        proc = doc.get("proc", name)
        dropped[proc] = doc.get("dropped", 0)
        for s in doc.get("spans", []):
            s = dict(s)
            s.setdefault("proc", proc)
            all_spans.append(s)
    waves = [
        w for w in local.get("waves", [])
        if wave is None or w.get("wave") == wave
    ]
    # per-wave ring evictions summed across the contributing processes
    # (each doc's wave summaries carry their own `dropped`): the stitched
    # summary must flag degraded coverage exactly like a local one
    dropped_by_wave: dict[int, int] = {}
    for doc in [local, *peer_docs.values()]:
        for w in doc.get("waves", []):
            wid = w.get("wave")
            if wid is not None:
                dropped_by_wave[wid] = (
                    dropped_by_wave.get(wid, 0) + int(w.get("dropped", 0) or 0)
                )
    stitched_waves = [
        stitch_spans(
            all_spans, w["wave"], w.get("trace_id", ""),
            dropped=dropped_by_wave.get(w["wave"], 0),
        )
        for w in waves
    ]
    return {
        "proc": local_proc,
        "procs": sorted({s.get("proc", "?") for s in all_spans}),
        "dropped": dropped,
        "waves": stitched_waves,
        "spans": all_spans,
    }


def render_attribution_table(summary: dict) -> str:
    """The stitched-wave attribution table as text (``trace analyze`` and
    the bench print this; the JSON record stays the machine surface)."""
    degraded = (
        f" DEGRADED(dropped={summary.get('dropped', 0)})"
        if summary.get("coverage_degraded")
        else ""
    )
    lines = [
        f"wave {summary.get('wave')} trace {summary.get('trace_id', '')} "
        f"total {summary.get('total_s', 0.0):.3f}s coverage "
        f"{summary.get('coverage', 0.0) * 100:.1f}%{degraded}",
        "phase                       self_s",
    ]
    for name, v in sorted(
        summary.get("phases", {}).items(), key=lambda kv: -kv[1]
    ):
        lines.append(f"{name:<27} {v:8.4f}")
    if summary.get("process_s"):
        lines.append("process                     self_s")
        for name, v in sorted(summary["process_s"].items()):
            lines.append(f"{name:<27} {v:8.4f}")
    if summary.get("channels"):
        lines.append(
            "channel      rpcs  ev/msg   client_s   server_s  network_s"
        )
        for name, v in sorted(summary["channels"].items()):
            ev_per = v.get(
                "events_per_rpc",
                (v.get("events", v["rpcs"]) / v["rpcs"]) if v["rpcs"] else 0.0,
            )
            lines.append(
                f"{name:<10} {v['rpcs']:6d} {ev_per:7.2f} "
                f"{v['client_s']:10.4f} "
                f"{v['server_s']:10.4f} {v['network_s']:10.4f}"
            )
    return "\n".join(lines)


# --------------------------------------------------------------------------
# slow-wave flight recorder
# --------------------------------------------------------------------------


def flight_slo() -> Optional[float]:
    """The armed SLO (seconds), or None when the recorder is off —
    KARMADA_TPU_TRACE_SLO_SECONDS unset/empty/unparseable means OFF, and
    the whole recorder costs one env read per wave boundary."""
    raw = os.environ.get(TRACE_SLO_ENV, "").strip()
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


def flight_dir() -> str:
    raw = os.environ.get(FLIGHT_DIR_ENV, "").strip()
    if raw:
        return raw
    import tempfile

    return os.path.join(tempfile.gettempdir(), "karmada_tpu_flight")


def _flight_cap() -> int:
    raw = os.environ.get(FLIGHT_CAP_ENV, "").strip()
    try:
        return max(int(raw), 1) if raw else _DEFAULT_FLIGHT_CAP
    except ValueError:
        return _DEFAULT_FLIGHT_CAP


def flight_baseline(wave: int) -> dict:
    """The begin-of-wave snapshot the recorder deltas against: the full
    metrics registry + the fired-fault count."""
    from .faultinject import injector
    from .metrics import registry

    inj = injector()
    return {
        "wave": wave,
        "metrics": registry.snapshot(),
        "fault_events": len(inj.log) if inj is not None else 0,
    }


def _metrics_delta(before: Optional[dict], after: dict) -> dict:
    """Per-family sample deltas (after − before); families/samples absent
    from ``before`` delta against 0. Zero deltas are dropped — the record
    carries what MOVED during the wave."""
    out: dict = {}
    before = before or {}
    for family, samples in after.items():
        prev = before.get(family, {})
        fam_delta: dict = {}
        for key, val in samples.items():
            if isinstance(val, dict):
                pv = prev.get(key, {})
                d = {
                    k: round(val.get(k, 0) - pv.get(k, 0), 9)
                    for k in val
                    if val.get(k, 0) != pv.get(k, 0)
                }
                if d:
                    fam_delta[key] = d
            else:
                d = val - prev.get(key, 0)
                if d:
                    fam_delta[key] = round(d, 9)
        if fam_delta:
            out[family] = fam_delta
    return out


def _delta_total(delta: dict, family: str) -> float:
    vals = delta.get(family, {})
    total = 0.0
    for v in vals.values():
        if isinstance(v, dict):
            total += v.get("count", 0)
        else:
            total += v
    return total


def maybe_flight_record(tr: WaveTracer, wave: int) -> Optional[str]:
    """The ``end_wave`` hook: when the recorder is armed and the closing
    wave breached the SLO — or a breaker transition / degraded pass /
    QuotaExceeded denial fired during it — persist the stitched trace, the
    metrics delta and the fired fault log as one JSONL record. Returns the
    record path when a record was written."""
    slo = flight_slo()
    if slo is None:
        return None
    from .faultinject import injector
    from .metrics import registry

    summary = tr.wave_summary(wave)
    wall = summary.get("total_s", 0.0)
    baseline = tr.flight_baseline_for(wave) or {}
    delta = _metrics_delta(baseline.get("metrics"), registry.snapshot())
    reasons: list[str] = []
    if wall > slo:
        reasons.append(f"slo:{wall:.3f}s>{slo:.3f}s")
    if _delta_total(delta, "karmada_tpu_degraded_passes_total") > 0:
        reasons.append("degraded-pass")
    if _delta_total(delta, "karmada_tpu_quota_denied_total") > 0:
        reasons.append("quota-exceeded")
    if summary.get("span_counts", {}).get("channel.breaker"):
        reasons.append("breaker-transition")
    if not reasons:
        return None

    inj = injector()
    fault_log = []
    if inj is not None:
        start = baseline.get("fault_events", 0)
        fault_log = [
            {"seq": e.seq, "point": e.point, "action": e.action,
             "key": e.key}
            for e in inj.log[start:]
        ]
    # reuse the stitch the history sampler just built for this close
    # (the sampler runs first in end_wave) — a breaching wave pays the
    # peer fetch once; with stitched sampling off (no peers registered
    # or KARMADA_TPU_HISTORY_STITCH=0), only a RECORDED wave pays it
    stitched = tr.consume_stitch_handoff(wave)
    if stitched is None:
        local = trace_debug_doc(wave=wave, tracer_obj=tr)
        peer_docs = fetch_peer_dumps(peers(), timeout=2.0, wave=wave)
        stitched = stitch_dumps(local, peer_docs, wave=wave)
    stitched_summary = (
        stitched["waves"][-1] if stitched.get("waves") else summary
    )
    record = {
        "wave": wave,
        "trace_id": summary.get("trace_id", ""),
        "proc": tr.proc,
        "recorded_at": time.time(),
        "slo_seconds": slo,
        "wall_s": wall,
        "reasons": reasons,
        "summary": stitched_summary,
        "spans": stitched["spans"],
        "procs": stitched["procs"],
        "dropped": stitched["dropped"],
        "metrics_delta": delta,
        "fault_events": fault_log,
        # ISSUE 12: the breaching wave's history row + recent-window
        # digests (end_wave samples BEFORE recording, so the row exists)
        # — `trace analyze` renders breach-vs-recent-baseline offline
        "history": tr.history.breach_context(wave),
    }
    # ISSUE 13: the K worst (denied/unschedulable/displaced) bindings'
    # explanations, when the explain plane captured this wave — `trace
    # analyze` answers "why" offline. Lazy import: the store is
    # numpy-backed and most waves never arm it.
    try:
        from .explainstore import store as _explain_store

        explain_ctx = _explain_store().worst_context(wave)
        if explain_ctx is not None:
            record["explain"] = explain_ctx
    except Exception:  # noqa: BLE001 — provenance is attachment, not
        # the record; a broken capture never blocks the flight write
        pass
    return _flight_append(record)


def _flight_append(record: dict) -> str:
    """Append one JSONL record under KARMADA_TPU_FLIGHT_DIR, ring-capped:
    the file keeps at most KARMADA_TPU_FLIGHT_CAP records (oldest
    dropped)."""
    dir_ = flight_dir()
    os.makedirs(dir_, exist_ok=True)
    path = os.path.join(dir_, "flight.jsonl")
    line = json.dumps(record, sort_keys=True)
    cap = _flight_cap()
    lines: list[str] = []
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
    lines.append(line)
    if len(lines) > cap:
        lines = lines[-cap:]
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    os.replace(tmp, path)
    log.warning(
        "flight record: wave %s (%s) -> %s",
        record["wave"], ",".join(record["reasons"]), path,
    )
    return path


def load_flight_records(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        return [
            json.loads(ln) for ln in f.read().splitlines() if ln.strip()
        ]


def analyze_record(record: dict) -> dict:
    """Re-derive a flight record's attribution from its RAW spans and
    compare against the summary stored at record time — the offline
    ``trace analyze`` surface. ``identical`` proves the stitcher is a pure
    function of the spans (the bench asserts it). The recorded `dropped`
    count is INPUT data, not derived from the spans, so it feeds back
    into the re-derivation. A record carrying history context
    additionally renders the breach-vs-recent-window table."""
    recorded = record.get("summary", {})
    recomputed = stitch_spans(
        record.get("spans", []), record.get("wave", 0),
        record.get("trace_id", ""),
        dropped=int(recorded.get("dropped", 0) or 0),
    )
    table = render_attribution_table(recomputed)
    hist = record.get("history")
    if hist and hist.get("row"):
        from .history import render_breach_table

        table += "\n" + render_breach_table(hist)
    # ISSUE 13: a record carrying worst-binding explanations renders
    # the "why" block too — the offline form of /debug/explain
    expl = record.get("explain")
    if expl and expl.get("worst"):
        from .explainstore import render_worst_table

        table += "\n" + render_worst_table(expl)
    # purity check tolerant of OLDER records: summary keys this build
    # added (coverage_degraded/dropped) are ignored when the recorded
    # summary predates them — a pre-upgrade flight record must still
    # prove the stitcher pure, not flag a schema addition
    recomputed_vs = {
        k: v for k, v in recomputed.items() if k in recorded
    }
    return {
        "wave": record.get("wave"),
        "trace_id": record.get("trace_id", ""),
        "reasons": record.get("reasons", []),
        "wall_s": record.get("wall_s"),
        "slo_seconds": record.get("slo_seconds"),
        "summary": recomputed,
        "identical": recomputed_vs == recorded,
        "metrics_delta": record.get("metrics_delta", {}),
        "fault_events": record.get("fault_events", []),
        "history": hist,
        "explain": record.get("explain"),
        "table": table,
    }


# --------------------------------------------------------------------------
# events
# --------------------------------------------------------------------------


@dataclass
class Event:
    object_ref: str  # "<kind>/<key>"
    type: str  # Normal | Warning
    reason: str
    message: str
    timestamp: float = field(default_factory=time.time)


class EventRecorder:
    """In-memory event sink (kube EventRecorder seam). Bounded ring —
    ``deque(maxlen=...)`` so append-at-capacity is O(1) and atomic, with
    a lock over append/snapshot: the shared global ``recorder`` is written
    by every controller thread and read by status surfaces concurrently."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._events: deque[Event] = deque(maxlen=capacity)
        self._lock = threading.Lock()

    @property
    def events(self) -> list[Event]:
        """Snapshot (consumers iterate/filter freely; the historical
        attribute was a mutable list — a snapshot keeps that read
        contract race-free)."""
        with self._lock:
            return list(self._events)

    def event(self, object_ref: str, type_: str, reason: str, message: str) -> None:
        with self._lock:
            self._events.append(Event(object_ref, type_, reason, message))

    def for_object(self, object_ref: str) -> list[Event]:
        with self._lock:
            return [e for e in self._events if e.object_ref == object_ref]


# shared recorder (cmd binaries each had one; in-proc a single sink suffices)
recorder = EventRecorder()
