"""TensorScheduler: the batched Filter/Score/Select/Assign pipeline.

Re-architecture of the reference's per-binding pipeline
(core/generic_scheduler.go:70-115 — findClustersThatFit ->
prioritizeClusters -> SelectClusters -> AssignReplicas) as chunked tensor
programs over [bindings, clusters] arrays:

- Filter: mask composition from compiled placements + per-binding leniency
  (already-placed) and eviction masks — HOT LOOP 1+2 of SURVEY.md section 3.1
  collapse into gathers and boolean ops.
- Score: locality scoring (cluster already holds the resource scores 100,
  clusterlocality/cluster_locality.go:43-56); used by spread selection.
- Select: spread-constraint group selection (karmada_tpu.scheduler.spread).
- Assign: the unified division kernel (karmada_tpu.ops.divide).

The ordered ClusterAffinities retry loop (scheduler.go:533-596) runs as a
short host loop over affinity-term rounds: each round schedules every not-
yet-placed binding against its term-t mask, so T rounds of fully batched
kernels replace per-binding retries (T == max #terms, almost always 1).

Chunking: bindings are processed in fixed-size chunks (padded) so jit traces
once; 100k bindings x 5k clusters stream through [chunk, C] arrays sized for
HBM.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Optional, Sequence

import numpy as np
import jax.numpy as jnp

from ..api.policy import Placement
from ..ops.divide import divide_replicas
from ..ops.estimate import general_estimate, merge_estimates
from ..utils.features import CUSTOMIZED_CLUSTER_RESOURCE_MODELING, feature_gate
from .snapshot import ClusterSnapshot, CompiledPlacement, compile_placement

LOCALITY_SCORE = 100  # cluster_locality.go:43-56


def kernel_variant(
    avail_max: int, static_max: int, prev_max: int, max_n: int, c: int
) -> tuple[bool, Optional[tuple]]:
    """Choose the divide-kernel specialization from host-known bounds.

    Returns ``(wide, fast)`` for divide_replicas: int32 fast path when every
    weight x target product and per-row weight sum provably fits 31 bits
    (weights can be avail, prev, the fresh-mode avail+prev sum, or static
    weights; targets <= replicas), and the packed-key top_k dispense when
    the (weight, lastReplicas, index) key fits 31 bits with a small
    remainder rank. The bit split snaps to tiers so the static tuple (and
    hence the jit trace) does not churn as data maxima drift."""
    # exact weight bound by cohort: avail (<= avail_max), prev (<= prev_max),
    # fresh = avail + credited prev (<= sum), static (<= static_max) — the
    # bound decides both the int32 gate and the packed-key bit budget, so
    # every saved bit widens the fast path's reach
    max_w = max(avail_max + prev_max, static_max, 1)
    narrow = max_w * max(max_n, 1) < 2**31 and max_w * c < 2**31
    fast = None
    if narrow:
        w_bits = max(1, max_w.bit_length())
        l_bits = max(1, int(prev_max).bit_length())
        i_bits = max(1, (c - 1).bit_length())
        k_top = min(c, 1 << max(1, max(1, max_n) - 1).bit_length())
        div_f32 = max_w * max(max_n, 1) < 2**24 and max_n < 2**22
        if k_top <= 1024:
            if w_bits + l_bits + i_bits <= 31:
                # every tier is one bounded, persistently-cached trace; a
                # floor above 4 would push tight-budget fleets (large
                # i_bits + moderate w_bits) off the snap entirely and churn
                # traces with every data-maxima drift
                for l_tier in (4, 8, 12, 16):
                    if l_bits <= l_tier and w_bits <= 31 - i_bits - l_tier:
                        l_bits = l_tier
                        w_bits = 31 - i_bits - l_tier
                        break
                fast = (w_bits, l_bits, k_top, div_f32, True)
            elif w_bits + l_bits <= 31:
                # (weight, last) alone fits: the two-stage top_k dispense
                # (take_by_weight_fast with_idx=False) recovers index
                # tie-breaks without packing the index
                for l_tier in (4, 8, 12, 16):
                    if l_bits <= l_tier and w_bits <= 31 - l_tier:
                        l_bits = l_tier
                        w_bits = 31 - l_tier
                        break
                fast = (w_bits, l_bits, k_top, div_f32, False)
    return (not narrow), fast


def host_profile_table(
    snapshot, uniq: np.ndarray, models_active: bool = False
) -> np.ndarray:
    """numpy mirror of ``TensorScheduler._profile_table`` over unique
    request profiles: int64[U, C], MAX_INT32 sentinel where nothing is
    requested or the cluster gives no summary (ops/estimate.py:25-38),
    with the resource-model estimator replacing the summary estimate
    where applicable when ``models_active`` (general.go:63-94,118-135 —
    pods cap applied separately, exactly like the device form). THE
    single host-side mirror — the tiny-batch fast path and the fleet's
    avail-max bound both consume it, so sentinel semantics cannot drift.
    Values are clamped to the sentinel BEFORE comparison, exactly like
    the device form's final min — an absurd-but-legal ratio above 2^31-1
    must read as "no answer -> clamp to spec.Replicas", not as a huge
    availability."""
    mi = 2**31 - 1  # plain int (ops.estimate.MAX_INT32 is a DEVICE scalar)
    cap = np.maximum(np.asarray(snapshot.available_cap), 0)
    table = np.full((uniq.shape[0], cap.shape[0]), mi, np.int64)
    for d in range(uniq.shape[1]):
        req = uniq[:, d]
        ratio = cap[None, :, d] // np.maximum(req[:, None], 1)
        table = np.where((req > 0)[:, None], np.minimum(table, ratio), table)
    table = np.minimum(table, mi)
    if models_active:
        from ..models.modeling import estimate_by_models_np

        mp = snapshot.model_pack
        pods_dim = snapshot.dim_index("pods")
        req_models = np.asarray(uniq)
        if pods_dim is not None:
            req_models = req_models.copy()
            req_models[:, pods_dim] = 0
        model_avail, applicable = estimate_by_models_np(
            np.asarray(mp.min_bounds), np.asarray(mp.counts),
            np.asarray(mp.covered), req_models,
        )
        model_avail = model_avail.astype(np.int64)
        if pods_dim is not None:
            allowed = np.minimum(np.maximum(cap[:, pods_dim], 0), mi)
            model_avail = np.minimum(model_avail, allowed[None, :])
        use_model = np.asarray(mp.has_models)[None, :] & applicable
        table = np.where(use_model, model_avail, table)
    return np.where(np.asarray(snapshot.has_summary)[None, :], table, mi)


class _BoostedSnapshot:
    """Capacity-shifted view of a ClusterSnapshot for the preemption
    re-solve: ``available_cap`` reads as ``base + freed_caps`` (the
    victims' resources, per cluster column); every other attribute
    delegates. Never cached anywhere — the per-profile/selection caches
    key on the real snapshot only."""

    def __init__(self, base, freed_caps):
        self._base = base
        self.available_cap = np.asarray(base.available_cap) + np.asarray(
            freed_caps, dtype=np.asarray(base.available_cap).dtype
        )

    def __getattr__(self, name):
        return getattr(self._base, name)


@dataclass
class BindingProblem:
    """Engine-level scheduling unit (decoupled from the API object; the
    scheduler process builds these from ResourceBindings)."""

    key: str
    placement: Optional[Placement] = None
    replicas: int = 0
    requests: dict[str, int] = dc_field(default_factory=dict)
    gvk: str = ""
    prev: dict[str, int] = dc_field(default_factory=dict)  # spec.clusters
    evict_clusters: tuple[str, ...] = ()  # graceful-eviction tasks
    fresh: bool = False  # reschedule triggered
    namespace: str = ""  # quota-admission namespace ("" = not quota'd)
    # scarcity plane (ISSUE 14): the binding's priority class (0 = the
    # back-compat default — never preempts, preemptible by any class
    # above it) and the subset of evict_clusters whose eviction task is
    # a preemption (the explain capture's stage-7 bit)
    priority: int = 0
    preempt_clusters: tuple[str, ...] = ()


@dataclass
class ScheduleResult:
    key: str
    clusters: dict[str, int] = dc_field(default_factory=dict)
    feasible: tuple[str, ...] = ()  # post-filter candidates (zero-replica set)
    affinity_name: str = ""
    error: str = ""

    @property
    def success(self) -> bool:
        return not self.error


#: the divider's insufficient-capacity verdict (wire/compat surface —
#: tests and the oracle match on it; REASONS classifies it as
#: InsufficientReplicas). The preemption plane's demander predicate:
#: only THIS failure means "freeing capacity could place the binding".
INSUFFICIENT_ERROR = "clusters available replicas are not enough"

#: why a row leaves the fleet table for the general host path, in the
#: order _host_path_reason tests them (a row counts for the first it meets)
HOST_PATH_REASONS = (
    "terms", "evict_tasks", "terms_spread", "replicas", "selection",
)
#: the spans of a host-path chunk's stages timed by
#: scheduling_algorithm_duration (Filter, Score, Select, AssignReplicas)
_HOST_STAGES = (
    "scheduler.host.pack", "scheduler.host.estimate",
    "scheduler.host.select", "scheduler.host.assign",
)


def _host_path_reason(p, cp: CompiledPlacement) -> str:
    """The bound a row the fleet-eligibility predicate turned away passed
    first: the placement's terms, the binding's eviction tasks, terms
    beside spread constraints, a Divided row's replicas (past what a cell
    holds, fleet.replicas_bound); else a spread-constrained row that was
    given no selection."""
    from .fleet import K_EVICT, T_CAP, row_rides

    if len(cp.terms) > T_CAP:
        return "terms"
    if len(p.evict_clusters) > K_EVICT:
        return "evict_tasks"
    if len(cp.terms) > 1 and not cp.fleet_terms:
        return "terms_spread"
    if not row_rides(p, cp):
        return "replicas"
    return "selection"


@dataclass
class PreemptionOutcome:
    """One pass's preemption verdict, deposited on the engine as
    ``last_preemption`` for the scheduler controller to act on (victim
    evictions are store writes — the engine never touches API objects,
    the quota-plane division of labor)."""

    #: (key, resident placement dict, priority) per selected victim
    victims: list = dc_field(default_factory=list)
    #: demander keys that re-solved successfully against the freed
    #: capacity (their results were patched in place)
    placed: list = dc_field(default_factory=list)
    #: demander keys still unschedulable even with every victim freed
    still_unschedulable: list = dc_field(default_factory=list)
    #: int64[C, R] capacity the victims free, per cluster column
    freed_caps: Optional[np.ndarray] = None


def _placement_flags(cp: CompiledPlacement) -> tuple:
    """What the prologue reads of a compiled placement to place a row: the
    placement half of the fleet-eligibility predicate, whether the Select
    stage selects for it, and the strategy fleet.row_rides asks. Functions
    of the PLACEMENT alone, so a snapshot swap leaves them standing (the
    moved-positions pass compares them to be told if that ever stops being
    so)."""
    return cp.fleet_terms, cp.spread_single_term, cp.strategy


class TensorScheduler:
    """Schedules batches of bindings against one cluster snapshot."""

    #: the in-tree filter/score plugin set (framework/plugins/registry.go:30-39)
    PLUGINS = (
        "APIEnablement",
        "ClusterAffinity",
        "ClusterEviction",
        "ClusterLocality",
        "SpreadConstraint",
        "TaintToleration",
    )

    def __init__(
        self,
        snapshot: ClusterSnapshot,
        chunk_size: int = 4096,
        extra_estimators: Sequence = (),
        disabled_plugins: Sequence[str] = (),
        custom_filters: Sequence = (),
        mesh=None,
        shard_clusters: bool = False,
        trace_manifest=None,
    ):
        self.snapshot = snapshot
        self.chunk_size = chunk_size
        # durable trace ledger (scheduler.prewarm.TraceManifest | path |
        # None = env default KARMADA_TPU_TRACE_MANIFEST, unset = off).
        # Resolved once here so every fleet table this engine builds
        # shares one manifest instance (one dedup set, one file).
        from .prewarm import resolve_manifest

        self.trace_manifest = resolve_manifest(trace_manifest)
        # scheduling-grid mesh (jax.sharding.Mesh with axes ("b", "c")):
        # the fleet solve shards its row axis over "b" (and the cluster
        # axis over "c" when shard_clusters) via sharding constraints —
        # multi-chip scale-out of the production path, placement-
        # identical to single-device. Resolved ONCE here, the manifest
        # pattern: an explicit Mesh passes through, None falls back to
        # the KARMADA_TPU_MESH_DEVICES env default, False forces
        # single-device even with the env set.
        from ..parallel.mesh import record_active_mesh, resolve_mesh

        self.mesh = resolve_mesh(mesh)
        if self.mesh is not None:
            record_active_mesh(self.mesh)
            # a >1 cluster axis only exists to shard clusters: opt in
            # automatically so the env knob alone configures both axes
            shard_clusters = bool(
                shard_clusters or self.mesh.shape.get("c", 1) > 1
            )
        self.shard_clusters = shard_clusters
        # callables (requests[B,R] int64, replicas[B] int32) -> int32[B,C]
        # availability with -1 for "no answer" (accurate estimators plug here)
        self.extra_estimators = list(extra_estimators)
        # --plugins enable/disable list (scheduler.go:243-247)
        self.disabled_plugins = set(disabled_plugins)
        # out-of-tree filter plugins (the plugin-registry seam,
        # framework/runtime/registry.go): callables
        # (snapshot, problems) -> bool[B, C] mask AND-composed with the
        # in-tree filters — batched by construction
        self.custom_filters = list(custom_filters)
        # id(placement) -> (placement, compiled), LRU-bounded. The strong
        # reference to the Placement keeps its id() from being reused by a
        # new object after GC — without it a fresh Placement landing at a
        # recycled address would silently reuse a stale compiled mask.
        # Eviction is safe (pin and compiled mask leave together) and bounds
        # memory under sustained binding churn against a long-lived engine.
        from collections import OrderedDict

        self._placement_cache: OrderedDict[
            int, tuple[Optional[Placement], CompiledPlacement]
        ] = OrderedDict()
        # device-resident fleet table (scheduler.fleet): engaged for large
        # batches of fleet-eligible bindings; generation counter lets the
        # table detect in-place snapshot swaps (update_snapshot). The table
        # holds the record of the batch it last scheduled (``batch``),
        # which schedule() diffs a later batch against once it is armed
        self._fleet = None
        self._snapshot_gen = 0
        from ..utils.metrics import scheduler_prologue_rows

        # positions of each full-path pass the prologue (kept, visited),
        # added once a pass
        self._prologue_tally = tuple(
            scheduler_prologue_rows.labels(outcome=o)
            for o in ("kept", "visited")
        )
        from .quota import AdmissionTally

        # what admission made of each pass's rows, by either route (the
        # fleet table adds the resident route's)
        self._quota_tally = AdmissionTally()
        # which branch the pass's solve took (identity | delta | full |
        # host): the scheduler.schedule span's path attr
        self._pass_path = "host"
        # the mask_token the host-selection line was last printed for
        self._host_select_told = None
        # the by-reason counts the host-path line was last printed for
        self._host_path_told = None
        from ..utils.metrics import fleet_host_path_rows_total

        # rows of each pass that left the fleet table, by reason, added
        # once a pass that has such rows
        self._host_path_tally = {
            r: fleet_host_path_rows_total.labels(reason=r)
            for r in HOST_PATH_REASONS
        }
        # chunks the general host path ran under the open scheduler.host
        self._host_chunks = 0
        # per-pass dirty-key set (ISSUE 20): the controller's invalidation
        # sources (watch bus, quota bumps, estimator movement, evictions)
        # accumulate binding keys whose problems changed since the last
        # wave; schedule() stages them here and the batch-identity diff
        # unions them with the id()-diff to form the moved positions.
        # None = caller supplied no dirty info (diff alone decides).
        self._dirty_keys: Optional[set] = None
        # binding key -> (row fingerprint, pinned placement, selection
        # bits | None): the last SelectClusters result of a spread row the
        # HOST selected (a snapshot with more regions than the device
        # kernel's table, or a row the explain capture asked about),
        # packed as the fleet's row state takes it (None = FitError).
        # Skips the packing+selection stage for unchanged rows at one
        # snapshot generation; across generations it is what ``moved`` is
        # told by
        self._row_selections: dict = {}
        # batched solves dispatched (host chunks + fleet passes): the
        # chaos bench reads this to prove a failover wave reschedules its
        # displaced bindings in O(chunks) solves, not O(bindings)
        self.solve_batches = 0
        # request-profile bytes -> availability row [C] (per snapshot gen)
        self._sel_profile_rows: dict = {}
        self._sel_profile_gen = -1
        # quota plane (scheduler.quota.QuotaSnapshot | None): a batch that
        # rides the fleet table whole is admitted from the table's row
        # state (a denial is a bit beside the row's answer); any other is
        # partitioned by ONE batched kernel pass before the solve
        # (_schedule_quota); static-assignment caps fold into availability
        # as one more estimator. Disarmed = a single `is None` check per
        # schedule() call.
        self.quota = None
        # the partition route's cache: (problem ids, quota generation,
        # admitted sub-list, denied results) of the last wave with
        # denials: keeps the admitted sub-list IDENTITY-stable across
        # steady storm passes so the batch-identity fast paths below still
        # fire under enforcement
        self._quota_cache: Optional[tuple] = None
        # device mirror of the static-assignment cap tensor, keyed by the
        # quota snapshot's cap_token (caps change rarely; remaining often)
        self._caps_dev = None
        self._caps_dev_token = None
        # engine-level trace ledger for the quota kernels (the fleet table
        # ledgers the solve family; admission dispatches engine-side)
        self._engine_traces: set = set()
        self._engine_new_trace = False
        # placement provenance (ISSUE 13): when armed, every schedule()
        # pass runs ONE extra batched explain dispatch per chunk and
        # deposits the exclusion masks + top-k summaries in the
        # process-wide ExplainStore. Disarmed — the default — the hot
        # path costs one `is None` check (the quota/fault pattern).
        from ..utils.explainstore import explain_armed, store as _estore

        self.explain = _estore() if explain_armed() else None
        # scarcity plane (ISSUE 14): when armed, a pass whose priority>0
        # rows answer "available replicas are not enough" runs ONE
        # batched plane-wide victim selection (ops.preempt) and re-solves
        # the demanders against the freed capacity IN THE SAME PASS.
        # ``preempt_source`` is a callable(exclude_keys) answering the
        # resident victim pool as BindingProblems (the controller wires
        # it per pass; None — the default — is the disarmed state: one
        # `is None` check per schedule() call, the quota/fault pattern).
        self.preempt_source = None
        self.last_preemption: Optional[PreemptionOutcome] = None

    PLACEMENT_CACHE_CAP = 8192
    #: minimum eligible-batch size before the device-resident path engages
    #: (below it, per-pass dispatch overhead beats the host packing cost).
    #: Kept low enough that a storm's straggler batches ride the same
    #: already-compiled fleet trace instead of fresh host-path chunk shapes
    fleet_threshold = 256

    # -- compilation -------------------------------------------------------

    def _compiled(self, placement: Optional[Placement]) -> CompiledPlacement:
        key = id(placement) if placement is not None else 0
        hit = self._placement_cache.get(key)
        if hit is not None:
            self._placement_cache.move_to_end(key)
            return hit[1]
        cp = compile_placement(placement, self.snapshot)
        # placement-level half of the fleet-eligibility predicate, computed
        # once per compiled placement (the per-binding half is
        # fleet.row_rides; schedule() applies both to every row of a batch
        # whose identity fast path did not fire)
        from .fleet import T_CAP
        from .spread import should_ignore_spread_constraint

        unconstrained = (
            not cp.spread_constraints
            or should_ignore_spread_constraint(cp.placement or Placement())
        )
        # ordered affinity terms ride as term slots of the fleet's rows, up
        # to T_CAP of them; several terms TOGETHER with spread constraints
        # keep the host's per-term round loop
        cp.fleet_terms = len(cp.terms) <= T_CAP and unconstrained
        cp.spread_single_term = len(cp.terms) == 1 and not unconstrained
        self._placement_cache[key] = (placement, cp)
        # the cap must exceed the fleet table's live-slot budget: a live
        # placement set larger than the LRU turns a storm's cyclic access
        # into a 100% miss rate (~every row recompiles its selector, tens
        # of seconds per pass), and each recompile mints a NEW compiled
        # object whose id() mints a NEW fleet slot — ballooning the slot
        # table until it dies (observed on the 9k-unique rotation bench)
        cache_cap = self.PLACEMENT_CACHE_CAP
        if self._fleet is not None:
            cache_cap = max(cache_cap, 2 * self._fleet._max_slots())
        if len(self._placement_cache) > cache_cap:
            self._placement_cache.popitem(last=False)
        return cp

    # -- public API --------------------------------------------------------

    def update_snapshot(self, snapshot: ClusterSnapshot) -> bool:
        """Swap in a refreshed snapshot over the SAME cluster set (the
        informer-cache delta case: capacity/taints/enablements drifted but
        no cluster joined or left). Returns False when the cluster set or
        resource dims changed — callers must rebuild the engine then.

        Keeps the device-resident fleet table's binding rows valid (cluster
        indices are stable), so a fleet-wide storm after a status heartbeat
        costs mask/estimator table rebuilds instead of a full repack."""
        if (
            snapshot.names != self.snapshot.names
            or snapshot.dims != self.snapshot.dims
        ):
            return False
        # compiled placements are functions of the FILTER fields only
        # (snapshot.mask_token): an availability-only swap keeps every
        # cached mask valid, so a heterogeneous fleet's churn pass skips
        # recompiling thousands of selectors (~0.5s/pass at 3.5k placements)
        if snapshot.mask_token != self.snapshot.mask_token:
            self._placement_cache.clear()
        self.snapshot = snapshot
        # spread selections depend on capacities: their row fingerprints
        # carry this generation, so none outlives the swap
        self._snapshot_gen += 1
        return True

    @property
    def last_pass_new_trace(self) -> bool:
        """True when the last schedule() pass dispatched at least one XLA
        trace signature the fleet table had not dispatched before (a compile
        ran). Bench warmup loops
        poll this until a pass is compile-stable before opening a timed
        window. Engine-dispatched quota kernels count too."""
        return bool(
            (self._fleet is not None and self._fleet.new_trace_last_pass)
            or self._engine_new_trace
        )

    @property
    def mesh_info(self):
        """Canonical shape of the scheduling mesh — ``(("b", nb),
        ("c", nc))``, or None single-device. The reporting form: the
        solver sidecar's boot line, ``/debug/traces`` and the warmup
        stats all quote it so an operator can tell a single-chip from an
        8-chip plane."""
        from ..parallel.mesh import mesh_shape

        return mesh_shape(self.mesh)

    def set_quota(self, quota) -> None:
        """Swap in a (re)built QuotaSnapshot (None = enforcement off).

        A changed ``cap_token`` (static-assignment content or cluster
        columns moved) drops the fleet table: cap rows are baked into its
        interned profile slots. A generation-only bump (remaining moved —
        the common case: usage recompute, quota raise) keeps every packed
        row and trace; only the admission partition recomputes — a denied
        binding clears on a quota raise without a full re-pack. Another
        namespace SET is the fleet table's to notice (FleetTable._sync_ns
        re-derives its rows' namespace column at the next pass)."""
        old = self.quota
        self.quota = quota
        # a quota with NO static assignments bakes nothing into the fleet
        # profile slots — treat its cap token as absent so toggling
        # enforcement (or FRQ churn without caps) never drops the table
        new_tok = (
            quota.cap_token
            if quota is not None and quota.cap_index
            else None
        )
        old_tok = (
            old.cap_token if old is not None and old.cap_index else None
        )
        if new_tok != old_tok:
            self._fleet = None  # and the batch it held
            self._quota_cache = None
            self._caps_dev = None
            self._caps_dev_token = None
            # spread selections rank groups on cap-folded availability:
            # cap content changes invalidate them
            self._row_selections.clear()

    # -- quota admission ---------------------------------------------------

    _ENGINE_TRACE_KERNELS = {
        "Q": "quota_admit",
        "K": "quota_cluster_caps",
        "E": "explain_pass",
        "P": "preempt_select",
    }

    def _mark_trace(self, *key) -> bool:
        """Engine-side trace ledger for the quota kernels — the fleet
        table's contract (new-trace flag + compile counter + manifest
        record eligibility), for kernels dispatched outside it."""
        if key in self._engine_traces:
            return False
        self._engine_traces.add(key)
        self._engine_new_trace = True
        from ..utils.metrics import kernel_compiles

        bucket = "x".join(
            str(v) for v in key[1:] if isinstance(v, (int, bool))
        )[:64]
        kernel_compiles.inc(
            kernel=self._ENGINE_TRACE_KERNELS.get(key[0], str(key[0])),
            bucket=bucket,
        )
        return True

    def _record_trace(self, kernel: str, key, arrays, **statics) -> None:
        """Best-effort manifest record of a fresh engine-side trace (the
        fleet table's semantics: durability is optional, the wave is
        not)."""
        manifest = self.trace_manifest
        if manifest is None:
            return
        try:
            manifest.record(kernel, key, arrays, statics)
        except Exception as exc:  # noqa: BLE001 — never abort a wave
            import logging

            logging.getLogger("karmada_tpu").warning(
                "trace manifest record of %s failed (%s)",
                kernel, type(exc).__name__,
            )

    def _caps_device(self):
        """Device mirror of the static-assignment cap tensor, rebuilt only
        when the quota snapshot's cap content changes. Rebuilds refresh
        the device-byte ledger's quota slice (the fleet table publishes
        its own kinds per pass)."""
        q = self.quota
        if self._caps_dev is None or self._caps_dev_token != q.cap_token:
            self._caps_dev = jnp.asarray(q.cluster_caps)
            self._caps_dev_token = q.cap_token
            from ..utils.metrics import device_bytes as device_bytes_gauge

            caps = self._caps_dev
            try:
                platform = next(iter(caps.devices())).platform
            except Exception:  # noqa: BLE001 — label is best-effort
                platform = "none"
            device_bytes_gauge.remove_matching(kind="quota_caps")
            device_bytes_gauge.set(
                int(caps.nbytes),
                kind="quota_caps",
                bucket="x".join(str(int(s)) for s in caps.shape),
                platform=platform,
            )
        return self._caps_dev

    def device_bytes(self) -> dict[str, int]:
        """Resident device bytes by ledger kind across this engine: the
        fleet table's kinds plus the quota cap tensor — the exact
        ``nbytes`` of the arrays held (ISSUE 12 b). The bench asserts
        the sum is constant across steady passes and equals the gauge's
        samples."""
        out: dict[str, int] = (
            self._fleet.device_bytes() if self._fleet is not None else {}
        )
        if self._caps_dev is not None:
            out["quota_caps"] = int(self._caps_dev.nbytes)
        return out

    def _quota_cap_rows(self, problems) -> Optional[np.ndarray]:
        """int32[B] row into the cap tensor per binding (-1 = uncapped),
        or None when no binding is in a capped namespace."""
        q = self.quota
        if q is None or not q.has_caps:
            return None
        cap_index = q.cap_index
        rows = np.fromiter(
            (cap_index.get(p.namespace, -1) for p in problems),
            np.int32,
            len(problems),
        )
        return rows if (rows >= 0).any() else None

    def _quota_caps_np(self, cap_rows, requests) -> np.ndarray:
        """Host mirror of the cap estimate (same kernel body as the
        device form — cluster_caps_np instantiates it over numpy)."""
        from ..ops.quota import cluster_caps_np

        return cluster_caps_np(
            self.quota.cluster_caps, cap_rows, requests
        )

    def _quota_caps_dev(self, cap_rows, requests) -> jnp.ndarray:
        from ..ops.quota import quota_cluster_caps

        caps_dev = self._caps_device()
        arrays = (
            caps_dev,
            jnp.asarray(cap_rows, jnp.int32),
            jnp.asarray(requests, jnp.int64),
        )
        # meshed cap fold: binding rows shard over "b" (cap tensor
        # replicates via _caps_device's one-time upload); ledger key per
        # mesh shape, manifest-unrecorded when meshed (see
        # _quota_admission for the rationale)
        q_mesh_el = None
        if self.mesh is not None:
            from ..parallel.mesh import mesh_shape, shard_rows

            rows_dev, req_dev = shard_rows(self.mesh, arrays[1], arrays[2])
            if rows_dev is not arrays[1]:
                q_mesh_el = mesh_shape(self.mesh)
            arrays = (caps_dev, rows_dev, req_dev)
        key = (
            "K", int(len(cap_rows)), tuple(int(s) for s in caps_dev.shape),
            q_mesh_el,
        )
        if self._mark_trace(*key) and q_mesh_el is None:
            self._record_trace("quota_cluster_caps", key, arrays)
        return quota_cluster_caps(*arrays)

    def _quota_admission(self, problems):
        """One batched admission pass over the wave. Returns
        ``(partition, pending_debit, quota_rows, dispatched)``: partition
        is None when no binding is quota'd or every row admitted, else
        (admitted sub-list, denied results as (index, ScheduleResult)
        pairs) — identity-stable across steady passes via _quota_cache so
        the batch-identity fast paths keep firing under enforcement.
        ``pending_debit`` is the wave's admitted demand per namespace, to
        be committed by the caller AFTER the solve (None on cache replay —
        already committed). ``quota_rows`` counts the wave's rows in a
        quota'd namespace, ``dispatched`` says whether the admission
        kernel ran (False: a replay, or nothing to admit).

        A binding that asks for nothing (its delta is not positive: it
        holds what it wants, or scales down) is not the quota's to deny,
        as upstream's enforcement lets such a delta through: it goes to
        the kernel as a row without a quota, whatever its namespace has
        left, and takes no place in its namespace's line."""
        from ..ops.quota import quota_admit
        from .quota import QUOTA_EXCEEDED_ERROR

        q = self.quota
        ns_index = q.ns_index
        b = len(problems)
        ns_ids = np.fromiter(
            (ns_index.get(p.namespace, -1) for p in problems), np.int32, b
        )
        quota_rows = int((ns_ids >= 0).sum())
        if not quota_rows:
            return None, None, 0, False
        cache = self._quota_cache
        ids = np.fromiter(map(id, problems), np.int64, b)
        if (
            cache is not None
            and cache[1] == q.generation
            and len(cache[0]) == b
            and np.array_equal(cache[0], ids)
        ):
            if cache[2] is None:  # cached all-admitted wave
                return None, None, quota_rows, False
            return (cache[2], cache[3]), None, quota_rows, False
        out = self._quota_admission_delta(problems, ids, ns_ids, cache)
        if out is not None:
            part, debit, dispatched = out
            return part, debit, quota_rows, dispatched
        demand = np.zeros((b, len(q.dims)), np.int64)
        for i in np.flatnonzero(ns_ids >= 0):
            p = problems[i]
            delta = p.replicas - sum(p.prev.values())
            if delta > 0:
                demand[i] = q.demand_row(p.requests, delta)
        ns_ids[~demand.any(axis=1)] = -1  # asks nothing: not the quota's
        # pow2 row padding bounds the admission kernel's trace count;
        # pad rows are unquota'd zero-demand and always admit
        b_pad = 1 << max(0, (b - 1).bit_length())
        if b_pad > b:
            ns_ids = np.pad(ns_ids, (0, b_pad - b), constant_values=-1)
            demand = np.pad(demand, ((0, b_pad - b), (0, 0)))
        n_pad = 1 << max(2, (q.remaining.shape[0] - 1).bit_length())
        remaining = q.remaining
        if n_pad > remaining.shape[0]:
            from ..ops.quota import UNLIMITED

            remaining = np.pad(
                remaining,
                ((0, n_pad - remaining.shape[0]), (0, 0)),
                constant_values=UNLIMITED,
            )
        arrays = (
            jnp.asarray(ns_ids),
            jnp.asarray(demand),
            jnp.asarray(remaining),
        )
        # meshed admission: the wave rows shard over "b" (the quota
        # family's FAMILY_SPECS layout), the remaining tensor replicates
        # — quota_admit's sort/cumsum ride GSPMD collectives, placement-
        # identical to single-device. The ledger key carries the mesh
        # shape (a sharded-input executable is a distinct compile), but
        # meshed dispatches stay manifest-UNRECORDED: the kernel has no
        # mesh static, so a replay could only compile the single-device
        # form and would fake coverage.
        q_mesh_el = None
        if self.mesh is not None:
            from ..parallel.mesh import mesh_shape, shard_rows

            ns_dev, dem_dev = shard_rows(self.mesh, arrays[0], arrays[1])
            if ns_dev is not arrays[0]:  # divisible: placement happened
                q_mesh_el = mesh_shape(self.mesh)
            arrays = (ns_dev, dem_dev, arrays[2])
        key = ("Q", b_pad, n_pad, int(remaining.shape[1]), q_mesh_el)
        if self._mark_trace(*key) and q_mesh_el is None:
            self._record_trace("quota_admit", key, arrays)
        admitted_dev, wave_used = quota_admit(*arrays)
        admitted = np.asarray(admitted_dev)[:b]
        # the wave's admitted demand is the PENDING debit against the
        # working remaining: a drain spanning multiple engine passes
        # within ONE quota generation (batch splits, follow-on waves
        # before the usage controller recomputes) must not re-admit the
        # same budget. The caller commits it AFTER the solve so a pass
        # that dies mid-solve (worker bisect/retry) charges nothing; the
        # next generation rebuilds remaining from recomputed usage, so
        # debit and accounting never double-count.
        wu = np.asarray(wave_used)[: q.remaining.shape[0]]
        debit = wu if wu.any() else None
        if admitted.all():
            # cache the all-admitted outcome: a steady storm re-passing
            # the same wave skips the demand rebuild and the kernel.
            # The problems list is PINNED so a recycled id() cannot alias
            # a stale partition (the resident batch's hazard).
            self._quota_cache = (
                ids, q.generation, None, None, np.zeros(0, np.int64),
                list(problems),
            )
            return None, debit, quota_rows, True
        denied_idx = np.flatnonzero(~admitted)
        denied = [
            (
                int(i),
                ScheduleResult(
                    key=problems[i].key, error=QUOTA_EXCEEDED_ERROR
                ),
            )
            for i in denied_idx
        ]
        # identity stability: an unchanged partition re-uses the PREVIOUS
        # admitted sub-list object, so the inner batch-identity paths see
        # the very same list across steady storm passes
        if (
            cache is not None
            and len(cache[4]) == len(denied_idx)
            and np.array_equal(cache[4], denied_idx)
            and len(cache[0]) == b
            and np.array_equal(cache[0], ids)
        ):
            sub = cache[2]
        else:
            sub = [problems[i] for i in np.flatnonzero(admitted)]
        # the full problems list is pinned (last element) so a recycled
        # id() cannot alias a stale partition
        self._quota_cache = (
            ids, q.generation, sub, denied, denied_idx, list(problems)
        )
        return (sub, denied), debit, quota_rows, True

    def _quota_admission_delta(self, problems, ids, ns_ids, cache):
        """Delta admission (ISSUE 20): a wave whose ids moved in a
        MINORITY of positions within the SAME quota generation re-admits
        only the changed rows. ``quota_admit`` is row_coupled (FIFO
        segments share a per-namespace cumsum), so the changed rows run
        through a COMPLETE admission kernel over their own sub-batch — a
        scoped full pass over the affected segment, never a partial
        dispatch — against the working remaining, which already carries
        every previously admitted row's debit. Unchanged rows replay
        their cached outcome exactly: within one generation the working
        remaining only decreases, so a prior denial stays denied and a
        prior admission stays charged. The returned debit covers ONLY
        the changed rows' delta demand — replayed rows are never
        re-charged (the PR 14 working-remaining restore contract,
        extended to the delta path). Returns (partition, debit, whether
        the kernel was dispatched), or None when ineligible (the caller
        runs the full admission)."""
        from ..ops.quota import quota_admit
        from .quota import QUOTA_EXCEEDED_ERROR

        q = self.quota
        b = len(problems)
        if (
            cache is None
            or cache[1] != q.generation
            or len(cache[0]) != b
        ):
            return None
        ch = np.flatnonzero(ids != cache[0])
        if ch.size == 0 or ch.size * 2 > b:
            return None
        nd = len(q.dims)
        m = int(ch.size)
        ns_ch = ns_ids[ch]
        demand = np.zeros((m, nd), np.int64)
        for j in np.flatnonzero(ns_ch >= 0):
            p = problems[int(ch[j])]
            delta = p.replicas - sum(p.prev.values())
            if delta > 0:
                demand[j] = q.demand_row(p.requests, delta)
        old_denied = cache[4]
        dispatched = bool(demand.any())
        if dispatched:
            ns_ch[~demand.any(axis=1)] = -1  # asks nothing: not the quota's
            b_pad = 1 << max(0, (m - 1).bit_length())
            ns_pad, dem_pad = ns_ch, demand
            if b_pad > m:
                ns_pad = np.pad(ns_ch, (0, b_pad - m), constant_values=-1)
                dem_pad = np.pad(demand, ((0, b_pad - m), (0, 0)))
            n_pad = 1 << max(2, (q.remaining.shape[0] - 1).bit_length())
            remaining = q.remaining
            if n_pad > remaining.shape[0]:
                from ..ops.quota import UNLIMITED

                remaining = np.pad(
                    remaining,
                    ((0, n_pad - remaining.shape[0]), (0, 0)),
                    constant_values=UNLIMITED,
                )
            arrays = (
                jnp.asarray(ns_pad),
                jnp.asarray(dem_pad),
                jnp.asarray(remaining),
            )
            q_mesh_el = None
            if self.mesh is not None:
                from ..parallel.mesh import mesh_shape, shard_rows

                ns_dev, dem_dev = shard_rows(self.mesh, arrays[0], arrays[1])
                if ns_dev is not arrays[0]:
                    q_mesh_el = mesh_shape(self.mesh)
                arrays = (ns_dev, dem_dev, arrays[2])
            key = ("Q", b_pad, n_pad, int(remaining.shape[1]), q_mesh_el)
            if self._mark_trace(*key) and q_mesh_el is None:
                self._record_trace("quota_admit", key, arrays)
            admitted_dev, wave_used = quota_admit(*arrays)
            adm_ch = np.asarray(admitted_dev)[:m]
            wu = np.asarray(wave_used)[: q.remaining.shape[0]]
            debit = wu if wu.any() else None
        else:
            # no changed row carries positive delta demand: all admit
            # trivially and nothing is charged
            adm_ch = np.ones(m, bool)
            debit = None
        new_denied = np.union1d(
            np.setdiff1d(old_denied, ch), ch[~adm_ch]
        ).astype(np.int64)
        if new_denied.size == 0:
            self._quota_cache = (
                ids.copy(), q.generation, None, None,
                np.zeros(0, np.int64), list(problems),
            )
            return None, debit, dispatched
        denied = [
            (
                int(i),
                ScheduleResult(
                    key=problems[int(i)].key, error=QUOTA_EXCEEDED_ERROR
                ),
            )
            for i in new_denied
        ]
        if (
            cache[2] is not None
            and len(old_denied) == new_denied.size
            and np.array_equal(old_denied, new_denied)
        ):
            # partition shape unchanged: swap the changed admitted rows
            # into the PREVIOUS sub-list so the solve-level delta path
            # sees an identity-stable wave downstream
            sub = list(cache[2])
            ch_adm = ch[adm_ch]
            if ch_adm.size:
                sub_pos = ch_adm - np.searchsorted(new_denied, ch_adm)
                for s_i, i in zip(sub_pos, ch_adm):
                    sub[int(s_i)] = problems[int(i)]
        else:
            admitted_mask = np.ones(b, bool)
            admitted_mask[new_denied] = False
            sub = [problems[i] for i in np.flatnonzero(admitted_mask)]
        self._quota_cache = (
            ids.copy(), q.generation, sub, denied, new_denied,
            list(problems),
        )
        return (sub, denied), debit, dispatched

    @property
    def cap_shrink_pending(self) -> bool:
        """A buffer-cap shrink desire is accumulating in the fleet table
        (see FleetTable.shrink_pending) — warm loops should continue until
        it either fires (compiling inside warmup) or clears."""
        return bool(self._fleet is not None and self._fleet.shrink_pending)

    def set_explain(self, store) -> None:
        """Arm/disarm provenance capture for this engine (None =
        disarmed; benches and tests arm programmatically, processes via
        ``KARMADA_TPU_EXPLAIN=1``)."""
        self.explain = store

    def set_preemption(self, source) -> None:
        """Arm/disarm the preemption plane for this engine (None =
        disarmed). ``source(exclude_keys)`` answers the resident victim
        pool; the controller arms it per pass so dry solves and disarmed
        planes never pay more than the `is None` check."""
        self.preempt_source = source

    def schedule(
        self,
        problems: Sequence[BindingProblem],
        dirty_keys: Optional[set] = None,
    ) -> list[ScheduleResult]:
        """Provenance wrapper: the solve runs unchanged; when explain is
        armed the pass's decision provenance captures AFTER the results
        exist (one extra armed-only dispatch per chunk — telemetry, so
        a capture failure logs and never aborts the wave).

        Which of four routes a batch takes (_schedule_inner) follows from
        what the engine observes, no option selects one. The fleet table
        holds ONE record of the batch it last scheduled (ResidentBatch),
        ARMED once every row of a pass rode the table. A later batch of
        its length (no custom filter, host-only estimator or disabled
        plugin set) is diffed against it once (ResidentBatch.diff: one id()
        sweep, the positions that hold another object, the caller's dirty
        keys, and whether most moved), and the routes are the outcomes of
        that one diff:

        - identity: nothing moved, no key was named dirty, and the armed
          compiled list stands (the generation it was armed at, or only
          availability moved: the armed ``mask_token`` is the
          snapshot's): the table's pass over the armed lists (root span
          ``path`` = identity);
        - delta and swap: a minority moved. The moved positions alone are
          compiled and held to the fleet-eligibility predicate, over the
          armed compiled list where it stands, else over the armed batch's
          distinct placements compiled anew (_moved_pass;
          ``scheduler.pack`` carries ``rows`` visited and ``kept``), and
          the table visits those positions alone. The table decides
          whether the rest REPLAY from its mirrors (``path`` = delta: the
          generation the batch was armed at stands, and neither the
          mirrors, the estimators, the preemption plane nor a moved spread
          row say otherwise; dirty keys that name no moved position are a
          pure replay) or every row is dispatched (``path`` = full). Rows
          that keep the host's selections keep the record's token None;
        - the walk, the one general path: no armed batch, another length,
          host-path rows in the last pass, most positions moved, a moved
          position that leaves the fleet, host-selected spread rows under
          a moved generation (no diff is made), a guard set: every
          position compiled and partitioned (``path`` = full, or host
          where no fleet pass follows).

        Problem objects are not mutated in place between passes: every
        identity route rests on that.

        ``dirty_keys`` (optional) is the caller's per-wave dirty-row set:
        binding keys whose problems changed since the last wave (watch-bus
        spec/generation movement, quota bumps, estimator pings, eviction
        displacements — the controller accumulates them). The diff unions
        it with the object-identity diff, so a caller that rebuilds a
        problem object without changing content still gets the row
        re-dispatched when it says so. Absent, the pass costs one ``is
        None`` check."""
        from ..utils.tracing import tracer as _tracer

        # the root of an engine wave: entry to return, with the path the
        # solve took (_schedule_inner stamps it), so no stretch of a pass
        # lies under no span and an idle device is charged to a stage
        with _tracer.span("scheduler.schedule", rows=len(problems)) as root:
            self._pass_path = "host"
            self.last_preemption = None
            # new-trace flags are per PASS: cleared here, so a pass that never
            # reaches the fleet table (the host path's small waves) does not
            # go on reporting the compile of an earlier one
            self._engine_new_trace = False
            if self._fleet is not None:
                self._fleet.new_trace_last_pass = False
            self._dirty_keys = set(dirty_keys) if dirty_keys else None
            try:
                results = self._schedule_quota(problems)
            finally:
                self._dirty_keys = None
                root.attrs["path"] = self._pass_path
            # the preemption pass runs BEFORE the explain capture so a
            # re-solved demander's provenance shows its final placement. A
            # failed preemption pass logs and leaves the demanders' honest
            # unschedulable results intact — never the wave.
            if self.preempt_source is not None and problems:
                try:
                    results = self._preempt_pass(list(problems), results)
                except Exception as exc:  # noqa: BLE001 — scarcity remedy is
                    # optional; losing it must never lose the solve results.
                    # The outcome is cleared too: a pass that died AFTER
                    # victim selection but BEFORE the re-solve must not hand
                    # the controller victims to evict with no demander placed
                    self.last_preemption = None
                    import logging

                    # with the message: a kernel the device refuses to compile
                    # says why only here
                    logging.getLogger("karmada_tpu").warning(
                        "preemption pass failed (%s: %s)",
                        type(exc).__name__, str(exc)[:2000],
                    )
            # the store's enabled gate honors KARMADA_TPU_EXPLAIN_CAP=0:
            # a disabled ring must not pay the capture dispatch either
            if self.explain is not None and self.explain.enabled and problems:
                try:
                    self._capture_explain(list(problems), results)
                except Exception as exc:  # noqa: BLE001 — provenance is
                    # telemetry: losing a capture must never lose the wave
                    import logging

                    logging.getLogger("karmada_tpu").warning(
                        "explain capture failed (%s: %s)",
                        type(exc).__name__, str(exc)[:2000],
                    )
            return results

    def _schedule_quota(
        self, problems: Sequence[BindingProblem]
    ) -> list[ScheduleResult]:
        """Quota admission around the solve, by one of two routes chosen
        from what the pass observes (no option selects one). Disarmed
        quota costs one `is None` check.

        - resident: the batch rides the fleet table WHOLE. The solve takes
          the presented batch, all of it, with the QuotaSnapshot; the
          table admits it from its row state in one kernel
          (FleetTable._admit_on_device) and the result list answers a
          denied row QuotaExceeded from the verdict's bit. A denial is a
          bit beside the row's answer, not the row's absence: the batch
          keeps its length and its list, so a quota generation that moves
          under an unmoved mask_token is an identity pass.
        - partition: a batch with rows off the table (row_rides false, a
          placement the table does not hold, an engine-level feature), a
          batch under the fleet threshold, one of more than
          MAX_ADMIT_ROWS rows, a quota packed over other dims than the
          snapshot's: ONE batched admission kernel over demands the host
          derives row by row partitions the wave (_quota_admission);
          denied bindings answer without being solved, the admitted
          sub-list rides the batched paths. The FIFO prefix is over the
          presented order either way. A batch that turns out to hold
          host rows only inside the prologue pays that prologue twice.

        The wave's budget debit COMMITS only after the solve returned: a
        pass that dies mid-solve (poisoned key, backend error: the worker
        bisects and retries) must not leave its demand charged, or the
        retry re-admits against an already-debited remaining and
        spuriously denies bindings that fit. A failed solve also drops
        both routes' cached verdicts."""
        q = self.quota
        if q is None or not q.active:
            return self._schedule_inner(problems)
        try:
            if self._quota_rides_table(problems, q):
                res = self._schedule_inner(problems, quota=q)
                if res is not None:
                    fleet = self._fleet
                    debit, fleet.quota_debit = fleet.quota_debit, None
                    self._apply_quota_debit(debit)
                    self._quota_cache = None
                    return res
            return self._schedule_partitioned(problems, q)
        except BaseException:
            self._quota_cache = None
            if self._fleet is not None:
                self._fleet._quota_verdict = None
            raise

    def _quota_rides_table(self, problems, q) -> bool:
        """What can be told before the prologue of whether the fleet table
        can admit this batch from its row state: the batch is one the
        fleet takes, one admission holds its rows, and the quota's demand
        dims are the profile slots' (the snapshot's)."""
        from ..ops.quota import MAX_ADMIT_ROWS

        return (
            self.fleet_threshold <= len(problems) <= MAX_ADMIT_ROWS
            and not self.custom_filters
            and not self.disabled_plugins
            and not self._host_only_estimators()
            and list(q.dims) == list(self.snapshot.dims)
        )

    def _schedule_partitioned(self, problems, q) -> list[ScheduleResult]:
        """The partition route of _schedule_quota: host admission, the
        solve of the admitted sub-list, the merge."""
        from ..utils.tracing import tracer as _tracer

        n = len(problems)
        with _tracer.span(
            "scheduler.quota", rows=n, host_rows=n, generation=q.generation
        ) as sp:
            part, debit, quota_rows, dispatched = self._quota_admission(
                problems
            )
            denied_n = len(part[1]) if part is not None else 0
            sp.attrs.update(
                quota_rows=quota_rows, denied=denied_n,
                dispatched=int(dispatched),
            )
        self._quota_tally.add(
            "partition" if dispatched else "replayed", n, quota_rows, denied_n
        )
        if part is None:
            res = self._schedule_inner(problems)
            self._apply_quota_debit(debit)
            return res
        sub, denied = part
        sub_res = self._schedule_inner(sub)
        self._apply_quota_debit(debit)
        results: list = [None] * n
        for i, res in denied:
            results[i] = res
        it = iter(sub_res)
        for i in range(n):
            if results[i] is None:
                results[i] = next(it)
        return results

    def _apply_quota_debit(self, debit) -> None:
        """Commit one admitted wave's demand against the working
        remaining (see QuotaSnapshot: debit within a generation, rebuilt
        from recomputed usage at the next). None = nothing to commit
        (cache replay, or no quota'd rows)."""
        if debit is None:
            return
        from ..ops.quota import UNLIMITED as _UNL

        q = self.quota
        limited = q.remaining < _UNL
        q.remaining = np.where(
            limited, np.maximum(q.remaining - debit, 0), q.remaining
        )

    # -- scarcity plane: plane-wide preemption (ISSUE 14) -------------------

    _PREEMPT_PAD = 256  # pow2 floor so tiny waves share one trace bucket

    def _preempt_pass(self, problems, results) -> list:
        """One armed-only preemption round per engine pass: demanders are
        the wave's priority>0 rows whose solve answered insufficient
        capacity AND that quota ADMITTED (a quota-denied row may never
        preempt its way past its namespace budget); victims come from the
        controller-wired resident pool. Victim selection is ONE
        ``ops.preempt.preempt_select`` dispatch over the combined rows;
        the freed per-cluster capacity re-enters the divide path in the
        same pass via ``_resolve_boosted``, and the outcome (victims to
        evict, re-solved placements) lands in ``last_preemption``.

        Returns the results list — MATERIALIZED to a plain list when a
        re-solve patched demander rows (the all-fleet path answers a
        lazy column-oriented ``_FleetResultList`` that rejects item
        assignment), the caller's original object otherwise."""
        import time as _time

        from ..ops.quota import DEMAND_CLAMP
        from ..utils.tracing import tracer as _tracer

        demand_idx = [
            i
            for i, (p, res) in enumerate(zip(problems, results))
            if getattr(p, "priority", 0) > 0
            and res.error == INSUFFICIENT_ERROR
        ]
        if not demand_idx:
            return results
        t0 = _time.perf_counter()
        snap = self.snapshot
        wave_keys = {p.key for p in problems}
        victims_pool = [
            v
            for v in (self.preempt_source(wave_keys) or ())
            if v.prev and sum(v.prev.values()) > 0
        ]
        outcome = PreemptionOutcome()
        self.last_preemption = outcome
        if not victims_pool:
            outcome.still_unschedulable = [
                problems[i].key for i in demand_idx
            ]
            return results
        dims = list(snap.dims)
        r = len(dims)
        c = snap.num_clusters
        demanders = [problems[i] for i in demand_idx]
        rows = demanders + victims_pool
        b = len(rows)
        prio = np.fromiter(
            (getattr(p, "priority", 0) for p in rows), np.int32, b
        )
        demand = np.zeros((b, r), np.int64)
        freed = np.zeros((b, r), np.int64)
        victim_ok = np.zeros(b, bool)
        weight = np.zeros(b, np.int32)
        assigned = np.zeros((b, c), np.int32)
        requests = np.zeros((b, r), np.int64)
        from .quota import per_replica_vector

        def scaled(req_row, count: int) -> np.ndarray:
            # scale in PYTHON ints (the quota demand_row rule): an
            # absurd-but-legal request x a huge count must clamp, not
            # wrap int64 to zero/negative and vanish from the cumsum
            return np.fromiter(
                (min(int(v) * count, DEMAND_CLAMP) for v in req_row),
                np.int64,
                len(req_row),
            )

        for i, p in enumerate(rows):
            req = per_replica_vector(p.requests, dims)
            requests[i] = np.minimum(req, DEMAND_CLAMP)
            if i < len(demanders):
                # unmet demand: the shortfall the divide could not cover
                # (fresh rows re-place everything, so the whole request
                # is unmet; scale-ups demand only the delta — the quota
                # plane's delta-demand rule)
                short = p.replicas - (
                    0 if p.fresh else sum(p.prev.values())
                )
                if short > 0:
                    demand[i] = scaled(requests[i], int(short))
            else:
                total = 0
                for name, reps in p.prev.items():
                    j = snap.index.get(name)
                    if j is not None and reps > 0:
                        assigned[i, j] = reps
                        total += int(reps)
                if total > 0:
                    weight[i] = min(total, 2**20 - 1)
                    victim_ok[i] = True
                    freed[i] = scaled(requests[i], total)
        if not demand.any() or not victim_ok.any():
            outcome.still_unschedulable = [p.key for p in demanders]
            return results

        # pow2 row padding bounds the trace count (pad rows are
        # priority-0 non-demander non-victims — inert by construction)
        b_pad = max(1 << max(0, (b - 1).bit_length()), self._PREEMPT_PAD)

        def pad(a):
            if b_pad == b:
                return a
            w = ((0, b_pad - b),) + ((0, 0),) * (a.ndim - 1)
            return np.pad(a, w)

        from ..ops.preempt import preempt_select
        from ..parallel.mesh import mesh_shape

        mesh = self.mesh
        if mesh is not None and b_pad % max(mesh.shape.get("b", 1), 1):
            mesh = None  # non-divisible batch: single-device semantics
        mesh_el = mesh_shape(mesh)
        arrays = tuple(
            jnp.asarray(a)
            for a in (
                pad(prio), pad(demand), pad(freed), pad(victim_ok),
                pad(weight), pad(assigned), pad(requests),
            )
        )
        key = ("P", int(b_pad), int(c), int(r), mesh_el)
        if self._mark_trace(*key):
            # recorded meshed too: preempt_select carries a real mesh
            # static (the explain_pass contract), so replay can
            # materialize the shape
            self._record_trace(
                "preempt_select", key, arrays, mesh=mesh_el
            )
        victims_dev, freed_caps_dev = preempt_select(*arrays, mesh=mesh)
        victim_mask = np.asarray(victims_dev)[:b]
        freed_caps = np.asarray(freed_caps_dev)
        if not victim_mask.any():
            outcome.still_unschedulable = [p.key for p in demanders]
            _tracer.record(
                "scheduler.preempt", _time.perf_counter() - t0, start=t0,
                demanders=len(demanders), victims=0,
            )
            return results
        for i in np.flatnonzero(victim_mask):
            p = rows[int(i)]
            outcome.victims.append(
                (p.key, dict(p.prev), int(getattr(p, "priority", 0)))
            )
        outcome.freed_caps = freed_caps

        # freed capacity re-enters the divide path NOW: one extra batched
        # solve over just the demanders, against availability recomputed
        # on boosted capacity (still min-folded with static quota caps —
        # preemption never lifts a cap)
        compiled = [self._compiled(p.placement) for p in demanders]
        self.solve_batches += 1
        re_res = self._resolve_boosted(demanders, compiled, freed_caps)
        # the all-fleet path answers a lazy _FleetResultList: patch a
        # materialized copy (iteration decodes each row exactly once)
        results = list(results)
        for i, res in zip(demand_idx, re_res):
            if res.success:
                results[i] = res
                outcome.placed.append(res.key)
            else:
                outcome.still_unschedulable.append(res.key)
        _tracer.record(
            "scheduler.preempt", _time.perf_counter() - t0, start=t0,
            demanders=len(demanders), victims=len(outcome.victims),
        )
        return results

    def _resolve_boosted(self, problems, compiled, freed_caps):
        """Re-solve a (small) demander batch against capacity boosted by
        the victims' freed resources: the general/model estimator mirror
        runs over ``available_cap + freed_caps`` (out-of-tree estimator
        answers are deliberately NOT consulted — they estimate from live
        member state, which cannot see a not-yet-evicted victim's
        capacity), static quota caps still fold, and the divide runs the
        oracle-identical numpy path when host-small (the
        ``_schedule_chunk`` bound) else the device kernels."""
        from ..ops import masks as mops
        from ..ops.divide import AGGREGATED as S_AGG, DYNAMIC_WEIGHT as S_DYN

        snap = self.snapshot
        out: list[ScheduleResult] = []
        for start in range(0, len(problems), self.chunk_size):
            chunk = problems[start : start + self.chunk_size]
            cchunk = compiled[start : start + self.chunk_size]
            base, strategy, replicas, static_w, requests, prev, fresh = (
                self._pack_chunk(chunk, cchunk, 0, with_affinity=False)
            )
            b = len(chunk)
            mi = 2**31 - 1
            # boosted availability: the host_profile_table mirror over a
            # capacity-shifted view of the snapshot (sentinel semantics
            # identical to _availability_np)
            boosted = _BoostedSnapshot(snap, freed_caps)
            uniq, inv = np.unique(requests, axis=0, return_inverse=True)
            dense = host_profile_table(
                boosted, uniq, models_active=self._models_active()
            )[inv]
            cap_rows = self._quota_cap_rows(chunk)
            if cap_rows is not None:
                dense = np.minimum(
                    dense, self._quota_caps_np(cap_rows, requests)
                )
            reps_col = replicas.astype(np.int64)[:, None]
            avail = np.where(reps_col == 0, mi, dense)
            avail = np.where(avail == mi, reps_col, avail)
            avail = np.minimum(avail, mi).astype(np.int32)

            # ordered-affinity selection on the boosted numbers (the
            # ranked path's exact predicate)
            cp_slot: dict[int, int] = {}
            unique_cps: list[CompiledPlacement] = []
            cp_idx = np.zeros(b, np.int32)
            for i, cp in enumerate(cchunk):
                slot = cp_slot.get(id(cp))
                if slot is None:
                    slot = len(unique_cps)
                    cp_slot[id(cp)] = slot
                    unique_cps.append(cp)
                cp_idx[i] = slot
            tmax = max(len(cp.terms) for cp in unique_cps)
            term_stack = np.zeros((len(unique_cps), tmax, snap.num_clusters), bool)
            term_len_u = np.ones(len(unique_cps), np.int32)
            for u, cp in enumerate(unique_cps):
                term_len_u[u] = len(cp.terms)
                for t, (_name, mask) in enumerate(cp.terms):
                    term_stack[u, t] = mask
            if "ClusterAffinity" in self.disabled_plugins:
                term_stack[:] = True
            cand_tc = base[:, None, :] & term_stack[cp_idx]
            rank, _fit = mops.first_fit_group(
                cand_tc,
                term_len_u[cp_idx],
                avail.astype(np.int64),
                replicas.astype(np.int64),
                prev.astype(np.int64),
                (strategy == S_DYN) | (strategy == S_AGG),
                fresh.astype(bool),
            )
            feasible = np.take_along_axis(
                cand_tc, rank[:, None, None].astype(np.intp), axis=1
            )[:, 0, :]
            candidates = self._select_for_chunk(
                chunk, cchunk, feasible, avail, prev
            )
            wmax = int(
                max(
                    int(avail.max(initial=0)) + int(prev.max(initial=0)),
                    int(static_w.max(initial=0)),
                    0,
                )
            )
            lmax = int(prev.max(initial=0)) + 1
            if (wmax + 1) * lmax * snap.num_clusters < 2**63:
                from ..refimpl.divider_np import assign_batch_np

                assignment, unschedulable = assign_batch_np(
                    strategy, replicas, candidates, static_w,
                    avail, prev, fresh,
                )
            else:
                res = self._assign(
                    strategy, replicas, candidates, static_w,
                    jnp.asarray(avail), prev, fresh,
                )
                assignment = np.asarray(res.assignment)
                unschedulable = np.asarray(res.unschedulable)
            out.extend(
                self._unpack(chunk, cchunk, rank, candidates,
                             assignment, unschedulable)
            )
        return out

    # -- placement provenance (ISSUE 13) -----------------------------------

    def _capture_explain(self, problems, results) -> None:
        """One armed-only provenance dispatch per chunk: compose the
        per-stage masks host-side (the same algebra ``_pack_chunk``
        feeds the solve, kept PER STAGE instead of AND-folded), run the
        ``ops.explain.explain_pass`` kernel, and deposit the capture in
        the process-wide ExplainStore under the current wave."""
        import time as _time

        from ..utils.tracing import tracer as _tracer

        t0 = _time.perf_counter()
        wave = _tracer.current_context().wave
        rows = 0
        for start in range(0, len(problems), self.chunk_size):
            chunk = problems[start : start + self.chunk_size]
            res = results[start : start + self.chunk_size]
            self.explain.add(self._explain_chunk(chunk, res, wave))
            rows += len(chunk)
        _tracer.record(
            "scheduler.explain", _time.perf_counter() - t0, start=t0,
            rows=rows,
        )

    def _explain_chunk(self, problems, results, wave: int):
        """Build one chunk's ExplainCapture. Stage masks carry the
        solve's exact leniency rules (already-placed taint/API leniency,
        evictions folded into the taint/NoExecute stage, the spread
        selection where the row has one) so a bit here means "this
        stage excluded this cluster in THIS pass". Out-of-tree custom
        filters are engine-level host hooks with no stage identity and
        are not attributed."""
        from ..ops import masks as mops
        from ..ops.divide import AGGREGATED as S_AGG, DYNAMIC_WEIGHT as S_DYN
        from ..ops.explain import explain_pass, topk_width
        from ..utils.explainstore import ExplainCapture
        from .quota import QUOTA_EXCEEDED_ERROR

        snap = self.snapshot
        disabled = self.disabled_plugins
        compiled = [self._compiled(p.placement) for p in problems]
        b, c = len(problems), snap.num_clusters

        cp_slot: dict[int, int] = {}
        unique_cps: list[CompiledPlacement] = []
        cp_idx = np.empty(b, np.int32)
        for i, cp in enumerate(compiled):
            slot = cp_slot.get(id(cp))
            if slot is None:
                slot = len(unique_cps)
                cp_slot[id(cp)] = slot
                unique_cps.append(cp)
            cp_idx[i] = slot
        spread_pl = np.stack([cp.spread_field_ok for cp in unique_cps])
        taint_pl = np.stack([cp.taint_ok for cp in unique_cps])

        gvk_slot: dict[str, int] = {}
        gvk_masks: list[np.ndarray] = []
        gvk_idx = np.empty(b, np.int32)
        for i, p in enumerate(problems):
            slot = gvk_slot.get(p.gvk)
            if slot is None:
                slot = len(gvk_masks)
                gvk_slot[p.gvk] = slot
                gid = snap.gvk_vocab.get(p.gvk) if p.gvk else None
                if gid is None:
                    m = (
                        np.zeros(c, bool)
                        if p.gvk and len(snap.gvk_vocab) > 0
                        else np.ones(c, bool)
                    )
                else:
                    word, bit_ = gid // 32, gid % 32
                    m = (snap.gvk_bits[:, word] >> np.uint32(bit_)) & 1 != 0
                gvk_masks.append(m)
            gvk_idx[i] = slot
        api_gvk = np.stack(gvk_masks)

        replicas = np.fromiter((p.replicas for p in problems), np.int32, b)
        fresh = np.fromiter((p.fresh for p in problems), bool, b)
        strategy = np.fromiter(
            (cp.strategy for cp in compiled), np.int32, b
        )
        r = len(snap.dims)
        prev = np.zeros((b, c), np.int32)
        evict = np.zeros((b, c), bool)
        preempted = np.zeros((b, c), bool)
        requests = np.zeros((b, r), np.int64)
        dim_index = {d: j for j, d in enumerate(snap.dims)}
        pods_dim = dim_index.get("pods")
        for i, p in enumerate(problems):
            for name, reps in p.prev.items():
                j = snap.index.get(name)
                if j is not None:
                    prev[i, j] = reps
            for name in p.evict_clusters:
                j = snap.index.get(name)
                if j is not None:
                    evict[i, j] = True
            for name in getattr(p, "preempt_clusters", ()):
                j = snap.index.get(name)
                if j is not None:
                    preempted[i, j] = True
            for d, q in p.requests.items():
                j = dim_index.get(d)
                if j is not None:
                    requests[i, j] = q
            if pods_dim is not None and p.replicas > 0:
                requests[i, pods_dim] = max(requests[i, pods_dim], 1)
        prev_mask = prev > 0

        taint_tol = taint_pl[cp_idx] | prev_mask
        if "TaintToleration" in disabled:
            taint_tol = np.ones((b, c), bool)
        if "ClusterEviction" in disabled:
            evict = np.zeros((b, c), bool)
        taint_ok = taint_tol & ~evict
        api_ok = api_gvk[gvk_idx] | (
            prev_mask & ~snap.complete_enablements[None, :]
        )
        if "APIEnablement" in disabled:
            api_ok = np.ones((b, c), bool)
        spread_ok = spread_pl[cp_idx]
        if "SpreadConstraint" in disabled:
            spread_ok = np.ones((b, c), bool)
        else:
            # spread rows with a selection: the Select stage's surviving
            # set IS the selection mask (placement-pinned row cache). Rows
            # the device selected have no cached one: the capture computes
            # the host selection for the rows it is asked about
            spread_rows = [
                i for i, cp in enumerate(compiled) if cp.spread_single_term
            ]
            if spread_rows and not self.extra_estimators:
                self._select_spread_rows(problems, compiled, spread_rows)
            for i, (p, cp) in enumerate(zip(problems, compiled)):
                if cp.spread_single_term:
                    hit = self._row_selections.get(p.key)
                    if (
                        hit is not None
                        and hit[0][0] == self._snapshot_gen
                        and hit[1] is p.placement
                        and hit[2] is not None
                    ):
                        sel = np.unpackbits(
                            np.frombuffer(hit[2], np.uint8),
                            bitorder="little",
                        )[:c].astype(bool)
                        spread_ok[i] = spread_ok[i] & sel

        # pre-cap merged availability: the host mirror when exact, the
        # device merge (without the cap estimator — the cap is its own
        # stage) when out-of-tree estimators are registered
        if self.extra_estimators:
            avail = np.asarray(
                self._availability(requests, replicas, None)
            ).astype(np.int32)
        else:
            avail = self._availability_np(requests, replicas, None)
        mi = np.int32(2**31 - 1)
        cap_rows = self._quota_cap_rows(problems)
        caps = (
            self._quota_caps_np(cap_rows, requests).astype(np.int32)
            if cap_rows is not None
            else np.full((b, c), mi, np.int32)
        )

        dynamic = (strategy == S_DYN) | (strategy == S_AGG)
        admitted = np.fromiter(
            (res.error != QUOTA_EXCEEDED_ERROR for res in results), bool, b
        )
        assignment = np.zeros((b, c), np.int32)
        for i, res in enumerate(results):
            for name, n_assigned in res.clusters.items():
                j = snap.index.get(name)
                if j is not None:
                    assignment[i, j] = n_assigned

        # selected affinity group: the tensorized ordered-failover
        # selection (ops.masks.first_fit_group — the ranked path's exact
        # predicate), so a displaced binding's capture records WHICH
        # fallback group it landed on. The SELECTION consumes the same
        # cap-folded availability the ranked solve ranks groups on
        # (_schedule_chunk_ranked passes cap_rows into _availability) —
        # only the kernel's per-stage avail input stays pre-cap, because
        # the cap is its own stage bit there.
        tmax = max(len(cp.terms) for cp in unique_cps)
        if tmax > 1 and "ClusterAffinity" not in disabled:
            if cap_rows is None:
                avail_rank = avail
            elif self.extra_estimators:
                avail_rank = np.asarray(
                    self._availability(requests, replicas, cap_rows)
                ).astype(np.int32)
            else:
                avail_rank = self._availability_np(
                    requests, replicas, cap_rows
                )
            term_stack = np.zeros((len(unique_cps), tmax, c), bool)
            term_len_u = np.ones(len(unique_cps), np.int32)
            for u, cp in enumerate(unique_cps):
                term_len_u[u] = len(cp.terms)
                for t, (_name, m) in enumerate(cp.terms):
                    term_stack[u, t] = m
            base = taint_ok & api_ok & spread_ok
            cand_tc = base[:, None, :] & term_stack[cp_idx]
            rank, _fit = mops.first_fit_group(
                cand_tc,
                term_len_u[cp_idx],
                avail_rank.astype(np.int64),
                replicas.astype(np.int64),
                prev.astype(np.int64),
                dynamic.astype(bool),
                fresh.astype(bool),
            )
            group_rank = rank.astype(np.int32)
            aff_ok = np.take_along_axis(
                term_stack[cp_idx],
                rank[:, None, None].astype(np.intp),
                axis=1,
            )[:, 0, :]
        else:
            group_rank = np.zeros(b, np.int32)
            aff_ok = np.stack(
                [cp.terms[0][1] for cp in unique_cps]
            )[cp_idx]
            if "ClusterAffinity" in disabled:
                aff_ok = np.ones((b, c), bool)

        # pow2 row padding bounds the trace count (the admission-kernel
        # discipline); pad rows are zero-replica all-excluded and are
        # sliced off before the capture
        b_pad = 1 << max(0, (b - 1).bit_length())
        b_pad = min(max(b_pad, b), max(self.chunk_size, b))
        pad = b_pad - b

        def pad_rows(a, value=0):
            if pad == 0:
                return a
            width = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
            return np.pad(a, width, constant_values=value)

        k = topk_width(c)
        mesh = self.mesh
        if mesh is not None and b_pad % max(mesh.shape.get("b", 1), 1):
            mesh = None  # non-divisible batch: single-device semantics
        shard_c = bool(self.shard_clusters and mesh is not None)
        arrays = tuple(
            jnp.asarray(a)
            for a in (
                pad_rows(aff_ok), pad_rows(taint_ok), pad_rows(api_ok),
                pad_rows(spread_ok), pad_rows(avail), pad_rows(caps),
                pad_rows(admitted, True), pad_rows(dynamic),
                pad_rows(replicas), pad_rows(assignment), pad_rows(prev),
                pad_rows(preempted),
            )
        )
        from ..parallel.mesh import mesh_shape

        mesh_el = mesh_shape(mesh)
        key = ("E", int(b_pad), int(c), int(k), mesh_el, shard_c)
        if self._mark_trace(*key):
            # recorded meshed too: explain_pass carries a real mesh
            # static (the fleet-kernel contract), so replay can
            # materialize the shape — unlike the static-less quota keys
            self._record_trace(
                "explain_pass", key, arrays,
                k=k, mesh=mesh_el, shard_c=shard_c,
            )
        mask_dev, topk_dev = explain_pass(
            *arrays, k=k, mesh=mesh, shard_c=shard_c
        )
        return ExplainCapture(
            wave=wave,
            names=snap.names,
            keys=[p.key for p in problems],
            masks=np.asarray(mask_dev)[:b],
            topk=np.asarray(topk_dev)[:b],
            group_rank=group_rank,
            errors=[res.error for res in results],
            assignment=assignment,
        )

    def _visit_moved(
        self, problems, positions: list, fc: list, spread_rides: bool = False
    ) -> tuple:
        """THE per-position loop over the moved positions (_moved_pass):
        each of ``positions`` gets its compiled placement under the current
        snapshot (a look-up; a placement new to the cache compiles) written
        into ``fc``, and is held to the fleet-eligibility predicate the
        walk applies (cp.fleet_terms, or a spread-constrained single-term
        row where ``spread_rides``: the pass has the fleet table select for
        it; and fleet.row_rides). Returns (positions visited, whether each
        of them rides the fleet): the loop ends at the first that does
        not."""
        from .fleet import row_rides

        # one look-up a placement, not a position (the positions pin
        # their placements, so no id comes twice in a pass)
        seen: dict = {}
        for visited, pos in enumerate(positions, 1):
            p = problems[pos]
            cp = seen.get(id(p.placement))
            if cp is None:
                cp = seen[id(p.placement)] = self._compiled(p.placement)
            if not (
                (cp.fleet_terms or (spread_rides and cp.spread_single_term))
                and row_rides(p, cp)
            ):
                return visited, False
            fc[pos] = cp
        return len(positions), True

    def _moved_pass(self, problems, rec, moved, pack) -> Optional[tuple]:
        """The prologue's work for the positions the diff found moved
        against the armed batch ``rec`` (a minority), the others spared:
        what the prologue derives a position from is its problem OBJECT
        (pinned by the record, never mutated in place) and its PLACEMENT
        (strategy, the term count and the spread constraints are functions
        of the placement alone): which placement the row names and whether
        it rides the fleet. Its compiled placement stands too where the
        armed compiled list does (the generation it was made at, or the
        same ``mask_token``: only availability drifted), and the list is
        copied; where the token moved (a taint, a label: update_snapshot
        cleared the cache) every distinct placement of the armed batch is
        compiled anew (one compile each) and the list is a take over the
        record's position -> placement index. Either way the moved
        positions go through _visit_moved, the index is corrected at them,
        and the spread rows' select positions are read from it.

        Returns (fp, fc, the spread rows' positions | None, the batch by
        placement) for the table, or None where the walk has to run: a
        placement's flags differ under the new snapshot, spread rows the
        device cannot select for (regions past R_CAP, extra estimators)
        under a moved generation, a moved position that leaves the fleet or
        whose selection the device cannot make. For a hand-off records the
        prologue's three stages (their ``rows``: the positions visited)
        under the open scheduler.pack span."""
        import time as _time

        from ..utils.metrics import fleet_host_path_rows
        from ..utils.tracing import tracer as _tracer
        from .select import regions_fit

        n = len(problems)
        k = int(moved.size)
        t_compile = _time.perf_counter()
        built = rec.placements
        if built is None:
            old = rec.compiled
            _, first, index = np.unique(
                np.fromiter(map(id, old), np.int64, n),
                return_index=True, return_inverse=True,
            )
            cps = [old[i] for i in first.tolist()]
            built = (
                [cp.placement for cp in cps],
                [_placement_flags(cp) for cp in cps],
                index,
            )
        placements, flags, index = built
        # a compile each where the token moved, a look-up where it stands
        cps = [self._compiled(pl) for pl in placements]
        gen_stands = rec.gen == self._snapshot_gen
        spread_rides = not self.extra_estimators and regions_fit(
            self.snapshot
        )
        if [_placement_flags(cp) for cp in cps] != flags or (
            # the host's selections follow the capacities
            not spread_rides and not gen_stands and any(f[1] for f in flags)
        ):
            return None
        if gen_stands or rec.token == self.snapshot.mask_token:
            # the armed list holds the cache's compiled placements
            fc = list(rec.compiled)
        else:
            table = np.empty(len(cps), object)
            table[:] = cps
            fc = table[index].tolist()
        t_eligible = _time.perf_counter()
        positions = moved.tolist()
        if not self._visit_moved(problems, positions, fc, spread_rides)[1]:
            return None
        # the index, corrected at the moved positions (a new array: the
        # armed one stands until the re-arm)
        slot = {id(cp): j for j, cp in enumerate(cps)}
        at = list(map(fc.__getitem__, positions))
        slots = list(map(slot.get, map(id, at)))
        joined = None in slots
        if joined:
            # a placement the armed batch did not name joins the distinct
            # ones for this pass; the next diff builds its index anew
            flags = list(flags)
            for j, cp in enumerate(at):
                if slots[j] is None:
                    slots[j] = slot.setdefault(id(cp), len(flags))
                    if slots[j] == len(flags):
                        flags.append(_placement_flags(cp))
        index = index.copy()
        index[moved] = slots
        built = None if joined else (placements, flags, index)
        t_spread = _time.perf_counter()
        select = None
        spread = np.fromiter((f[1] for f in flags), bool, len(flags))
        if spread_rides and spread.any():
            select = np.flatnonzero(spread[index])
            self._report_host_selected(0)
        # a list of its own: the caller's may change under the armed batch
        fp = list(problems)
        fleet_host_path_rows.set(0)
        t_end = _time.perf_counter()
        self.last_breakdown = {
            "compile": t_eligible - t_compile,
            "eligible": t_spread - t_eligible,
            "select": t_end - t_spread,
        }
        pack.attrs.update(rows=k, kept=n - k)
        n_select = 0 if select is None else int(select.size)
        _tracer.record(
            "scheduler.compile", t_eligible - t_compile, start=t_compile,
            rows=k, placements=len(cps),
        )
        _tracer.record(
            "scheduler.eligible", t_spread - t_eligible, start=t_eligible,
            rows=k, fleet_rows=n,
        )
        _tracer.record(
            "scheduler.spread", t_end - t_spread, start=t_spread,
            rows=n_select, on_device=int(n_select > 0),
        )
        return fp, fc, select, built

    def _schedule_inner(
        self, problems: Sequence[BindingProblem], quota=None
    ) -> Optional[list[ScheduleResult]]:
        """The solve of a batch by one of the routes schedule() names.

        ``quota`` (the resident route of _schedule_quota) is the
        QuotaSnapshot the fleet table admits the batch against: it rides
        with every fleet pass below, and where the batch does not ride the
        table whole (rows on the host path, no fleet pass) nothing is
        solved and None returned: the caller partitions the batch."""
        import time as _time

        from ..utils.tracing import tracer as _tracer

        # engine-level features that the device-resident path does not
        # model force the general host path for the whole batch
        fleet_ok = not (
            self.custom_filters
            or self._host_only_estimators()
            or self.disabled_plugins
        )
        # a batch of the armed batch's length is diffed against it by
        # object identity (ResidentBatch.diff), once a pass
        rec = self._fleet.batch if fleet_ok and self._fleet else None
        if rec is not None and not (
            rec.armed and len(rec.problems) == len(problems)
        ):
            rec = None
        diff = None
        if rec is not None and (
            rec.gen == self._snapshot_gen
            # availability-only drift keeps every compiled mask and the
            # eligibility partition valid (placements key on filter
            # fields = mask_token), and the fleet table re-selects its
            # spread rows on the device in every pass, so batches reuse
            # across the swap — churn passes skip the prologue too
            # (the token is None for a batch with host-selected rows:
            # those follow the capacities)
            or rec.token == self.snapshot.mask_token
        ):
            # batch-identity fast path: a storm re-scheduling the SAME
            # problem objects is pure in those inputs — compilation and the
            # eligibility partition key on object identity + the
            # snapshot's filter fields, and the spread selection is the
            # fleet table's own stage (_fleet_select, in every pass), so
            # one id() sweep (~8ms at 100k) replaces the ~55ms host
            # prologue. Like the table's own reuse, it assumes problem
            # objects are not mutated in place between passes.
            diff = rec.diff(problems, self._dirty_keys, True)
            if diff.hit:
                self.last_breakdown = {"compile": diff.took}
                self._pass_path = "identity"
                self.solve_batches += 1
                res = self._fleet.schedule(
                    rec.problems, rec.compiled, quota=quota
                )
                self.last_breakdown.update(self._fleet.last_breakdown)
                return res

        from contextlib import nullcontext

        # the host prologue (placement compile + spread selection +
        # eligibility partition) is the wave tree's "pack" phase: one
        # span, with its three stages as children at the intervals
        # last_breakdown times, so a storm's pass decomposes into pack /
        # handoff / solve(dispatch/device/fetch) / rearm under
        # scheduler.schedule. ``rows`` are the positions the prologue
        # visited, ``kept`` those a diff spared it
        swap = None
        with (
            _tracer.span("scheduler.pack", rows=len(problems), kept=0)
            if fleet_ok
            else nullcontext()
        ) as pack:
            if (
                rec is not None
                # host-selected rows under a moved generation: the walk
                and (diff is not None or rec.token is not None)
                and not self._fleet.slots_exhausted
            ):
                if diff is None:
                    # the generation moved under a moved mask_token: the
                    # diff is the prologue's first stage
                    diff = rec.diff(problems, self._dirty_keys, False)
                if diff.few:
                    swap = self._moved_pass(problems, rec, diff.moved, pack)
            if swap is None:
                t0 = _time.perf_counter()
                compiled = [self._compiled(p.placement) for p in problems]
                took = _time.perf_counter() - t0
                self.last_breakdown = {"compile": took}
            if fleet_ok and swap is None:
                _tracer.record(
                    "scheduler.compile", took, start=t0, rows=len(problems)
                )
                from .fleet import row_rides

                # spread-constraint rows ride the fleet too: their
                # selection is ROW STATE of the fleet table (one packed
                # mask a row, ANDed into the row's feasibility), computed
                # by the table's own kernel from its resident state
                # (_fleet_select) wherever the snapshot's regions fit the
                # kernel's subset table, FitError rows included; past
                # that the host selects, and the rows it accepts ride
                # with their masks uploaded. Batches with extra
                # estimators keep their spread rows off the fleet
                from .select import regions_fit

                # a with block, so that the host's SelectClusters span
                # (_select_spread_rows) nests under it
                with _tracer.span("scheduler.spread") as spread:
                    spread_idx = [] if self.extra_estimators else [
                        i for i, cp in enumerate(compiled)
                        if cp.spread_single_term
                    ]
                    on_device = bool(spread_idx) and regions_fit(
                        self.snapshot
                    )
                    if spread_idx:
                        self._report_host_selected(
                            0 if on_device else len(spread_idx)
                        )
                    sel_idx, sel_bits = self._select_spread_rows(
                        problems, compiled, [] if on_device else spread_idx
                    )
                    if on_device:
                        sel_idx = np.asarray(spread_idx, np.int64)
                    selected = frozenset(sel_idx.tolist())
                    spread.attrs.update(
                        rows=len(spread_idx), on_device=int(on_device)
                    )
                self.last_breakdown["select"] = spread.duration

                t0 = _time.perf_counter()
                # THE fleet-eligibility predicate, from what the code
                # observes: the placement half precomputed as
                # cp.fleet_terms (at most T_CAP ordered affinity terms, and
                # no spread constraints beside several of them; a
                # spread-constrained single-term row passes through the
                # selection it was given), the per-binding half
                # fleet.row_rides (at most K_EVICT eviction tasks, a
                # Divided row's counts within a cell of the table; any
                # previous result): the one expression _visit_moved
                # applies too.
                # Rows past it take the host path, row by row, below
                fast_idx = [
                    i
                    for i, (p, cp) in enumerate(zip(problems, compiled))
                    if (cp.fleet_terms or i in selected) and row_rides(p, cp)
                ]
                why = self._report_host_path(problems, compiled, fast_idx)
                took = _time.perf_counter() - t0
                self.last_breakdown["eligible"] = took
                eligible = _tracer.record(
                    "scheduler.eligible", took, start=t0,
                    rows=len(problems), fleet_rows=len(fast_idx),
                )
                if why is not None:
                    eligible.attrs["wide_rows"] = why["replicas"]
        if fleet_ok and (
            swap is not None or len(fast_idx) >= self.fleet_threshold
        ):
            selections = placed = None
            if swap is not None:
                fp, fc, select, placed = swap
            else:
                from .fleet import FleetTable

                if self._fleet is not None and self._fleet.slots_exhausted:
                    import sys as _sys

                    from ..utils.metrics import fleet_table_rebuilds

                    fleet_table_rebuilds.inc()
                    print(
                        "# fleet table rebuild: "
                        + self._fleet.exhaustion_summary(),
                        file=_sys.stderr,
                        flush=True,
                    )
                    self._fleet = None
                if self._fleet is None:
                    self._fleet = FleetTable(self)
                fp = [problems[i] for i in fast_idx]
                fc = [compiled[i] for i in fast_idx]
                select = None
                if selected:
                    # the selected rows' positions in the fleet batch (a
                    # selected row another clause sent to the host path
                    # takes its selection there itself)
                    fast_arr = np.asarray(fast_idx, np.int64)
                    pos = np.searchsorted(fast_arr, sel_idx)
                    pos = np.minimum(pos, len(fast_arr) - 1)
                    rides = fast_arr[pos] == sel_idx
                    if on_device:
                        select = pos[rides]
                    else:
                        selections = (pos[rides], sel_bits[rides])
            host_rows = len(problems) - len(fp)
            if host_rows and quota is not None:
                return None  # admission is over the presented batch
            # the table visits the diff's moved positions alone (and may
            # replay the rest) where the batch it gets is the one diffed
            moved = None if diff is None or host_rows else diff.moved
            self.solve_batches += 1
            kept, visited = self._prologue_tally
            kept.inc(pack.attrs["kept"])
            visited.inc(pack.attrs["rows"])
            # from the prologue's end to the table's door: the two
            # comprehensions, a rebuild, the selected rows' positions
            _tracer.record(
                "scheduler.handoff", _time.perf_counter() - pack.end,
                start=pack.end, rows=len(fp),
            )
            fast_res = self._fleet.schedule(
                fp, fc, moved, selections=selections, select=select,
                host_rows=host_rows, quota=quota,
            )
            self._pass_path = "delta" if self._fleet.replayed else "full"
            # from the table's answer to the engine's: arming the batch the
            # table holds (with the pass's sweep; a walk no diff came
            # before makes its own here), or the merge with the host
            # path's rows (scheduler.host its child)
            with _tracer.span(
                "scheduler.rearm", rows=len(problems), host_rows=host_rows
            ):
                self.last_breakdown.update(self._fleet.last_breakdown)
                if not host_rows:
                    # all rows rode the fleet: hand back the lazy
                    # column-oriented result list as-is, and arm the
                    # batch for the next pass's diff. Rows that hold the
                    # host's selections, the walk's or those a moved pass
                    # spared, arm no token
                    held = selections is not None or (
                        swap is not None and rec.token is None
                    )
                    self._fleet.batch.arm(
                        diff.ids if diff is not None
                        else np.fromiter(map(id, fp), np.int64, len(fp)),
                        placed, self._snapshot_gen,
                        None if held else self.snapshot.mask_token,
                    )
                    return fast_res
                results: list = [None] * len(problems)
                for i, res in zip(fast_idx, fast_res):
                    results[i] = res
                slow_idx = [
                    i for i in range(len(problems)) if results[i] is None
                ]
                if slow_idx:
                    slow_res = self._schedule_host(
                        [problems[i] for i in slow_idx],
                        [compiled[i] for i in slow_idx],
                    )
                    for i, res in zip(slow_idx, slow_res):
                        results[i] = res
                return results
        # no fleet pass: an engine-level feature, or fewer eligible rows
        # than the threshold, keeps the whole batch on the host path
        if quota is not None:
            return None
        from ..utils.metrics import fleet_host_path_rows

        fleet_host_path_rows.set(len(problems))
        return self._schedule_host(problems, compiled)

    def _host_only_estimators(self) -> bool:
        """Whether an extra estimator keeps the whole batch on the host
        path: one that cannot be asked BY PROFILE (a bare callable, whose
        answer may follow a row's replicas) has no place in the fleet
        table's fold. A registry's batch estimator can
        (``profile_table``), and its batches ride the fleet."""
        return any(
            not hasattr(est, "profile_table") for est in self.extra_estimators
        )

    def _est_tokens(self) -> tuple:
        """One refresh_token probe per extra estimator (None for
        estimators without the protocol): what the fleet table compares
        before it trusts the answers it folded."""
        tokens = []
        for est in self.extra_estimators:
            probe = getattr(est, "refresh_token", None)
            tokens.append(probe() if probe is not None else None)
        return tuple(tokens)

    def _report_host_path(self, problems, compiled, fast_idx):
        """Say how many rows of the batch leave the fleet table for the
        host path and why: the gauge every batch; where rows leave, the
        counter by reason (added once a pass) and a line on stderr once a
        batch layout. Each of those rows is packed and solved on the host
        in every wave. Returns the counts by reason (HOST_PATH_REASONS),
        or None where the batch rides whole; only the leaving rows are
        looked at."""
        from ..utils.metrics import fleet_host_path_rows

        n = len(problems)
        fleet_host_path_rows.set(n - len(fast_idx))
        if len(fast_idx) == n:
            return None
        from .fleet import K_EVICT, T_CAP, replicas_bound

        leaving = np.ones(n, bool)
        leaving[fast_idx] = False
        why = dict.fromkeys(HOST_PATH_REASONS, 0)
        for i in np.flatnonzero(leaving).tolist():
            why[_host_path_reason(problems[i], compiled[i])] += 1
        for reason, count in why.items():
            if count:
                self._host_path_tally[reason].inc(count)
        told = tuple(why.values())
        if told != self._host_path_told:
            import sys as _sys

            self._host_path_told = told
            print(
                f"# fleet host path: {n - len(fast_idx)} of {n} rows: "
                f"{why['terms']} with more than {T_CAP} affinity terms, "
                f"{why['evict_tasks']} with more than {K_EVICT} eviction "
                f"tasks, {why['terms_spread']} with several terms and "
                f"spread constraints, {why['replicas']} with more than "
                f"{replicas_bound(self.snapshot.num_clusters)} replicas, "
                f"{why['selection']} "
                "with no spread selection",
                file=_sys.stderr,
                flush=True,
            )
        return why

    def _report_host_selected(self, rows: int) -> None:
        """Say how many of the batch's spread rows the HOST selects (the
        snapshot holds more regions than the device kernel's table): the
        gauge every batch, a line on stderr once a snapshot layout. Those
        rows cost ~84 us each, every wave whose generation moved."""
        from ..utils.metrics import spread_host_selected_rows

        spread_host_selected_rows.set(rows)
        token = self.snapshot.mask_token
        if rows and self._host_select_told != token:
            import sys as _sys

            from .select import R_CAP, region_count

            self._host_select_told = token
            print(
                f"# spread selection on the host: {rows} rows, the snapshot "
                f"holds {region_count(self.snapshot)} regions and the fleet "
                f"table's select kernel takes {R_CAP}",
                file=_sys.stderr,
                flush=True,
            )

    def _select_spread_rows(
        self,
        problems: Sequence[BindingProblem],
        compiled: list[CompiledPlacement],
        spread_idx: list,
    ) -> tuple:
        """The Select stage (select_clusters.go's SelectClusters) ON THE
        HOST, for the rows ``spread_idx`` of a batch (single-term,
        spread-constrained). The fleet table selects such rows itself
        (_fleet_select, from its resident state); this is the path for the
        rows that kernel does not take (a snapshot with more regions than
        its subset table holds) and for the explain capture. Returns
        ``(idx, bits)``: the rows the selection ACCEPTED, ascending, and
        each one's selected set as a packed mask (uint8[k, ceil(C/8)],
        little bit order): the fleet table's row state, uploaded when it
        moves. Selection runs exactly as the general path's Select stage
        does (same code, same memoization). Rows the selection REJECTS
        (FitError) are left out and fall through to the host path, which
        reports the failure. Callers keep rows of a batch with extra
        estimators out: the selection ranks groups on the general estimate
        alone, and the host path's Select stage sees the merged
        availability.

        A selection is pure in (snapshot generation, placement,
        replicas/requests/prev), so a per-binding-key cache answers the
        unchanged rows of a generation without packing or selecting, and
        availability rows come from a per-profile cache (one device fetch
        per NEW profile per snapshot generation). A moved generation
        re-selects every row: what the selection ranks on moved."""
        import time as _time

        from ..utils.metrics import spread_selections
        from ..utils.tracing import tracer
        from .spread import select_clusters_batch

        snap = self.snapshot
        w8 = (snap.num_clusters + 7) // 8
        if not spread_idx:
            return np.empty(0, np.int64), np.empty((0, w8), np.uint8)
        t_start = _time.perf_counter()
        gen = self._snapshot_gen
        cache = self._row_selections
        picked: list[int] = []
        bits: list[bytes] = []
        pending: list[tuple] = []
        hits = fit_errors = failed = moved = 0
        for i in spread_idx:
            p = problems[i]
            fp = (
                gen, id(p.placement), p.replicas,
                tuple(p.requests.items()), tuple(p.prev.items()),
            )
            hit = cache.get(p.key)
            # hit[1] pins the Placement whose id() the fingerprint embeds:
            # without it a GC'd placement re-allocated at the same address
            # would alias a stale selection
            if hit is not None and hit[0] == fp and hit[1] is p.placement:
                hits += 1
                if hit[2] is None:
                    fit_errors += 1  # cached FitError: stays on the host path
                else:
                    picked.append(i)
                    bits.append(hit[2])
                continue
            pending.append((i, fp))

        for start in range(0, len(pending), self.chunk_size):
            part = pending[start : start + self.chunk_size]
            sub_p = [problems[i] for i, _ in part]
            sub_c = [compiled[i] for i, _ in part]
            feasible, _strat, replicas, _sw, requests, prev, _fr = (
                self._pack_chunk(sub_p, sub_c, 0)
            )
            avail = self._selection_availability(requests, replicas, gen)
            # static-assignment caps bound the SELECTION's availability
            # too: group selection must rank groups on the same
            # cap-folded numbers the divide will see, or it can pick a
            # group the capped divide cannot fill
            cap_rows = self._quota_cap_rows(sub_p)
            if cap_rows is not None:
                avail = np.minimum(
                    avail, self._quota_caps_np(cap_rows, requests)
                ).astype(np.int32)
            candidates = select_clusters_batch(
                snap, sub_p, sub_c, 0, feasible, avail, prev
            )
            packed = np.packbits(candidates, axis=1, bitorder="little")
            chosen = candidates.any(axis=1)
            for k, (i, fp) in enumerate(part):
                p = problems[i]
                # None = FitError: the host path reports it
                row_bits = packed[k].tobytes() if chosen[k] else None
                old = cache.get(p.key)
                if old is None or old[2] != row_bits:
                    moved += 1
                cache[p.key] = (fp, p.placement, row_bits)
                if row_bits is None:
                    failed += 1
                else:
                    picked.append(i)
                    bits.append(row_bits)
        if len(cache) > 4 * max(len(problems), 1) + 65536:
            cache.clear()  # key-churn bound; repopulates next pass

        if hits:
            spread_selections.inc(hits, outcome="hit")
        if len(pending) - failed:
            spread_selections.inc(len(pending) - failed, outcome="computed")
        if failed:
            spread_selections.inc(failed, outcome="fit_error")
        tracer.record(
            "scheduler.select", _time.perf_counter() - t_start,
            start=t_start, rows=len(spread_idx), device=0, hits=hits,
            computed=len(pending), fit_errors=fit_errors + failed,
            moved=moved,
        )
        idx = np.asarray(picked, np.int64)
        masks = np.frombuffer(b"".join(bits), np.uint8).reshape(-1, w8)
        order = np.argsort(idx)
        return idx[order], masks[order]

    def _selection_availability(
        self, requests: np.ndarray, replicas: np.ndarray, gen: int
    ) -> np.ndarray:
        """Per-row availability for the Select stage from a per-profile
        cache: one device fetch per NEW request profile per snapshot
        generation (requests repeat fleet-wide), mirroring merge_estimates
        exactly — min over estimates with -1 ignored, MAX_INT32 sentinel
        clamped to spec.Replicas, zero-replica short-circuit."""
        from ..ops.estimate import MAX_INT32 as _MI

        if self._sel_profile_gen != gen:
            self._sel_profile_gen = gen
            self._sel_profile_rows.clear()
        uniq, inv = np.unique(requests, axis=0, return_inverse=True)
        missing = [
            u for u in range(len(uniq))
            if uniq[u].tobytes() not in self._sel_profile_rows
        ]
        if missing:
            table = np.asarray(
                self._profile_table(uniq[np.asarray(missing)])
            ).astype(np.int64)
            for row, u in enumerate(missing):
                self._sel_profile_rows[uniq[u].tobytes()] = table[row]
        dense = np.stack(
            [self._sel_profile_rows[uniq[u].tobytes()] for u in range(len(uniq))]
        )[inv]
        reps_col = replicas.astype(np.int64)[:, None]
        avail = np.where(
            dense == int(_MI), reps_col, np.where(dense < 0, reps_col, dense)
        )
        # zero-replica rows short-circuit to the sentinel path exactly
        # like merge_estimates (avail == replicas == 0 everywhere)
        avail = np.where(reps_col == 0, 0, avail)
        return np.minimum(avail, int(_MI)).astype(np.int32)

    def _schedule_host(
        self,
        problems: Sequence[BindingProblem],
        compiled: list[CompiledPlacement],
    ) -> list[ScheduleResult]:
        from ..utils.tracing import tracer

        # rows, Σ their replicas, the most previous sites of any of them;
        # chunks = what the rounds dispatched, each chunk's stages a child
        with tracer.span(
            "scheduler.host",
            rows=len(problems),
            replicas=sum(p.replicas for p in problems),
            prev_max=max((len(p.prev) for p in problems), default=0),
        ) as host:
            self._host_chunks = 0
            try:
                return self._schedule_host_rounds(problems, compiled)
            finally:
                host.attrs["chunks"] = self._host_chunks

    def _record_chunk_stages(self, rows: int, stages, unpacked) -> None:
        """One span a stage of a host-path chunk (children of the open
        scheduler.host), at the intervals scheduling_algorithm_duration
        observed: pack (Filter), estimate (Score), select (Select), assign
        (AssignReplicas); unpack runs from the assign's end to
        ``unpacked``."""
        from ..utils.tracing import tracer

        self._host_chunks += 1
        for name, timed in zip(_HOST_STAGES, stages):
            tracer.record(name, timed.duration, start=timed.start, rows=rows)
        end = stages[-1].start + stages[-1].duration
        tracer.record(
            "scheduler.host.unpack", unpacked - end, start=end, rows=rows
        )

    def _schedule_host_rounds(
        self,
        problems: Sequence[BindingProblem],
        compiled: list[CompiledPlacement],
    ) -> list[ScheduleResult]:
        """Ordered ClusterAffinities dispatch. Multi-term batches take the
        TENSORIZED first-fit path: the per-binding ranked affinity-group
        selection (ops.masks.first_fit_group) picks every row's group in
        one vectorized pass and the whole batch solves ONCE — a failover
        wave rescheduling thousands of displaced bindings costs one
        batched solve per chunk, not T sequential rounds. Multi-term rows
        that ALSO carry spread constraints keep the per-round loop (their
        per-term group search is a host search, and the combination is
        rare); single-term batches keep the plain one-round path."""
        max_terms = max((len(cp.terms) for cp in compiled), default=1)
        if max_terms > 1:
            legacy_idx = [
                i
                for i, cp in enumerate(compiled)
                if len(cp.terms) > 1 and cp.spread_constraints
            ]
            if not legacy_idx:
                return self._schedule_ranked(problems, compiled)
            legacy = set(legacy_idx)
            ranked_idx = [i for i in range(len(problems)) if i not in legacy]
            results: list = [None] * len(problems)
            for res_i, res in zip(
                ranked_idx,
                self._schedule_ranked(
                    [problems[i] for i in ranked_idx],
                    [compiled[i] for i in ranked_idx],
                ),
            ):
                results[res_i] = res
            for res_i, res in zip(
                legacy_idx,
                self._schedule_round_loop(
                    [problems[i] for i in legacy_idx],
                    [compiled[i] for i in legacy_idx],
                ),
            ):
                results[res_i] = res
            return results
        return self._schedule_round_loop(problems, compiled)

    def _schedule_ranked(
        self,
        problems: Sequence[BindingProblem],
        compiled: list[CompiledPlacement],
    ) -> list[ScheduleResult]:
        out: list[ScheduleResult] = []
        for start in range(0, len(problems), self.chunk_size):
            out.extend(
                self._schedule_chunk_ranked(
                    list(problems[start : start + self.chunk_size]),
                    compiled[start : start + self.chunk_size],
                )
            )
        return out

    def _schedule_round_loop(
        self,
        problems: Sequence[BindingProblem],
        compiled: list[CompiledPlacement],
    ) -> list[ScheduleResult]:
        results: list[Optional[ScheduleResult]] = [None] * len(problems)
        max_terms = max((len(cp.terms) for cp in compiled), default=1)

        pending = list(range(len(problems)))
        for term_round in range(max_terms):
            if not pending:
                break
            in_round = [i for i in pending if term_round < len(compiled[i].terms)]
            if not in_round:
                break
            round_results = self._schedule_round(
                [problems[i] for i in in_round],
                [compiled[i] for i in in_round],
                term_round,
            )
            next_pending = []
            for i, res in zip(in_round, round_results):
                has_more = term_round + 1 < len(compiled[i].terms)
                if res.success or not has_more:
                    results[i] = res
                else:
                    next_pending.append(i)  # FitError -> try next group
            # bindings whose term list was exhausted before this round keep
            # their last failure
            for i in pending:
                if i not in in_round and results[i] is None:
                    results[i] = ScheduleResult(
                        key=problems[i].key, error="no affinity group fits"
                    )
            pending = next_pending
        for i, res in enumerate(results):
            if res is None:
                results[i] = ScheduleResult(key=problems[i].key, error="not scheduled")
        return results  # type: ignore[return-value]

    # -- internals ---------------------------------------------------------

    def _schedule_round(
        self,
        problems: list[BindingProblem],
        compiled: list[CompiledPlacement],
        term_round: int,
    ) -> list[ScheduleResult]:
        out: list[ScheduleResult] = []
        for start in range(0, len(problems), self.chunk_size):
            chunk = problems[start : start + self.chunk_size]
            cchunk = compiled[start : start + self.chunk_size]
            out.extend(self._schedule_chunk(chunk, cchunk, term_round))
        return out

    def _pack_chunk(
        self,
        problems: list[BindingProblem],
        compiled: list[CompiledPlacement],
        term_round: int,
        with_affinity: bool = True,
    ):
        """Vectorized packing: per-binding work is O(sparse entries); the
        O(B x C) mask algebra happens once per *unique* placement/GVK and is
        gathered by row — the constant-factor lever SURVEY.md section 7 calls
        out for label matching at fleet scale."""
        snap = self.snapshot
        b, c, r = len(problems), snap.num_clusters, len(snap.dims)
        dim_index = {d: j for j, d in enumerate(snap.dims)}
        disabled = self.disabled_plugins

        # --- unique placements -> stacked per-placement masks -------------
        cp_slot: dict[int, int] = {}
        unique_cps: list[CompiledPlacement] = []
        cp_idx = np.empty(b, np.int32)
        for i, cp in enumerate(compiled):
            slot = cp_slot.get(id(cp))
            if slot is None:
                slot = len(unique_cps)
                cp_slot[id(cp)] = slot
                unique_cps.append(cp)
            cp_idx[i] = slot
        aff_pl = np.stack(
            [cp.terms[min(term_round, len(cp.terms) - 1)][1] for cp in unique_cps]
        )
        spread_pl = np.stack([cp.spread_field_ok for cp in unique_cps])
        taint_pl = np.stack([cp.taint_ok for cp in unique_cps])
        static_pl = np.stack([cp.static_weights for cp in unique_cps])
        strategy = np.array([cp.strategy for cp in unique_cps], np.int32)[cp_idx]

        # --- unique GVKs -> per-GVK enablement masks ----------------------
        gvk_slot: dict[str, int] = {}
        gvk_masks: list[np.ndarray] = []
        gvk_idx = np.empty(b, np.int32)
        for i, p in enumerate(problems):
            slot = gvk_slot.get(p.gvk)
            if slot is None:
                slot = len(gvk_masks)
                gvk_slot[p.gvk] = slot
                gid = snap.gvk_vocab.get(p.gvk) if p.gvk else None
                if gid is None:
                    mask = (
                        np.zeros(c, bool)
                        if p.gvk and len(snap.gvk_vocab) > 0
                        else np.ones(c, bool)
                    )
                else:
                    word, bit = gid // 32, gid % 32
                    mask = (snap.gvk_bits[:, word] >> np.uint32(bit)) & 1 != 0
                gvk_masks.append(mask)
            gvk_idx[i] = slot
        api_gvk = np.stack(gvk_masks)

        # --- sparse per-binding state -------------------------------------
        replicas = np.fromiter((p.replicas for p in problems), np.int32, b)
        fresh = np.fromiter((p.fresh for p in problems), bool, b)
        prev = np.zeros((b, c), np.int32)
        evict = np.zeros((b, c), bool)
        requests = np.zeros((b, r), np.int64)
        pods_dim = dim_index.get("pods")
        for i, p in enumerate(problems):
            for name, reps in p.prev.items():
                j = snap.index.get(name)
                if j is not None:
                    prev[i, j] = reps
            for name in p.evict_clusters:
                j = snap.index.get(name)
                if j is not None:
                    evict[i, j] = True
            for d, q in p.requests.items():
                j = dim_index.get(d)
                if j is not None:
                    requests[i, j] = q
            if pods_dim is not None and p.replicas > 0:
                # each replica occupies a pod (getAllowedPodNumber)
                requests[i, pods_dim] = max(requests[i, pods_dim], 1)
        prev_mask = prev > 0

        # --- mask composition (api_enablement.go / taint_toleration.go
        # leniency for already-placed clusters) -----------------------------
        feasible = np.ones((b, c), bool)
        if with_affinity and "ClusterAffinity" not in disabled:
            feasible &= aff_pl[cp_idx]
        if "SpreadConstraint" not in disabled:
            feasible &= spread_pl[cp_idx]
        if "APIEnablement" not in disabled:
            feasible &= api_gvk[gvk_idx] | (
                prev_mask & ~snap.complete_enablements[None, :]
            )
        if "TaintToleration" not in disabled:
            feasible &= taint_pl[cp_idx] | prev_mask
        if "ClusterEviction" not in disabled:
            feasible &= ~evict
        for custom in self.custom_filters:
            feasible &= np.asarray(custom(snap, problems), bool)
        static_w = static_pl[cp_idx]
        return feasible, strategy, replicas, static_w, requests, prev, fresh

    def _profile_table(self, profiles_np: np.ndarray) -> jnp.ndarray:
        """int32[P, C] general+model availability per unique request profile
        (-1 where the cluster gives no answer). The shared estimator core of
        _availability and the device-resident fleet path (scheduler.fleet)."""
        snap = self.snapshot
        req = jnp.asarray(profiles_np)
        general = general_estimate(jnp.asarray(snap.available_cap), req)
        mp = snap.model_pack
        if self._models_active():
            # model path replaces the summary path where applicable, still
            # capped by allowed pods (general.go:63-94,118-135)
            from ..models import estimate_by_models

            # the implicit pods dimension is the allowedPods cap, applied
            # separately — models never declare it (general.go:96-114 vs
            # :198-249), so it must not defeat model applicability
            pods_dim = snap.dim_index("pods")
            req_models = (
                req.at[:, pods_dim].set(0) if pods_dim is not None else req
            )
            model_avail, applicable = estimate_by_models(
                jnp.asarray(mp.min_bounds),
                jnp.asarray(mp.counts),
                jnp.asarray(mp.covered),
                req_models,
            )
            if pods_dim is not None:
                allowed_pods = jnp.minimum(
                    jnp.maximum(jnp.asarray(snap.available_cap[:, pods_dim]), 0),
                    2**31 - 1,
                ).astype(jnp.int32)
                model_avail = jnp.minimum(model_avail, allowed_pods[None, :])
            use_model = jnp.asarray(mp.has_models)[None, :] & applicable
            general = jnp.where(use_model, model_avail, general)
        # clusters with no ResourceSummary give no answer (UnauthenticReplica)
        return jnp.where(
            jnp.asarray(snap.has_summary)[None, :], general, jnp.int32(-1)
        )

    def _profile_table_quota(
        self, profiles_np: np.ndarray, prof_ns: np.ndarray
    ) -> jnp.ndarray:
        """``_profile_table`` with the static-assignment quota ceiling
        folded per (profile, namespace) slot — the fleet table's interned
        profiles carry a cap-namespace id beside the request vector, so
        the device-resident path divides against cap-bounded availability
        with NO kernel-signature change. The fold mirrors the host merge:
        a constrained cell becomes a real estimator answer (min of the
        general answer — or the untouched sentinel — and the cap), an
        unconstrained cell passes through, including the -1 no-summary
        convention this table uses."""
        table = self._profile_table(profiles_np)
        q = self.quota
        prof_ns = np.asarray(prof_ns, np.int32)
        if q is None or not q.has_caps or not (prof_ns >= 0).any():
            return table
        caps_out = self._quota_caps_dev(prof_ns, profiles_np)
        mi = jnp.int32(2**31 - 1)
        return jnp.where(
            caps_out < mi,
            jnp.minimum(jnp.where(table < 0, mi, table), caps_out),
            table,
        )

    def _models_active(self) -> bool:
        """Whether the resource-model estimator path would answer — THE
        predicate _profile_table activates the model estimation with; the
        tiny-batch host fast path must gate on exactly the same condition
        or small batches would silently diverge from the device path."""
        return bool(
            feature_gate.enabled(CUSTOMIZED_CLUSTER_RESOURCE_MODELING)
            and self.snapshot.model_pack.has_models.any()
        )

    def _availability_np(
        self,
        requests: np.ndarray,
        replicas: np.ndarray,
        cap_rows: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Host mirror of ``_availability`` for the tiny-batch fast path
        (general + resource-model estimators — callers gate off
        out-of-tree estimators only): the shared ``host_profile_table``
        plus merge_estimates' exact sentinel semantics (no-summary -> no
        answer -> clamp to spec.Replicas; zero-replica short-circuit).
        ``cap_rows`` folds the static-assignment quota caps as one more
        estimator answer, mirroring the device path's merge order: min
        over estimates FIRST, then the zero-replica override, then the
        untouched-sentinel clamp."""
        mi = 2**31 - 1
        uniq, inv = np.unique(requests, axis=0, return_inverse=True)
        dense = host_profile_table(
            self.snapshot, uniq, models_active=self._models_active()
        )[inv]
        if cap_rows is not None:
            dense = np.minimum(
                dense, self._quota_caps_np(cap_rows, requests)
            )
        reps_col = replicas.astype(np.int64)[:, None]
        avail = np.where(reps_col == 0, mi, dense)
        avail = np.where(avail == mi, reps_col, avail)
        return np.minimum(avail, mi).astype(np.int32)

    def _availability(
        self,
        requests: np.ndarray,
        replicas: np.ndarray,
        cap_rows: Optional[np.ndarray] = None,
    ) -> jnp.ndarray:
        """calAvailableReplicas (core/util.go:54-104): min-merge over
        registered estimators, sentinel clamped to spec.Replicas.

        Request rows are interned host-side (np.unique): the general/model
        estimators run per unique profile ([U, C]) and per-binding rows are a
        gather — fleets carry few unique ReplicaRequirements, so this removes
        the O(B x C x R) division hot loop. ``cap_rows`` joins the merge as
        one more estimator answer (the static-assignment quota ceiling,
        MAX_INT32 = no constraint)."""
        profiles_np, prof_inv = np.unique(requests, axis=0, return_inverse=True)
        reps = jnp.asarray(replicas)
        general = self._profile_table(profiles_np)
        # profile -> binding gather ([U, C] -> [B, C])
        estimates = [general[jnp.asarray(prof_inv.astype(np.int32))]]
        if cap_rows is not None:
            estimates.append(self._quota_caps_dev(cap_rows, requests))
        for est in self.extra_estimators:
            # out-of-tree estimators see the full per-binding requests
            estimates.append(jnp.asarray(est(jnp.asarray(requests), reps)))
        return merge_estimates(reps, tuple(estimates))

    def _schedule_chunk(
        self,
        problems: list[BindingProblem],
        compiled: list[CompiledPlacement],
        term_round: int,
    ) -> list[ScheduleResult]:
        import time as _time

        from ..utils.metrics import scheduling_algorithm_duration as algo_timer

        snap = self.snapshot
        with algo_timer.time(schedule_step="Filter") as t_pack:
            feasible, strategy, replicas, static_w, requests, prev, fresh = (
                self._pack_chunk(problems, compiled, term_round)
            )
            # pad the binding axis to the next power of two (capped at the
            # chunk size) so jit traces are reused across differently-sized
            # batches; pad rows are no-candidate zero-replica bindings
            b = len(problems)
            padded = 1
            while padded < b:
                padded *= 2
            padded = min(padded, self.chunk_size)
            if padded > b:
                pad = padded - b
                feasible = np.pad(feasible, ((0, pad), (0, 0)))
                strategy = np.pad(strategy, (0, pad))
                replicas = np.pad(replicas, (0, pad))
                static_w = np.pad(static_w, ((0, pad), (0, 0)))
                requests = np.pad(requests, ((0, pad), (0, 0)))
                prev = np.pad(prev, ((0, pad), (0, 0)))
                fresh = np.pad(fresh, (0, pad))
        # tiny-batch host fast path: a handful of bindings pays more in
        # device dispatch + fetch round-trips than the whole problem costs
        # in numpy (the crossover is not measured on this machine). The vectorized-numpy divider is the
        # oracle-verified identity referent (tests/test_divider_np.py +
        # every bench run), so placements are bit-identical. The resource-
        # model estimator has its own exact numpy mirror (host_profile
        # _table models_active branch), so only out-of-tree estimators
        # force the device path.
        host_small = (
            padded * snap.num_clusters <= 1 << 16
            and not self.extra_estimators
        )
        cap_rows = self._quota_cap_rows(problems)
        if cap_rows is not None and padded > b:
            cap_rows = np.pad(cap_rows, (0, padded - b), constant_values=-1)
        with algo_timer.time(schedule_step="Score") as t_est:
            avail = (
                self._availability_np(requests, replicas, cap_rows)
                if host_small
                else self._availability(requests, replicas, cap_rows)
            )

        # Select: spread-constraint group selection narrows the candidate set
        from .spread import select_clusters_batch  # local import (cycle-free)

        with algo_timer.time(schedule_step="Select") as t_sel:
            # avail stays on device unless a row carries spread constraints
            # (select pulls it lazily) — a constraint-free chunk does zero
            # device->host traffic between estimate and assign
            candidates = select_clusters_batch(
                snap, problems, compiled, term_round, feasible, avail, prev,
            )

        if host_small:
            # the numpy dispense packs (weight, last, index) into ONE int64
            # key; inputs beyond that bound (near-MAX availability with
            # large previous counts) must take the device kernels, which
            # have no such packing
            avail_np = np.asarray(avail)
            wmax = int(
                max(
                    int(avail_np.max(initial=0)) + int(prev.max(initial=0)),
                    int(static_w.max(initial=0)),
                    0,
                )
            )
            lmax = int(prev.max(initial=0)) + 1
            host_small = (wmax + 1) * lmax * snap.num_clusters < 2**63
        with algo_timer.time(schedule_step="AssignReplicas") as t_assign:
            self.solve_batches += 1
            if host_small:
                from ..refimpl.divider_np import assign_batch_np

                assignment, unschedulable = assign_batch_np(
                    strategy, replicas, candidates, static_w,
                    avail_np, prev, fresh,
                )
            else:
                res = self._assign(
                    strategy, replicas, candidates, static_w, avail,
                    prev, fresh,
                )
                assignment = np.asarray(res.assignment)
                unschedulable = np.asarray(res.unschedulable)
        out = self._unpack(problems, compiled, term_round, candidates,
                           assignment, unschedulable)
        self._record_chunk_stages(
            b, (t_pack, t_est, t_sel, t_assign), _time.perf_counter()
        )
        return out

    def _schedule_chunk_ranked(
        self,
        problems: list[BindingProblem],
        compiled: list[CompiledPlacement],
    ) -> list[ScheduleResult]:
        """One chunk of the tensorized ordered-failover path: pack every
        term's mask as a [B, T, C] candidate tensor, pick each row's first
        fitting affinity group in one vectorized selection
        (ops.masks.first_fit_group — the divider's exact schedulability
        predicate), then solve the WHOLE chunk once against the selected
        masks. T ordered fallback groups cost T batched [B, C] reductions
        plus one solve, instead of up to T sequential solves."""
        import time as _time

        from ..ops import masks as mops
        from ..ops.divide import AGGREGATED as S_AGG, DYNAMIC_WEIGHT as S_DYN
        from ..utils.metrics import scheduling_algorithm_duration as algo_timer

        snap = self.snapshot
        with algo_timer.time(schedule_step="Filter") as t_pack:
            base, strategy, replicas, static_w, requests, prev, fresh = (
                self._pack_chunk(problems, compiled, 0, with_affinity=False)
            )
            b = len(problems)
            padded = 1
            while padded < b:
                padded *= 2
            padded = min(padded, self.chunk_size)
            if padded > b:
                pad = padded - b
                base = np.pad(base, ((0, pad), (0, 0)))
                strategy = np.pad(strategy, (0, pad))
                replicas = np.pad(replicas, (0, pad))
                static_w = np.pad(static_w, ((0, pad), (0, 0)))
                requests = np.pad(requests, ((0, pad), (0, 0)))
                prev = np.pad(prev, ((0, pad), (0, 0)))
                fresh = np.pad(fresh, (0, pad))
            # stacked per-placement term tensors (the ranked affinity-
            # group surface): bool[U, Tmax, C] + live-term counts
            cp_slot: dict[int, int] = {}
            unique_cps: list[CompiledPlacement] = []
            cp_idx = np.zeros(padded, np.int32)
            for i, cp in enumerate(compiled):
                slot = cp_slot.get(id(cp))
                if slot is None:
                    slot = len(unique_cps)
                    cp_slot[id(cp)] = slot
                    unique_cps.append(cp)
                cp_idx[i] = slot
            tmax = max(len(cp.terms) for cp in unique_cps)
            c = snap.num_clusters
            term_stack = np.zeros((len(unique_cps), tmax, c), bool)
            term_len_u = np.ones(len(unique_cps), np.int32)
            for u, cp in enumerate(unique_cps):
                term_len_u[u] = len(cp.terms)
                for t, (_name, mask) in enumerate(cp.terms):
                    term_stack[u, t] = mask
            disabled = self.disabled_plugins
            if "ClusterAffinity" in disabled:
                term_stack[:] = True

        host_small = (
            padded * snap.num_clusters <= 1 << 16
            and not self.extra_estimators
        )
        cap_rows = self._quota_cap_rows(problems)
        if cap_rows is not None and padded > b:
            cap_rows = np.pad(cap_rows, (0, padded - b), constant_values=-1)
        with algo_timer.time(schedule_step="Score") as t_est:
            avail = (
                self._availability_np(requests, replicas, cap_rows)
                if host_small
                else self._availability(requests, replicas, cap_rows)
            )

        with algo_timer.time(schedule_step="Select") as t_sel:
            avail_np = np.asarray(avail)
            cand_tc = base[:, None, :] & term_stack[cp_idx]
            rank, _fit = mops.first_fit_group(
                cand_tc,
                term_len_u[cp_idx],
                avail_np.astype(np.int64),
                replicas.astype(np.int64),
                prev.astype(np.int64),
                (strategy == S_DYN) | (strategy == S_AGG),
                fresh.astype(bool),
            )
            feasible = np.take_along_axis(
                cand_tc, rank[:, None, None].astype(np.intp), axis=1
            )[:, 0, :]
            # spread selection still narrows single-term spread rows
            # (multi-term spread rows never reach this path)
            candidates = self._select_for_chunk(
                problems, compiled, feasible, avail, prev
            )

        if host_small:
            wmax = int(
                max(
                    int(avail_np.max(initial=0)) + int(prev.max(initial=0)),
                    int(static_w.max(initial=0)),
                    0,
                )
            )
            lmax = int(prev.max(initial=0)) + 1
            host_small = (wmax + 1) * lmax * snap.num_clusters < 2**63
        with algo_timer.time(schedule_step="AssignReplicas") as t_assign:
            self.solve_batches += 1
            if host_small:
                from ..refimpl.divider_np import assign_batch_np

                assignment, unschedulable = assign_batch_np(
                    strategy, replicas, candidates, static_w,
                    avail_np, prev, fresh,
                )
            else:
                res = self._assign(
                    strategy, replicas, candidates, static_w, avail,
                    prev, fresh,
                )
                assignment = np.asarray(res.assignment)
                unschedulable = np.asarray(res.unschedulable)
        out = self._unpack(problems, compiled, rank, candidates,
                           assignment, unschedulable)
        self._record_chunk_stages(
            b, (t_pack, t_est, t_sel, t_assign), _time.perf_counter()
        )
        return out

    def _select_for_chunk(self, problems, compiled, feasible, avail, prev):
        from .spread import select_clusters_batch

        return select_clusters_batch(
            self.snapshot, problems, compiled, 0, feasible, avail, prev
        )

    def _assign(self, strategy, replicas, candidates, static_w, avail, prev, fresh):
        from ..ops.divide import AGGREGATED

        max_n = int(replicas.max(initial=0))
        c = candidates.shape[1] if candidates.ndim == 2 else 1
        wide, fast = kernel_variant(
            int(jnp.max(avail)) if avail.size else 0,
            int(static_w.max(initial=0)),
            int(prev.max(initial=0)),
            max_n,
            c,
        )
        return divide_replicas(
            jnp.asarray(strategy),
            jnp.asarray(replicas),
            jnp.asarray(candidates),
            jnp.asarray(static_w),
            avail,
            jnp.asarray(prev),
            jnp.asarray(fresh),
            has_aggregated=bool((strategy == AGGREGATED).any()),
            wide=wide,
            fast=fast,
        )

    def _unpack(
        self, problems, compiled, term_round, candidates, assignment, unschedulable
    ) -> list[ScheduleResult]:
        """Vectorized result building: one np.nonzero over the whole chunk
        replaces per-binding scans, and the feasible-cluster tuple is only
        materialized for zero-replica (non-workload) bindings — its sole
        consumer (the scheduler controller writes all feasible clusters as
        the schedule of a non-workload binding)."""
        snap = self.snapshot
        names = snap.names
        b = len(problems)
        has_candidates = candidates[:b].any(axis=1)
        rows, cols = np.nonzero(assignment[:b] > 0)
        boundaries = np.searchsorted(rows, np.arange(1, b))
        per_row = np.split(cols, boundaries)
        out = []
        per_row_term = isinstance(term_round, np.ndarray)
        for i, p in enumerate(problems):
            tr = int(term_round[i]) if per_row_term else term_round
            term_idx = min(tr, len(compiled[i].terms) - 1)
            term_name = compiled[i].terms[term_idx][0]
            if not has_candidates[i]:
                out.append(
                    ScheduleResult(
                        key=p.key,
                        affinity_name=term_name,
                        error="no clusters fit the placement",
                    )
                )
                continue
            if unschedulable[i]:
                out.append(
                    ScheduleResult(
                        key=p.key,
                        affinity_name=term_name,
                        error=INSUFFICIENT_ERROR,
                    )
                )
                continue
            row = assignment[i]
            placed = {names[j]: int(row[j]) for j in per_row[i]}
            feasible = (
                tuple(names[j] for j in np.flatnonzero(candidates[i]))
                if p.replicas == 0
                else ()
            )
            out.append(
                ScheduleResult(
                    key=p.key,
                    clusters=placed,
                    feasible=feasible,
                    affinity_name=term_name,
                )
            )
        return out
