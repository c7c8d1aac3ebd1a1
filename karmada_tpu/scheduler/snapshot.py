"""Cluster snapshot packing: API objects -> tensor-ready arrays.

The analogue of the scheduler cache snapshot (ref: pkg/scheduler/cache/
cache.go:42-62) fused with selector pre-compilation. Where the reference
deep-copies Cluster objects per scheduling attempt and re-runs string
matching per (binding, cluster, plugin), this build interns every string
universe once per snapshot (labels, taints, GVKs, topology) and compiles each
Placement into boolean masks over the cluster axis — the filter plugins of
framework/plugins/* become a handful of bitset ANDs.

Mask semantics per plugin:
- ClusterAffinity (cluster_affinity.go:46-77): per-term mask via
  util.ClusterMatches semantics (exclude > names/labels/fields).
- TaintToleration (taint_toleration.go:46-74): untolerated NoSchedule/
  NoExecute taints; per-binding leniency for already-placed clusters is
  composed downstream in the engine.
- APIEnablement (api_enablement.go:46-73): GVK bit present; leniency for
  already-placed clusters when enablements are incomplete composed downstream.
- SpreadConstraint filter (spread_constraint.go:44-60): topology field must
  be non-empty when a constraint spreads by it.
- ClusterEviction (cluster_eviction.go:46-53): per-binding, composed
  downstream from graceful-eviction tasks.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from ..api.cluster import NO_EXECUTE, NO_SCHEDULE, Cluster, Toleration
from ..api.policy import (
    DUPLICATED,
    DIVIDED,
    AGGREGATED as PREF_AGGREGATED,
    WEIGHTED,
    ClusterAffinity,
    Placement,
    SpreadConstraint,
)
from ..ops import masks as mops
from ..ops.divide import AGGREGATED, DUPLICATED as S_DUPLICATED, DYNAMIC_WEIGHT, STATIC_WEIGHT

# canonical resource dimension order; extras appended at build time
DEFAULT_DIMS = ("cpu", "memory", "pods", "ephemeral-storage")


def strategy_code(placement: Optional[Placement]) -> int:
    """Map a Placement to the kernel strategy code
    (ref: newAssignState, assignment.go:89-107)."""
    if placement is None or placement.replica_scheduling_type() == DUPLICATED:
        return S_DUPLICATED
    rs = placement.replica_scheduling
    assert rs is not None
    if rs.replica_division_preference == PREF_AGGREGATED:
        return AGGREGATED
    # Weighted (or unset preference defaults to weighted static behavior)
    if rs.weight_preference is not None and rs.weight_preference.dynamic_weight:
        return DYNAMIC_WEIGHT
    return STATIC_WEIGHT


class ClusterSnapshot:
    """Immutable packed view of all member clusters."""

    def __init__(self, clusters: Sequence[Cluster], dims: Sequence[str] = ()):
        self.clusters = list(clusters)
        self.names = [c.name for c in self.clusters]
        self.index = {n: i for i, n in enumerate(self.names)}
        c = len(self.clusters)

        extra = [
            d
            for cl in self.clusters
            for d in cl.status.resource_summary.allocatable
            if d not in DEFAULT_DIMS
        ]
        self.dims: list[str] = list(DEFAULT_DIMS) + sorted(set(extra) | set(dims) - set(DEFAULT_DIMS))
        r = len(self.dims)

        # --- label / key vocab + bits ---
        self.label_vocab = mops.Vocab()
        self.key_vocab = mops.Vocab()
        pair_rows, key_rows = [], []
        for cl in self.clusters:
            p, k = mops.intern_labels(self.label_vocab, self.key_vocab, cl.meta.labels)
            pair_rows.append(p)
            key_rows.append(k)
        self.label_bits = mops.pack_bits(pair_rows, self.label_vocab.words)
        self.key_bits = mops.pack_bits(key_rows, self.key_vocab.words)

        # --- taints (only effects the scheduler filters on) ---
        self.taint_vocab = mops.Vocab()
        taint_rows = []
        self.taints = []  # vocab id -> Taint
        for cl in self.clusters:
            row = []
            for t in cl.spec.taints:
                if t.effect not in (NO_SCHEDULE, NO_EXECUTE):
                    continue
                tid = self.taint_vocab.intern(f"{t.key}={t.value}:{t.effect}")
                if tid == len(self.taints):
                    self.taints.append(t)
                row.append(tid)
            taint_rows.append(row)
        self.taint_bits = mops.pack_bits(taint_rows, self.taint_vocab.words)

        # --- API enablement ---
        self.gvk_vocab = mops.Vocab()
        gvk_rows = [
            [self.gvk_vocab.intern(g) for g in cl.status.api_enablements]
            for cl in self.clusters
        ]
        self.gvk_bits = mops.pack_bits(gvk_rows, self.gvk_vocab.words)
        self.complete_enablements = np.array(
            [
                any(
                    cond.type == "CompleteAPIEnablements" and cond.status
                    for cond in cl.status.conditions
                )
                for cl in self.clusters
            ],
            bool,
        )

        # --- topology ids (0 = missing field) ---
        self.provider_vocab = mops.Vocab()
        self.region_vocab = mops.Vocab()
        self.zone_vocab = mops.Vocab()
        for v in (self.provider_vocab, self.region_vocab, self.zone_vocab):
            v.intern("")  # id 0 reserved for "missing"
        self.provider_ids = np.array(
            [self.provider_vocab.intern(cl.spec.provider) for cl in self.clusters],
            np.int32,
        )
        self.region_ids = np.array(
            [self.region_vocab.intern(cl.spec.region) for cl in self.clusters], np.int32
        )
        self.zone_ids = np.array(
            [self.zone_vocab.intern(cl.spec.zone) for cl in self.clusters], np.int32
        )

        # --- capacity (general-estimator inputs) ---
        self.available_cap = np.zeros((c, r), np.int64)
        self.has_summary = np.zeros((c,), bool)
        for i, cl in enumerate(self.clusters):
            rs_ = cl.status.resource_summary
            self.has_summary[i] = bool(rs_.allocatable)
            for j, d in enumerate(self.dims):
                self.available_cap[i, j] = (
                    rs_.allocatable.get(d, 0)
                    - rs_.allocated.get(d, 0)
                    - rs_.allocating.get(d, 0)
                )

        # --- resource-model grades (CustomizedClusterResourceModeling) ---
        from ..models import pack_models

        self.model_pack = pack_models(self.clusters, self.dims)

    @property
    def num_clusters(self) -> int:
        return len(self.clusters)

    @property
    def mask_token(self) -> int:
        """Digest of every field the FILTER masks are a function of (names,
        labels, taints, API enablements, topology ids) — capacities and
        resource models excluded. Snapshots with equal tokens compile every
        placement to identical masks, so mask tables built against one are
        valid against the other: the fleet table uses this to skip the
        ~hundreds-of-MB mask-table re-upload on availability-only swaps
        (update_snapshot churn)."""
        tok = getattr(self, "_mask_token", None)
        if tok is None:
            import hashlib

            h = hashlib.blake2b(digest_size=16)
            h.update("\x00".join(self.names).encode())
            # every bitset/id array AND its vocab string table: equal bit
            # patterns under a renamed vocabulary (env=prod -> env=blue
            # interned at the same id) are DIFFERENT mask inputs
            h.update(self.label_bits.tobytes())
            h.update("\x00".join(self.label_vocab._ids).encode())
            h.update(self.key_bits.tobytes())
            h.update("\x00".join(self.key_vocab._ids).encode())
            h.update(self.taint_bits.tobytes())
            h.update("\x00".join(self.taint_vocab._ids).encode())
            h.update(self.gvk_bits.tobytes())
            h.update("\x00".join(self.gvk_vocab._ids).encode())
            h.update(self.complete_enablements.tobytes())
            h.update(self.provider_ids.tobytes())
            h.update("\x00".join(self.provider_vocab._ids).encode())
            h.update(self.region_ids.tobytes())
            h.update("\x00".join(self.region_vocab._ids).encode())
            h.update(self.zone_ids.tobytes())
            h.update("\x00".join(self.zone_vocab._ids).encode())
            tok = int.from_bytes(h.digest(), "little")
            self._mask_token = tok
        return tok

    def dim_index(self, name: str) -> Optional[int]:
        try:
            return self.dims.index(name)
        except ValueError:
            return None


def compile_affinity(aff: Optional[ClusterAffinity], snap: ClusterSnapshot) -> np.ndarray:
    """Evaluate a ClusterAffinity into bool[C] (util.ClusterMatches)."""
    c = snap.num_clusters
    m = np.ones((c,), bool)
    if aff is None:
        return m
    if aff.exclude:
        excl = {snap.index[n] for n in aff.exclude if n in snap.index}
        if excl:
            m[list(excl)] = False
    if aff.cluster_names:
        allow = np.zeros((c,), bool)
        idxs = [snap.index[n] for n in aff.cluster_names if n in snap.index]
        if idxs:
            allow[idxs] = True
        m &= allow
    if aff.label_selector is not None:
        sel = aff.label_selector
        require_pairs, require_keys, forbid_pairs, forbid_keys = [], [], [], []
        or_groups: list[list[int]] = []
        for k, v in sel.match_labels.items():
            pid = snap.label_vocab.get(mops.label_pair(k, v))
            if pid is None:
                return np.zeros((c,), bool)  # pair no cluster has
            require_pairs.append(pid)
        for req in sel.match_expressions:
            if req.operator == "In":
                ids = [
                    pid
                    for v in req.values
                    if (pid := snap.label_vocab.get(mops.label_pair(req.key, v)))
                    is not None
                ]
                if not ids:
                    return np.zeros((c,), bool)
                or_groups.append(ids)
            elif req.operator == "NotIn":
                # a key holds one value, so forbidding the listed pairs is
                # exactly NotIn (absent key passes)
                forbid_pairs.extend(
                    pid
                    for v in req.values
                    if (pid := snap.label_vocab.get(mops.label_pair(req.key, v)))
                    is not None
                )
            elif req.operator == "Exists":
                kid = snap.key_vocab.get(req.key)
                if kid is None:
                    return np.zeros((c,), bool)
                require_keys.append(kid)
            elif req.operator == "DoesNotExist":
                kid = snap.key_vocab.get(req.key)
                if kid is not None:
                    forbid_keys.append(kid)
            else:
                raise ValueError(f"unknown selector operator {req.operator}")
        lw, kw = snap.label_vocab.words, snap.key_vocab.words
        if require_pairs:
            m &= mops.contains_all(snap.label_bits, mops.bits_from_ids(require_pairs, lw))
        if require_keys:
            m &= mops.contains_all(snap.key_bits, mops.bits_from_ids(require_keys, kw))
        if forbid_pairs:
            m &= ~mops.intersects(snap.label_bits, mops.bits_from_ids(forbid_pairs, lw))
        if forbid_keys:
            m &= ~mops.intersects(snap.key_bits, mops.bits_from_ids(forbid_keys, kw))
        for ids in or_groups:
            m &= mops.intersects(snap.label_bits, mops.bits_from_ids(ids, lw))
    if aff.field_selector is not None:
        fields = {
            "provider": (snap.provider_ids, snap.provider_vocab),
            "region": (snap.region_ids, snap.region_vocab),
            "zone": (snap.zone_ids, snap.zone_vocab),
        }
        for req in aff.field_selector.match_expressions:
            ids_arr, vocab = fields[req.key]
            wanted = {vocab.get(v) for v in req.values} - {None}
            hit = np.isin(ids_arr, list(wanted)) if wanted else np.zeros((c,), bool)
            if req.operator == "In":
                m &= hit
            elif req.operator == "NotIn":
                m &= ~hit
            else:
                raise ValueError(f"unsupported field operator {req.operator}")
    return m


def _tolerated_bits(tolerations: Sequence[Toleration], snap: ClusterSnapshot) -> np.ndarray:
    ids = [
        tid
        for tid, taint in enumerate(snap.taints)
        if any(tol.tolerates(taint) for tol in tolerations)
    ]
    return mops.bits_from_ids(ids, snap.taint_vocab.words)


@dataclass
class CompiledPlacement:
    """A Placement evaluated against one snapshot."""

    placement: Optional[Placement]
    # ordered affinity groups: (name, mask[C]); a single unnamed group when
    # cluster_affinities is unset (scheduler.go:533-596)
    terms: list[tuple[str, np.ndarray]] = field(default_factory=list)
    taint_ok: np.ndarray = field(default_factory=lambda: np.zeros(0, bool))
    spread_field_ok: np.ndarray = field(default_factory=lambda: np.zeros(0, bool))
    strategy: int = S_DUPLICATED
    static_weights: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    spread_constraints: list[SpreadConstraint] = field(default_factory=list)
    # at most fleet.T_CAP affinity terms + no effective spread constraints:
    # the placement-level half of the fleet-eligibility gate for rows no
    # selection was made for, precomputed by TensorScheduler._compiled
    fleet_terms: bool = False
    # single-affinity-term WITH effective spread constraints: the rows the
    # Select stage selects (on the device, scheduler.select, or on the host)
    spread_single_term: bool = False


def compile_placement(placement: Optional[Placement], snap: ClusterSnapshot) -> CompiledPlacement:
    c = snap.num_clusters
    out = CompiledPlacement(placement=placement)
    pl = placement or Placement()

    if pl.cluster_affinities:
        out.terms = [
            (t.affinity_name, compile_affinity(t, snap)) for t in pl.cluster_affinities
        ]
    else:
        out.terms = [("", compile_affinity(pl.cluster_affinity, snap))]

    tol_bits = _tolerated_bits(pl.cluster_tolerations, snap)
    out.taint_ok = ~mops.intersects(snap.taint_bits, ~tol_bits)

    out.spread_field_ok = np.ones((c,), bool)
    for sc in pl.spread_constraints:
        if sc.spread_by_field == "provider":
            out.spread_field_ok &= snap.provider_ids != 0
        elif sc.spread_by_field == "region":
            out.spread_field_ok &= snap.region_ids != 0
        elif sc.spread_by_field == "zone":
            out.spread_field_ok &= snap.zone_ids != 0
    out.spread_constraints = list(pl.spread_constraints)

    out.strategy = strategy_code(placement)
    out.static_weights = np.zeros((c,), np.int32)
    if (
        out.strategy == STATIC_WEIGHT
        and pl.replica_scheduling is not None
        and pl.replica_scheduling.weight_preference is not None
    ):
        # weight = max over matching rules (division_algorithm.go:44-48)
        for rule in pl.replica_scheduling.weight_preference.static_weight_list:
            rule_mask = compile_affinity(rule.target_cluster, snap)
            out.static_weights = np.where(
                rule_mask,
                np.maximum(out.static_weights, np.int32(rule.weight)),
                out.static_weights,
            )
    return out
