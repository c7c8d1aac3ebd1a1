"""QuotaSnapshot: FederatedResourceQuota packed beside the cluster snapshot.

Ref: federatedresourcequota_types.go + the scheduling-side enforcement the
reference gates behind FederatedQuotaEnforcement. Where the cluster
snapshot packs member state into the filter/estimate tensors, this packs
the control plane's FRQ objects into the ADMISSION tensors the quota
kernels (ops.quota) consume:

- ``ns_index``/``remaining``: namespace -> row, and per-namespace
  ``limit - used`` over the engine snapshot's resource dims (int64,
  ``UNLIMITED`` where the namespace's quotas don't track a dim). Multiple
  FRQs in one namespace compose by elementwise min of remaining — every
  quota must admit.
- ``cap_index``/``cluster_caps``: namespaces with static_assignments get
  an ``[N, C, R]`` hard-cap tensor over the snapshot's cluster columns
  (UNLIMITED where a cluster/dim carries no slice) — folded into the
  divide kernel's availability as one more estimator answer.

Generation-stamped by the OWNER (the scheduler controller bumps on FRQ
watch events), so the engine's batch-identity replay can prove a wave's
admission inputs unchanged, and a denied binding retries on the next
quota generation instead of every pass. ``cap_token`` digests the
static-assignment layout alone: the fleet table bakes cap rows into its
interned profile slots, so the engine drops the table only when the CAP
content changes — a quota raise (remaining moved, caps unchanged) never
forces a re-pack.
"""

from __future__ import annotations

import hashlib
from typing import Optional, Sequence

import numpy as np

from ..ops.quota import DEMAND_CLAMP, UNLIMITED

#: ScheduleResult.error for a quota-denied binding; the scheduler
#: controller maps it to the Scheduled=False ``QuotaExceeded`` condition.
#: The reason code comes from THE taxonomy (utils.reasons.REASONS —
#: ISSUE 13 unification): it doubles as exclusion-mask stage bit 5, and
#: graftlint GL010 keeps every emission site on registered codes.
from ..utils.reasons import REASONS as _REASONS

QUOTA_EXCEEDED_REASON = _REASONS["QuotaExceeded"].code
QUOTA_EXCEEDED_ERROR = "namespace quota exceeded"


class AdmissionTally:
    """The two admission counters on bound tallies, added once a pass:
    what admission made of the pass's rows, and the route it took
    (resident | partition | replayed)."""

    def __init__(self):
        from ..utils.metrics import (
            quota_admission_passes,
            quota_admission_rows,
        )

        self._rows = tuple(
            quota_admission_rows.labels(outcome=o)
            for o in ("admitted", "denied", "unquotad")
        )
        self._routes = {
            r: quota_admission_passes.labels(route=r)
            for r in ("resident", "partition", "replayed")
        }

    def add(self, route: str, rows: int, quota_rows: int, denied: int):
        admitted_t, denied_t, unquotad_t = self._rows
        admitted_t.inc(quota_rows - denied)
        denied_t.inc(denied)
        unquotad_t.inc(rows - quota_rows)
        self._routes[route].inc()


class QuotaSnapshot:
    """Packed view of every FederatedResourceQuota.

    ``remaining`` is WORKING state within one generation: the engine
    debits each wave's admitted demand from it so a drain spanning
    multiple engine passes (batch splits, follow-on waves before the
    usage controller recomputes) cannot re-admit the same budget; the
    next generation rebuilds it from recomputed usage, so debit and
    accounting never double-count. Everything else is immutable."""

    def __init__(
        self,
        dims: Sequence[str],
        ns_index: dict[str, int],
        remaining: np.ndarray,  # int64[N, R]
        cap_index: dict[str, int],
        cluster_caps: np.ndarray,  # int64[Ncap, C, R]
        generation: int,
        cap_token: int,
    ):
        self.dims = list(dims)
        self.ns_index = ns_index
        self.remaining = remaining
        self.cap_index = cap_index
        self.cluster_caps = cluster_caps
        self.generation = generation
        self.cap_token = cap_token

    @property
    def active(self) -> bool:
        return bool(self.ns_index)

    @property
    def has_caps(self) -> bool:
        return bool(self.cap_index)

    def demand_row(self, requests: dict, replicas_delta: int) -> np.ndarray:
        """int64[R] wave demand for one binding: per-replica requests over
        the snapshot dims (each replica occupies one pod, mirroring the
        estimator's implicit pods request) scaled by the replica delta and
        clamped so a whole wave's cumsum stays in int64. The scale runs in
        PYTHON ints (R is tiny): an int64 multiply of an absurd-but-legal
        request by a huge delta would wrap to zero/negative BEFORE a
        post-hoc clamp could bound it — silently bypassing admission and
        inflating remaining on debit."""
        vec = per_replica_vector(requests, self.dims)
        delta = max(int(replicas_delta), 0)
        return np.fromiter(
            (min(int(v) * delta, DEMAND_CLAMP) for v in vec),
            np.int64,
            len(vec),
        )


def per_replica_vector(requests: dict, dims: Sequence[str]) -> np.ndarray:
    """int64[R] per-replica request over ``dims`` with the implicit
    one-pod-per-replica floor (the same projection _pack_chunk and the
    usage controller apply, so demand, usage, and estimates agree)."""
    vec = np.zeros(len(dims), np.int64)
    for j, d in enumerate(dims):
        q = requests.get(d, 0)
        if q:
            vec[j] = q
    if "pods" in dims:
        pods = dims.index("pods")
        vec[pods] = max(vec[pods], 1)
    return vec


def usage_from_bindings(store, namespaces) -> dict:
    """namespace -> {resource: used} from bound ResourceBindings:
    ``assigned replicas x per-replica request`` per resource, each
    replica occupying one pod (the same projection demand_row applies,
    so demand and usage can never disagree). THE single source of the
    usage formula — the FRQ status controller delegates here, and the
    snapshot builder falls back to it for FRQs whose status has not been
    reconciled yet."""
    usage: dict[str, dict[str, int]] = {ns: {} for ns in namespaces}
    for rb in store.list("ResourceBinding"):
        acc = usage.get(rb.meta.namespace)
        if acc is None:
            continue
        assigned = sum(int(tc.replicas or 0) for tc in rb.spec.clusters)
        if assigned <= 0:
            continue
        req = (
            rb.spec.replica_requirements.resource_request
            if rb.spec.replica_requirements
            else {}
        )
        for res, qty in req.items():
            if qty:
                acc[res] = acc.get(res, 0) + assigned * int(qty)
        if not req.get("pods"):
            acc["pods"] = acc.get("pods", 0) + assigned
    return usage


def build_quota_snapshot(
    frqs: Sequence,
    snapshot,
    generation: int,
    store=None,
) -> Optional["QuotaSnapshot"]:
    """Pack FRQ objects against one ClusterSnapshot (dims + cluster
    columns). Returns None when no FRQ exists — the engine's quota hook
    is one ``is None`` check then.

    ``store``, when given, closes the status-lag window: an FRQ whose
    status has not been reconciled against its current spec
    (``status.overall != spec.overall`` — a fresh create, or a spec edit
    the status controller hasn't caught up with) has its namespace's
    usage recomputed LIVE from bound bindings instead of trusting the
    stale/empty ``status.overall_used`` — otherwise the first wave after
    creating an FRQ over a namespace with existing usage would admit a
    full extra budget that nothing ever revokes."""
    frqs = [q for q in frqs if q.meta.namespace]
    if not frqs:
        return None
    dims = list(snapshot.dims)
    r = len(dims)
    dim_index = {d: j for j, d in enumerate(dims)}
    by_ns: dict[str, list] = {}
    for q in frqs:
        by_ns.setdefault(q.meta.namespace, []).append(q)
    namespaces = sorted(by_ns)
    live_usage: dict = {}
    if store is not None:
        stale_ns = {
            q.meta.namespace
            for q in frqs
            if q.status.overall != q.spec.overall
        }
        if stale_ns:
            live_usage = usage_from_bindings(store, stale_ns)
    ns_index = {ns: i for i, ns in enumerate(namespaces)}
    remaining = np.full((len(namespaces), r), UNLIMITED, np.int64)
    cap_ns: list[str] = []
    cap_rows: list[np.ndarray] = []
    c = snapshot.num_clusters
    token = hashlib.blake2b(digest_size=16)
    for ns in namespaces:
        caps: Optional[np.ndarray] = None
        for q in by_ns[ns]:
            # every quota in the namespace must admit: compose remaining
            # by elementwise min over FRQs. Unreconciled FRQs read live
            # usage (see docstring) instead of their lagging status.
            if q.status.overall != q.spec.overall and ns in live_usage:
                used = live_usage[ns]
            else:
                used = q.status.overall_used or {}
            for res, limit in q.spec.overall.items():
                j = dim_index.get(res)
                if j is None:
                    continue  # resource outside the scheduling dims
                rem = max(int(limit) - int(used.get(res, 0)), 0)
                remaining[ns_index[ns], j] = min(
                    remaining[ns_index[ns], j], rem
                )
            for assignment in q.spec.static_assignments:
                col = snapshot.index.get(assignment.cluster_name)
                if col is None:
                    continue
                if caps is None:
                    caps = np.full((c, r), UNLIMITED, np.int64)
                for res, hard in assignment.hard.items():
                    j = dim_index.get(res)
                    if j is None:
                        continue
                    caps[col, j] = min(caps[col, j], int(hard))
                    token.update(
                        f"{ns}\x00{assignment.cluster_name}\x00{res}"
                        f"\x00{int(hard)}".encode()
                    )
        if caps is not None:
            cap_ns.append(ns)
            cap_rows.append(caps)
    cap_index = {ns: i for i, ns in enumerate(cap_ns)}
    cluster_caps = (
        np.stack(cap_rows)
        if cap_rows
        else np.zeros((0, c, r), np.int64)
    )
    # the cap token also pins the cluster-column universe: caps are packed
    # against snapshot.index, so a changed cluster set changes the rows
    token.update("\x00".join(snapshot.names).encode())
    return QuotaSnapshot(
        dims=dims,
        ns_index=ns_index,
        remaining=remaining,
        cap_index=cap_index,
        cluster_caps=cluster_caps,
        generation=generation,
        cap_token=int.from_bytes(token.digest(), "little"),
    )
