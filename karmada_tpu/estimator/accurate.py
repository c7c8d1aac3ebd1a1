"""Accurate estimator: node-level MaxAvailableReplicas per member cluster.

The analogue of the karmada-scheduler-estimator server (ref:
pkg/estimator/server/estimate.go:59-112): one estimator instance per member
cluster watches that cluster's nodes/pods and answers
``max available = sum over matching nodes of min_dim((allocatable -
requested) // request)`` with a node-affinity + toleration prefilter and the
allowed-pod headroom per node.

Tensorization: each cluster's node state packs into ``[N, R]`` arrays; a
request batch evaluates as one ``[B, N]`` kernel per cluster. The scheduler
side fans out over estimators and min-merges (client/accurate.go:56-68 —
here a direct call; the gRPC transport wraps this same object in
karmada_tpu.estimator.service).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops  # noqa: F401  — enables x64 before the int64 kernel traces
from ..api.work import ReplicaRequirements

UNAUTHENTIC = -1

#: kill-switch for the batched wire protocol (utils.flags ENV_FLAGS): 0
#: forces every connection onto the per-profile unary fallback — the
#: mixed-version escape hatch and the bench's fallback-parity tier
BATCH_ENV = "KARMADA_TPU_ESTIMATOR_BATCH"
#: seconds a generation confirmation stays trusted across invalidate();
#: 0 re-pings the servers on every invalidated pass
PING_ENV = "KARMADA_TPU_ESTIMATOR_PING_SECONDS"
#: in-flight unary RPCs per server channel on the pipelined fallback path
WIDTH_ENV = "KARMADA_TPU_ESTIMATOR_FALLBACK_WIDTH"


def batch_enabled() -> bool:
    return os.environ.get(BATCH_ENV, "1").lower() not in ("0", "false", "")


def ping_trust_seconds() -> float:
    try:
        return float(os.environ.get(PING_ENV, "0") or 0.0)
    except ValueError:
        return 0.0


def fallback_width() -> int:
    try:
        width = int(os.environ.get(WIDTH_ENV, "4") or 4)
    except ValueError:
        width = 4
    return max(1, width)


def conn_supports_batch(conn) -> Optional[bool]:
    """Per-connection negotiation state: None = not yet probed, False =
    server answered UNIMPLEMENTED (probed once; a reconnect builds a fresh
    connection and re-probes — a wire failure also resets the pin to None
    so a server that dies and returns mid-pass re-negotiates). The env
    kill-switch overrides."""
    if not batch_enabled():
        return False
    return getattr(conn, "supports_batch", None)


def conn_breaker_engaged(conn) -> bool:
    """Is the connection's circuit breaker currently rejecting calls?
    Routing layers consult this BEFORE submitting fan-out work so a
    breaker-open server answers UnauthenticReplica immediately instead of
    burning the executor (and the pass deadline) on a doomed RPC. The
    check is non-consuming — the half-open probe that heals the breaker
    is taken by the transport's own call path, never by routing."""
    br = getattr(conn, "breaker", None)
    return br is not None and br.engaged()


@dataclass
class NodeState:
    """One member node (canonical int units)."""

    name: str
    allocatable: dict[str, int] = field(default_factory=dict)
    requested: dict[str, int] = field(default_factory=dict)  # sum of pod requests
    labels: dict[str, str] = field(default_factory=dict)
    taints: list = field(default_factory=list)  # api.cluster.Taint
    num_pods: int = 0


#: NodeSnapshot generation source: every instance gets a fresh, monotonic
#: generation so a snapshot SWAP (the informer-refresh idiom — build a new
#: NodeSnapshot, assign est.snapshot) always reads as movement to the
#: generation gate. Owners that can prove content equality may carry the
#: old generation forward (controlplane._refresh_estimators does). Offset
#: far above any NodeCache event count so the two generation spaces can
#: never collide for one cluster across a cache<->snapshot swap.
import itertools as _itertools

_SNAPSHOT_GEN = _itertools.count(1 << 32)


class NodeSnapshot:
    """Packed node arrays for one cluster (ref: the lifted kube-scheduler
    NodeInfo snapshot, pkg/util/lifted/scheduler/cache)."""

    def __init__(self, nodes: Sequence[NodeState], dims: Sequence[str]):
        nodes, dims = list(nodes), list(dims)
        available = np.zeros((len(nodes), len(dims)), np.int64)
        pods_dim = dims.index("pods") if "pods" in dims else None
        for i, node in enumerate(nodes):
            for j, d in enumerate(dims):
                available[i, j] = node.allocatable.get(d, 0) - node.requested.get(
                    d, 0
                )
            if pods_dim is not None:
                # allowed pods = allocatable pods - running pods
                # (server/estimate.go:104-112)
                available[i, pods_dim] = max(
                    node.allocatable.get("pods", 0) - node.num_pods, 0
                )
        self._adopt(available, dims, nodes)

    @classmethod
    def from_arrays(
        cls, available: np.ndarray, dims: Sequence[str]
    ) -> "NodeSnapshot":
        """A snapshot from the packed ``int64[N, R]`` free-resource array an
        estimator would ship (``dims`` names its columns; the pods column
        holds allowed pods). The array is adopted, not copied: the caller
        hands it over. Nothing but each node's free resources is known, so
        the nodes read as ``NodeCache`` holes: a ``node_claim`` prefilter
        passes none of them, a claim-free estimate sums them all."""
        available = np.asarray(available, np.int64)
        if available.ndim != 2 or available.shape[1] != len(dims):
            raise ValueError(
                f"available {available.shape} does not fit dims {list(dims)}"
            )
        self = cls.__new__(cls)
        self._adopt(available, list(dims), [None] * len(available))
        return self

    def _adopt(self, available: np.ndarray, dims: list, nodes: list) -> None:
        """The one constructor body: a fresh generation for every instance."""
        self.nodes = nodes
        self.dims = dims
        self.available = available
        self.generation = next(_SNAPSHOT_GEN)


class NodeCache:
    """Incrementally-maintained node state for one member cluster.

    Ref: pkg/util/lifted/scheduler/cache/cache.go (AddPod/RemovePod/
    AddNode/RemoveNode/UpdateNode) + server/estimate.go:59-102, where the
    estimator server keeps a kube-scheduler cache incrementally updated
    and snapshots it per request. ``NodeSnapshot`` repacks the full
    [N, R] array from scratch — fine at test scale, wrong shape for a
    10k-node member where every pod event would cost O(N x R). This cache
    mutates packed rows IN PLACE: O(R) per event, stable row ids (a
    freed row is recycled), and the estimator reads the live arrays with
    no copy. Duck-type compatible with ``NodeSnapshot`` (``nodes`` /
    ``dims`` / ``available``), so ``AccurateEstimator`` takes either."""

    def __init__(self, dims: Sequence[str], nodes: Sequence[NodeState] = ()):
        self.dims = list(dims)
        self._pods_dim = (
            self.dims.index("pods") if "pods" in self.dims else None
        )
        self.nodes: list[Optional[NodeState]] = []
        self.available = np.zeros((0, len(self.dims)), np.int64)
        self._rows: dict[str, int] = {}
        self._free: list[int] = []
        self.generation = 0
        for node in nodes:
            self.upsert_node(node)

    def _pack_row(self, i: int, node: NodeState) -> None:
        for j, d in enumerate(self.dims):
            self.available[i, j] = (
                node.allocatable.get(d, 0) - node.requested.get(d, 0)
            )
        if self._pods_dim is not None:
            self.available[i, self._pods_dim] = max(
                node.allocatable.get("pods", 0) - node.num_pods, 0
            )

    def upsert_node(self, node: NodeState) -> None:
        row = self._rows.get(node.name)
        if row is None:
            if self._free:
                row = self._free.pop()
            else:
                row = len(self.nodes)
                self.nodes.append(None)
                if row >= self.available.shape[0]:
                    grown = np.zeros(
                        (max(16, 2 * self.available.shape[0]), len(self.dims)),
                        np.int64,
                    )
                    grown[: self.available.shape[0]] = self.available
                    self.available = grown
            self._rows[node.name] = row
        self.nodes[row] = node
        self._pack_row(row, node)
        self.generation += 1

    def remove_node(self, name: str) -> None:
        row = self._rows.pop(name, None)
        if row is None:
            return
        self.nodes[row] = None
        self.available[row] = 0  # zero rows contribute zero replicas
        self._free.append(row)
        self.generation += 1

    def add_pod(self, node_name: str, requests: Mapping[str, int]) -> None:
        """A pod scheduled onto the node: its requests reduce the node's
        headroom and occupy one pod slot (cache.go AddPod)."""
        row = self._rows.get(node_name)
        if row is None:
            return
        node = self.nodes[row]
        for d, q in requests.items():
            node.requested[d] = node.requested.get(d, 0) + q
        node.num_pods += 1
        self._pack_row(row, node)
        self.generation += 1

    def remove_pod(self, node_name: str, requests: Mapping[str, int]) -> None:
        row = self._rows.get(node_name)
        if row is None:
            return
        node = self.nodes[row]
        for d, q in requests.items():
            node.requested[d] = node.requested.get(d, 0) - q
        node.num_pods = max(0, node.num_pods - 1)
        self._pack_row(row, node)
        self.generation += 1

    def live_nodes(self) -> list[NodeState]:
        return [n for n in self.nodes if n is not None]


def _node_sum_kernel(xp, node_avail, node_ok, requests):
    """node-sum estimate over an array module: min over requested dims of
    floor(avail / request) per node, summed over prefilter-passing nodes,
    int32-clamped. ONE body serves both array modules — jit for real
    batches, plain numpy for SMALL problems, where an estimator server
    answering one unary request (or one cluster's profile rows over a
    handful of nodes) pays more in jit dispatch than the whole estimate
    costs in numpy (~3 ms versus ~50 us per call, which IS the server's
    unary throughput ceiling on small members). Pure int math, so the two
    instantiations are bit-identical by construction (asserted in
    tests/test_estimators.py)."""
    avail = xp.maximum(node_avail, 0)
    per_node = xp.full(
        (requests.shape[0], avail.shape[0]), xp.int64(2**62)
    )
    for r in range(requests.shape[-1]):
        req_r = requests[:, r][:, None]
        ratio = avail[None, :, r] // xp.maximum(req_r, 1)
        per_node = xp.where(req_r > 0, xp.minimum(per_node, ratio), per_node)
    per_node = xp.where(per_node >= 2**62, 0, per_node)  # no requested dims
    total = xp.sum(xp.where(node_ok, per_node, 0), axis=1)
    return xp.minimum(total, xp.int64(2**31 - 1)).astype(xp.int32)


def _node_sum_estimate_np(node_avail, node_ok, requests):
    return _node_sum_kernel(np, node_avail, node_ok, requests)


@jax.jit
def _node_sum_estimate(node_avail, node_ok, requests):
    return _node_sum_kernel(jnp, node_avail, node_ok, requests)


#: below this B x N footprint the numpy mirror beats the jit kernel's
#: dispatch overhead (same crossover idea as the engine's host_small path)
_NP_ESTIMATE_CELLS = 1 << 14


@jax.jit
def node_sum_table(node_table, node_counts, requests):
    """``_node_sum_kernel`` over the member axis: ONE dispatch answers every
    in-process member. ``node_table`` int64[C, N, R] (the members' node
    arrays, ragged members padded), ``node_counts`` int32[C] (a member's
    nodes are its first ``node_counts[c]`` rows; the pad rows pass no
    prefilter), ``requests`` int64[P, R]. Returns int32[P, C]."""
    with jax.named_scope("estimator.node_sum"):
        node_ok = (
            jnp.arange(node_table.shape[1], dtype=jnp.int32)[None, :]
            < node_counts[:, None]
        )
        per_member = jax.vmap(
            lambda avail, ok: _node_sum_kernel(jnp, avail, ok[None, :], requests)
        )(node_table, node_ok)
        return per_member.T


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_members(node_table, slots, rows):
    return node_table.at[slots].set(rows)


@jax.jit
def _place_columns(answers, col_slot, host_cols):
    """int32[P, C] of a batch estimator's columns: the node table's answer
    where ``col_slot`` names a table slot, the host-built column (memo or
    -1) elsewhere."""
    picked = answers[:, jnp.maximum(col_slot, 0)]
    return jnp.where(col_slot[None, :] >= 0, picked, host_cols)


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _node_cap(n: int) -> int:
    """Node-axis capacity of the table for a largest member of ``n`` nodes:
    a power of two up to 1024, a multiple of 1024 above, so a member that
    gains a node does not mint a new trace."""
    return max(8, _pow2(n)) if n <= 1024 else -(-n // 1024) * 1024


class NodeTable:
    """The in-process members' node arrays as ONE device-resident
    ``int64[C, N_cap, R]`` table (ragged members zero-padded, their true
    lengths in ``int32[C]``). ``sync`` re-uploads exactly the members whose
    snapshot generation moved since their slice was uploaded; ``estimate``
    is one ``node_sum_table`` dispatch whose ``int32[P, C]`` answer stays on
    the device."""

    #: (C, N_cap, R, P) signatures dispatched so far in this process: a
    #: fresh one compiles, and counts as a serving-path compile
    _traces: set = set()

    def __init__(self) -> None:
        self.names: tuple = ()
        self.slot: dict[str, int] = {}
        self.gens: list = []
        self._counts = np.zeros(0, np.int32)
        #: per member, per dim: sum over nodes of max(free, 0); bounds its
        #: answers from above without a device fetch (``bound``)
        self._colsum = np.zeros((0, 0), np.int64)
        self.dev = None
        self.counts_dev = None

    @staticmethod
    def _read(est) -> tuple:
        """(generation, nodes, array): the generation is read BEFORE the
        array, so a concurrent member event makes the slice look stale."""
        snap = est.snapshot
        gen = int(getattr(snap, "generation", 0))
        n = len(snap.nodes)
        return gen, n, np.asarray(snap.available)[:n]

    def sync(self, members: Sequence[tuple]) -> Optional[dict]:
        """Bring the table up to ``members`` ([(name, estimator)], the slot
        order). Returns {"members", "nodes", "bytes"} of what was uploaded
        (zeros where nothing moved), or None where the members' arrays do
        not stack (no members, or differing dims): the caller then keeps
        the per-member path."""
        reads = [self._read(est) for _name, est in members]
        if not reads or len({a.shape[1] for _g, _n, a in reads}) != 1:
            return None
        names = tuple(name for name, _est in members)
        r = reads[0][2].shape[1]
        cap = _node_cap(max(n for _g, n, _a in reads))
        full = (
            names != self.names
            or self.dev is None
            or self.dev.shape[1:] != (cap, r)
        )
        moved = [
            i for i, (gen, _n, _a) in enumerate(reads)
            if full or self.gens[i] != gen
        ]
        if not moved:
            return {"members": 0, "nodes": 0, "bytes": 0}
        if full:
            self.names = names
            self.slot = {name: i for i, name in enumerate(names)}
            self.gens = [None] * len(names)
            self._counts = np.zeros(len(names), np.int32)
            self._colsum = np.zeros((len(names), r), np.int64)
        k = len(moved)
        whole = full or 2 * k > len(names)
        # the slots to write: all of them, or the moved ones padded to a
        # power of two by repeating the first (identical duplicate writes)
        slots = (
            list(range(len(names))) if whole
            else moved + [moved[0]] * (_pow2(k) - k)
        )
        # a fresh staging array each time: it is handed to the device whole
        # and never written again (a CPU device may alias it)
        stage = np.zeros((len(slots), cap, r), np.int64)
        for row, i in enumerate(slots):
            gen, n, arr = reads[i]
            stage[row, :n] = arr
            self.gens[i], self._counts[i] = gen, n
            self._colsum[i] = np.maximum(arr, 0).sum(axis=0)
        if whole:
            self.dev = jnp.asarray(stage)
        else:
            self.dev = _scatter_members(
                self.dev, jnp.asarray(np.asarray(slots, np.int32)),
                jnp.asarray(stage),
            )
        self.counts_dev = jnp.asarray(self._counts.copy())
        uploaded = slots if whole else moved
        return {
            "members": len(uploaded),
            "nodes": int(sum(reads[i][1] for i in uploaded)),
            "bytes": int(stage.nbytes),
        }

    def _known_dims(self, requests: np.ndarray) -> tuple:
        """(the request columns the node arrays carry, bool[P] rows that
        ask for a dim beyond them). The table's dims are a prefix of the
        caller's: a dim no node carries is one no node offers, so such a
        row fits nowhere."""
        req = np.asarray(requests, np.int64)
        r = self._colsum.shape[1]
        return req[:, :r], (req[:, r:] > 0).any(axis=1)

    def estimate(self, requests: np.ndarray):
        """int32[P, C] on the device, one dispatch."""
        from ..utils.metrics import estimator_nodes_estimated, kernel_compiles

        requests, unmet = self._known_dims(requests)
        key = self.dev.shape + (len(requests),)
        if key not in NodeTable._traces:
            NodeTable._traces.add(key)
            kernel_compiles.inc(
                kernel="node_sum_table", bucket="x".join(map(str, key))
            )
        estimator_nodes_estimated.inc(int(self._counts.sum()))
        out = node_sum_table(self.dev, self.counts_dev, jnp.asarray(requests))
        return jnp.where(jnp.asarray(unmet)[:, None], 0, out) if unmet.any() else out

    def bound(self, requests: np.ndarray) -> int:
        """An upper bound of every answer ``estimate(requests)`` gives: a
        sum of per-node floors never exceeds the floor of the sums, nor a
        sum of minima the minimum of sums. Host arithmetic only."""
        req, _unmet = self._known_dims(requests)
        if not len(req) or not len(self._colsum):
            return 0
        best = np.full((len(req), len(self._colsum)), 2**62, np.int64)
        for d in range(req.shape[1]):
            ratio = self._colsum[None, :, d] // np.maximum(req[:, d], 1)[:, None]
            best = np.where((req[:, d] > 0)[:, None], np.minimum(best, ratio), best)
        best = np.where(best >= 2**62, 0, best)
        return int(min(best.max(), 2**31 - 1))


class ResourceQuotaPlugin:
    """Estimate plugin capping replicas by namespace ResourceQuota headroom
    (ref: estimator server mini plugin framework,
    server/framework/interface.go + plugins/resourcequota/resourcequota.go,
    gated by the ResourceQuotaEstimate feature).

    ``quotas`` maps namespace -> {resource: remaining} (canonical units)."""

    def __init__(self, quotas: Optional[dict[str, dict[str, int]]] = None):
        self.quotas = quotas or {}

    def estimate(
        self, namespace: str, requirements: Optional[ReplicaRequirements]
    ) -> Optional[int]:
        """Max replicas the namespace quota still admits; None = no opinion."""
        quota = self.quotas.get(namespace)
        if quota is None or requirements is None:
            return None
        best: Optional[int] = None
        for res, req in requirements.resource_request.items():
            if req <= 0 or res not in quota:
                continue
            fit = max(quota[res], 0) // req
            best = fit if best is None else min(best, fit)
        return best


class AccurateEstimator:
    """Per-cluster node-level estimator service object."""

    def __init__(
        self,
        cluster_name: str,
        snapshot: NodeSnapshot,
        quota_plugin: Optional[ResourceQuotaPlugin] = None,
    ):
        self.cluster_name = cluster_name
        self.snapshot = snapshot
        self.quota_plugin = quota_plugin
        # unschedulable replicas per workload key (fed by the member watcher;
        # ref: server/replica/replica.go:43-77)
        self.unschedulable: dict[str, int] = {}

    def _node_prefilter(
        self, requirements: Optional[ReplicaRequirements]
    ) -> np.ndarray:
        nodes = self.snapshot.nodes
        ok = np.ones(len(nodes), bool)
        if requirements is None or requirements.node_claim is None:
            return ok
        claim = requirements.node_claim
        for i, node in enumerate(nodes):
            if node is None:  # NodeCache hole (removed node)
                ok[i] = False
                continue
            if claim.node_selector:
                if any(node.labels.get(k) != v for k, v in claim.node_selector.items()):
                    ok[i] = False
                    continue
            if node.taints:
                from ..api.cluster import NO_EXECUTE, NO_SCHEDULE, Toleration

                tolerations = [
                    t if isinstance(t, Toleration) else Toleration(**t)
                    for t in claim.tolerations
                ]
                untolerated = any(
                    t.effect in (NO_SCHEDULE, NO_EXECUTE)
                    and not any(tol.tolerates(t) for tol in tolerations)
                    for t in node.taints
                )
                if untolerated:
                    ok[i] = False
        return ok

    def max_available_replicas(
        self,
        requirements: Optional[ReplicaRequirements],
        requests_batch: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """int32[B] for a request batch sharing one node_claim. When
        ``requests_batch`` is None a single row is built from
        ``requirements.resource_request``."""
        if len(self.snapshot.nodes) == 0:
            return np.zeros(
                1 if requests_batch is None else len(requests_batch), np.int32
            )
        if requests_batch is None:
            req = np.zeros((1, len(self.snapshot.dims)), np.int64)
            if requirements is not None:
                for j, d in enumerate(self.snapshot.dims):
                    req[0, j] = requirements.resource_request.get(d, 0)
        else:
            req = np.asarray(requests_batch, np.int64)
        n = len(self.snapshot.nodes)
        node_ok = np.broadcast_to(
            self._node_prefilter(requirements)[None, :], (len(req), n)
        )
        if len(req) * n <= _NP_ESTIMATE_CELLS:
            out = _node_sum_estimate_np(
                # trim to the row count: a NodeCache over-allocates
                np.asarray(self.snapshot.available[:n]), node_ok, req
            )
        else:
            out = np.asarray(
                _node_sum_estimate(
                    jnp.asarray(self.snapshot.available[:n]),
                    jnp.asarray(node_ok),
                    jnp.asarray(req),
                )
            )
        # quota plugin caps the node-sum estimate (server/estimate.go:98-101,
        # RunEstimateReplicasPlugins min-merge), feature-gated
        from ..utils.features import RESOURCE_QUOTA_ESTIMATE, feature_gate

        if (
            self.quota_plugin is not None
            and requirements is not None
            and feature_gate.enabled(RESOURCE_QUOTA_ESTIMATE)
        ):
            cap = self.quota_plugin.estimate(requirements.namespace, requirements)
            if cap is not None:
                out = np.minimum(out, np.int32(cap))
        return out

    def get_unschedulable_replicas(self, workload_key: str) -> int:
        """Ref: server GetUnschedulableReplicas; counts come from the member
        watcher's pod conditions."""
        return self.unschedulable.get(workload_key, 0)


class EstimatorRegistry:
    """Scheduler-side estimator fan-out (ref: client/accurate.go:33-68 — the
    per-cluster connection cache + concurrent fan-out), batch-native and
    delta-aware.

    Estimates memoize per (cluster, unique request profile) and are GATED
    by the owning estimator's snapshot generation: ``invalidate()`` marks
    every cluster unconfirmed, and the next pass re-confirms them with one
    GetGenerations ping per SERVER connection — only clusters whose
    generation actually advanced re-pay the profile fan-out, and the
    fan-out itself is one MaxAvailableReplicasBatch per server instead of
    clusters x profiles unary calls. Old servers (UNIMPLEMENTED) keep the
    reference shape: full per-cluster re-query on every invalidation,
    pipelined over the channel."""

    def __init__(self) -> None:
        self._by_cluster: dict[str, AccurateEstimator] = {}
        self._pool = None
        # wall seconds spent in live estimator traffic (generation pings +
        # memo-miss fan-outs) since construction — benches diff this across
        # passes to report the snapshot-refresh latency of estimator-backed
        # availability
        self.fanout_seconds_total = 0.0
        # memoized answers, one scalar per (cluster, profile bytes); the
        # profile key is positional over the engine snapshot's dims, so one
        # registry serves one dims universe at a time (as before)
        self._memo: dict[tuple[str, bytes], int] = {}
        # last generation each cluster's memo entries were computed at
        self._gen: dict[str, int] = {}
        # clusters whose memo is trusted this epoch -> monotonic confirm
        # time (the PING_ENV trust window keys off it)
        self._confirmed: dict[str, float] = {}
        # live RPCs issued since construction, by kind — benches diff this
        # per pass to prove the O(servers) steady-pass shape
        self.rpc_counts: dict[str, int] = {"batch": 0, "unary": 0, "ping": 0}
        # memo-content version: bumped whenever an entry is written or
        # dropped. confirm_token() folds it into the token the scheduler's
        # batch-identity fast path compares — equal tokens prove the
        # estimator contribution to a replayed batch is unchanged
        self._epoch = 0
        # the in-process members' node arrays, device-resident (NodeTable)
        self._node_table = NodeTable()

    def _count_rpc(self, kind: str, n: int = 1) -> None:
        """One choke point for wire accounting: the per-registry
        ``rpc_counts`` dict (benches diff it per pass) AND the process
        metric family (karmada_tpu_estimator_rpcs_total) move together so
        the two surfaces can never disagree."""
        from ..utils.metrics import estimator_rpcs

        self.rpc_counts[kind] += n
        estimator_rpcs.inc(n, kind=kind)

    def register(self, est: AccurateEstimator) -> None:
        self._by_cluster[est.cluster_name] = est
        # a (re)registered estimator invalidates exactly its own cluster's
        # memo — columns are keyed by name, so other members keep theirs
        self._drop_cluster(est.cluster_name)

    def deregister(self, cluster_name: str) -> None:
        self._by_cluster.pop(cluster_name, None)
        self._drop_cluster(cluster_name)

    def _drop_cluster(self, name: str) -> None:
        self._gen.pop(name, None)
        self._confirmed.pop(name, None)
        self._epoch += 1
        for key in [k for k in self._memo if k[0] == name]:
            del self._memo[key]

    def get(self, cluster_name: str) -> Optional[AccurateEstimator]:
        return self._by_cluster.get(cluster_name)

    def invalidate(self, drop: bool = False) -> None:
        """Mark memoized estimates stale. Staleness contract: an estimate
        is a point-in-time answer memoized per (cluster, profile) until the
        owner observes member state change (cluster status heartbeat /
        snapshot swap) and invalidates. The default is GENERATION-GATED:
        memo entries survive, and the next pass re-confirms each cluster's
        snapshot generation (one ping per server) — a no-movement refresh
        never touches the profile fan-out. ``drop=True`` is the hard form
        (membership changes, tests, benches): forget everything and re-pay
        the full fan-out next pass."""
        if drop:
            self._memo.clear()
            self._gen.clear()
            self._confirmed.clear()
            self._epoch += 1
            return
        trust = ping_trust_seconds()
        if trust <= 0:
            self._confirmed.clear()
            return
        import time as _time

        now = _time.monotonic()
        self._confirmed = {
            c: t for c, t in self._confirmed.items() if now - t < trust
        }

    def make_batch_estimator(
        self,
        cluster_names: Sequence[str],
        *,
        max_workers: int = 64,
        timeout_seconds: Optional[float] = None,
    ):
        """Adapter for TensorScheduler.extra_estimators: returns
        fn(requests[B,R], replicas[B]) -> int32[B,C] with -1 where no
        estimator serves the cluster.

        Fan-out is CONCURRENT under one shared deadline
        (client/accurate.go:139-162), grouped by server connection: one
        batch RPC per server covers every hosted cluster's misses; clusters
        on fallback (unary) connections fan out per cluster with pipelined
        per-profile calls. A cluster missing the deadline answers
        UnauthenticReplica (-1) for this pass, so the min-merge ignores it
        instead of blocking scheduling — its late result is discarded,
        never applied to a later pass, and (per-column completeness) it
        never blocks memoization of the clusters that did answer."""
        names = list(cluster_names)
        # registered clusters the LAST estimate pass answered -1 for
        # (unconfirmed or cells missing): such a pass is degraded and must
        # never be replayed by the scheduler's batch-identity fast path —
        # the cluster may become confirmable right after (its server
        # recovers), at which point a replayed pass would pin the
        # transient -1 forever while a real pass would answer from memo
        unanswered: set = set()

        def memo_columns(prof_keys, skip=()) -> np.ndarray:
            """int32[U, C] of the memoized answers after a refresh, -1
            where a cluster gives none; columns in ``skip`` are left at -1
            for the caller to fill. Notes every registered cluster that
            answered -1 transiently in ``unanswered``."""
            table = np.full((len(prof_keys), len(names)), UNAUTHENTIC, np.int32)
            memo = self._memo
            unanswered.clear()
            for ci, name in enumerate(names):
                if name in skip:
                    continue
                # clusters with no registered estimator answer -1
                # STRUCTURALLY (deterministic); unconfirmed clusters answer
                # -1 for this pass only
                if name not in self._confirmed:
                    if name in self._by_cluster:
                        unanswered.add(name)
                    continue
                for u, key in enumerate(prof_keys):
                    val = memo.get((name, key))
                    if val is not None:
                        table[u, ci] = val
                    else:
                        unanswered.add(name)
            if unanswered:
                # degraded pass: at least one registered cluster answered
                # -1 transiently. Observable (the counter) and never
                # replayable (refresh_token below answers None).
                from ..utils.metrics import degraded_passes

                degraded_passes.inc(channel="estimator")
            return table

        def estimate(requests: np.ndarray, replicas: np.ndarray) -> np.ndarray:
            reqs = np.asarray(requests)
            reps = np.asarray(replicas)
            out = np.full((len(reqs), len(names)), UNAUTHENTIC, np.int32)
            # zero-replica rows (the engine's power-of-two PAD rows, plus
            # real scale-to-zero bindings) never need a live answer — the
            # min-merge ignores -1 and the divider assigns 0 regardless, so
            # their profiles must not force a wire wave of their own
            live = reps > 0
            if not live.any():
                return out
            uniq, inv = np.unique(reqs[live], axis=0, return_inverse=True)
            prof_keys = [row.tobytes() for row in uniq]
            self._refresh(names, uniq, prof_keys, max_workers, timeout_seconds)
            out[live] = memo_columns(prof_keys)[inv]
            return out

        def profile_table(profiles: np.ndarray, live: Optional[int] = None):
            """The answers BY PROFILE, for the fleet table's fold:
            int32[P, C] for the request vectors ``profiles`` int64[P, R]
            (what ``estimate`` answers a row of that profile with one
            replica or more), -1 = no answer. Rows from ``live`` on are
            padding: they cost no wire and answer -1 from every member off
            the node table. The in-process members' columns come from ONE
            ``node_sum_table`` dispatch and the result stays on the device;
            every other member's column is its memo, as in ``estimate``."""
            profs = np.asarray(profiles, np.int64)
            n_live = len(profs) if live is None else int(live)
            prof_keys = [row.tobytes() for row in profs[:n_live]]
            answers = self._refresh(
                names, profs[:n_live], prof_keys, max_workers,
                timeout_seconds, on_device=profs,
            )
            slot = self._node_table.slot if answers is not None else {}
            host = np.full((len(profs), len(names)), UNAUTHENTIC, np.int32)
            host[:n_live] = memo_columns(prof_keys, skip=slot)
            if answers is None:
                return jnp.asarray(host)
            col_slot = np.asarray(
                [slot.get(name, -1) for name in names], np.int32
            )
            return _place_columns(
                answers, jnp.asarray(col_slot), jnp.asarray(host)
            )

        def profile_bound(profiles: np.ndarray) -> int:
            """An upper bound of what ``profile_table(profiles)`` just
            answered, from host state only (the memo's values; the node
            table's column sums)."""
            keys = {row.tobytes() for row in np.asarray(profiles, np.int64)}
            slot = self._node_table.slot
            held = [
                v for (name, key), v in self._memo.items()
                if key in keys and name not in slot
            ]
            return max(
                max(held, default=0),
                self._node_table.bound(profiles) if slot else 0,
            )

        def refresh_token():
            # the scheduler's batch-identity fast path probes this before
            # replaying a storm pass: it confirms generations (O(servers)
            # pings) and returns an unchanged token iff no memo content
            # moved AND the last pass answered every registered cluster —
            # a degraded pass (transient -1 cells) is never replayable
            token = self.confirm_token(
                names, max_workers=max_workers,
                timeout_seconds=timeout_seconds,
            )
            if token is None or unanswered:
                return None
            return token

        estimate.refresh_token = refresh_token
        estimate.profile_table = profile_table
        estimate.profile_bound = profile_bound
        return estimate

    # -- live refresh machinery (ping + grouped fan-out) -------------------

    def _refresh(
        self,
        names: Sequence[str],
        uniq: np.ndarray,
        prof_keys: Sequence[bytes],
        max_workers: int,
        timeout_seconds: Optional[float],
        on_device: Optional[np.ndarray] = None,
    ):
        """Bring every (cluster, profile) memo cell either up to date or
        provably unanswerable for this pass. Mutates memo/generation state
        only on the calling thread — pool tasks just return data.

        The in-process members answer from the device-resident node table
        (``NodeTable``): with ``on_device`` (the request vectors of the
        fleet's fold, padding included) their answer is returned as it
        lies on the device, int32[P, members of the table], and their memo
        cells are left alone; without it the answer is fetched and
        memoized like any other member's, unless the refresh is so small
        (``_NP_ESTIMATE_CELLS``) that the per-member numpy path wins."""
        import time as _time

        from ..utils.metrics import (
            estimator_delta_requeries,
            estimator_refresh_seconds,
        )
        from ..utils.tracing import tracer

        t0 = _time.perf_counter()
        deadline = (
            None if timeout_seconds is None else t0 + timeout_seconds
        )

        def remaining() -> Optional[float]:
            if deadline is None:
                return None
            return max(deadline - _time.perf_counter(), 0.0)

        answers = None
        with tracer.span("estimator.refresh") as sp:
            # steps A+B: confirm generations (local reads + one ping per
            # server connection)
            touched_wire = self._confirm_generations(
                names, prof_keys, max_workers, remaining
            )

            # ---- step C: fetch — clusters with any unmemoized profile,
            # grouped by batch-capable connection; the rest per cluster
            fetch: list = []  # (name, est, conn | None)
            for name in names:
                est = self._by_cluster.get(name)
                if est is None:
                    continue
                if name in self._confirmed and all(
                    (name, k) in self._memo for k in prof_keys
                ):
                    continue
                fetch.append((name, est, getattr(est, "conn", None)))
            moved = 0
            local = [f for f in fetch if f[2] is None]
            if on_device is not None or (
                len(uniq) * sum(len(est.snapshot.nodes) for _n, est, _c in local)
                > _NP_ESTIMATE_CELLS
            ):
                synced = self._sync_node_table()
                if synced is not None:
                    moved = synced["members"]
                    fetch = [f for f in fetch if f[0] not in self._node_table.slot]
                    if on_device is not None:
                        answers = self._dispatch_node_table(on_device)
                    elif local:
                        self._memoize_node_table(
                            [f[0] for f in local], uniq, prof_keys
                        )
            sp.attrs["requeried_clusters"] = len(fetch) + moved
            if fetch or moved:
                touched_wire = True
                # the delta half of the generation-gated refresh: only
                # clusters whose generation moved (or never fetched)
                # re-pay the fan-out — this counter is that cardinality
                estimator_delta_requeries.inc(len(fetch) + moved)
            if fetch:
                self._fetch(fetch, uniq, prof_keys, max_workers, remaining)
        if touched_wire:
            elapsed = _time.perf_counter() - t0
            self.fanout_seconds_total += elapsed
            estimator_refresh_seconds.observe(elapsed)
        return answers

    def _sync_node_table(self) -> Optional[dict]:
        """Upload the moved in-process members' node arrays (span
        ``estimator.sync``). None where the registry holds no in-process
        member or their arrays do not stack."""
        from ..utils.metrics import estimator_upload_bytes
        from ..utils.tracing import tracer

        members = [
            (name, est) for name, est in self._by_cluster.items()
            if getattr(est, "conn", None) is None
        ]
        with tracer.span("estimator.sync") as sp:
            synced = self._node_table.sync(members)
            if synced is None:
                return None
            sp.attrs["members"] = synced["members"]
            sp.attrs["nodes"] = synced["nodes"]
            sp.attrs["upload_mb"] = synced["bytes"] / 1e6
        estimator_upload_bytes.inc(synced["bytes"])
        return synced

    def _dispatch_node_table(self, requests: np.ndarray):
        from ..utils.tracing import tracer

        with tracer.span(
            "estimator.dispatch",
            profiles=len(requests), members=len(self._node_table.names),
        ):
            return self._node_table.estimate(requests)

    def _memoize_node_table(self, wanted, uniq, prof_keys) -> None:
        """The host path's form of the node table's answer: one dispatch
        (rows padded to a power of two, zero-request pad rows), one fetch,
        and the ``wanted`` members' cells memoized at the generation their
        slice was uploaded at."""
        table = self._node_table
        padded = np.zeros((max(4, _pow2(len(uniq))), uniq.shape[1]), np.int64)
        padded[: len(uniq)] = uniq
        out = np.asarray(self._dispatch_node_table(padded))
        for name in wanted:
            i = table.slot[name]
            self._memoize(name, prof_keys, out[: len(uniq), i], table.gens[i])

    def _confirm_generations(
        self,
        names: Sequence[str],
        prof_keys: Optional[Sequence[bytes]],
        max_workers: int,
        remaining,
    ) -> bool:
        """Confirm every unconfirmed cluster's snapshot generation: local
        estimators by a direct read, remote ones with one GetGenerations
        ping per server connection. A cluster whose generation moved drops
        its memo (the fetch step re-queries it). When ``prof_keys`` is
        given, remote clusters with ANY unmemoized profile skip the ping —
        the fetch returns their generation anyway; ``prof_keys=None``
        (confirm_token) pings every unconfirmed remote. Returns True when
        any wire traffic happened."""
        from concurrent.futures import wait as _fwait

        from .service import UnsupportedMethodError

        # ---- step A: local estimators confirm by direct generation read
        remote_unconfirmed: list = []  # (name, est, conn)
        for name in names:
            if name in self._confirmed:
                continue
            est = self._by_cluster.get(name)
            if est is None:
                continue
            conn = getattr(est, "conn", None)
            if conn is None:
                gen = int(getattr(est.snapshot, "generation", 0))
                if self._gen.get(name) != gen:
                    self._drop_cluster(name)
                    self._gen[name] = gen
                self._confirm(name)
                continue
            remote_unconfirmed.append((name, est, conn))

        # ---- step B: generation pings, one per server connection
        ping_groups: dict[int, tuple] = {}
        for name, est, conn in remote_unconfirmed:
            if conn_breaker_engaged(conn):
                # breaker-open server: stay unconfirmed (-1 this pass)
                # WITHOUT submitting the doomed ping; the memo survives,
                # so the half-open probe that heals the channel
                # revalidates it without a refetch
                continue
            if prof_keys is not None and not all(
                (name, k) in self._memo for k in prof_keys
            ):
                continue
            if conn_supports_batch(conn) is False:
                # old server: no generations to ask for — re-pay the
                # fan-out for this cluster (the reference's shape)
                self._drop_cluster(name)
                continue
            key = id(conn)
            if key not in ping_groups:
                ping_groups[key] = (conn, [])
            ping_groups[key][1].append(name)
        if not ping_groups:
            return False
        from .service import GetGenerationsRequest

        pool = self._ensure_pool(max_workers)

        def ping(conn, members):
            return conn.call(
                "GetGenerations", GetGenerationsRequest(clusters=members)
            )

        futs = {}
        for conn, members in ping_groups.values():
            self._count_rpc("ping")
            futs[pool.submit(ping, conn, list(members))] = (conn, members)
        done, not_done = _fwait(futs, timeout=remaining())
        for f in not_done:
            f.cancel()  # members stay unconfirmed: -1 this pass
        for f in done:
            conn, members = futs[f]
            try:
                resp = f.result()
            except UnsupportedMethodError:
                conn.supports_batch = False
                for name in members:
                    self._drop_cluster(name)  # refetch on the unary path
                continue
            except Exception:  # noqa: BLE001 — server unreachable:
                # members stay unconfirmed (and answer -1) this pass;
                # the memo survives, so a later ping that finds the
                # generation unchanged revalidates it without a refetch
                continue
            for name in members:
                gen = resp.generations.get(name)
                if gen is not None and self._gen.get(name) == gen:
                    self._confirm(name)
                else:
                    self._drop_cluster(name)  # moved (or unknown)
        return True

    def confirm_token(
        self,
        cluster_names: Sequence[str],
        *,
        max_workers: int = 64,
        timeout_seconds: Optional[float] = None,
    ):
        """Prove the estimator contribution to a scheduling batch is
        unchanged, as cheaply as the protocol allows: confirm every
        registered cluster's snapshot generation (O(servers) pings; zero
        wire when everything is already confirmed) and return an opaque
        token that is EQUAL to a previous token iff no memo content
        changed in between. Returns None when any registered cluster could
        not be confirmed (old server, unreachable, or never fetched) — the
        caller must run the full estimate path, which retries those
        clusters. The scheduler's batch-identity fast path compares tokens
        to replay a storm pass without re-solving it."""
        import time as _time

        names = list(cluster_names)
        t0 = _time.perf_counter()
        deadline = (
            None if timeout_seconds is None else t0 + timeout_seconds
        )

        def remaining() -> Optional[float]:
            if deadline is None:
                return None
            return max(deadline - _time.perf_counter(), 0.0)

        touched = self._confirm_generations(names, None, max_workers, remaining)
        if touched:
            self.fanout_seconds_total += _time.perf_counter() - t0
        if all(
            name in self._confirmed
            for name in names
            if name in self._by_cluster
        ):
            return (self._epoch,)
        return None

    def _confirm(self, name: str) -> None:
        import time as _time

        self._confirmed[name] = _time.monotonic()

    def _ensure_pool(self, max_workers: int):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            from ..utils.tracing import ContextPropagatingExecutor

            # context-propagating: ping/fetch tasks open their RPC spans
            # under the refresh span that submitted them (estimator.rpc
            # must not land in wave 0 on a bare pool thread)
            self._pool = ContextPropagatingExecutor(
                ThreadPoolExecutor(max_workers)
            )
        return self._pool

    def _fetch(self, fetch, uniq, prof_keys, max_workers, remaining) -> None:
        """One batch RPC per batch-capable server connection; per-CHANNEL
        pipelined unary tasks for fallback servers; per-cluster tasks for
        local estimators. Results merge on the calling thread: a cluster
        that answered memoizes regardless of what happened to any other
        cluster (per-column completeness). Only the profile columns some
        fetched cluster is actually missing go over the wire — a pass whose
        only novelty is one new profile ships one row, not the matrix."""
        from concurrent.futures import wait as _fwait

        from .service import UnsupportedMethodError

        pool = self._ensure_pool(max_workers)
        # an unconfirmed cluster cannot trust ANY memo entry (its
        # generation is unknown), so it needs the full matrix; confirmed
        # clusters only their missing columns
        miss_idx: set = set()
        for name, _est, _conn in fetch:
            if name not in self._confirmed:
                miss_idx = set(range(len(prof_keys)))
                break
            miss_idx.update(
                u
                for u, k in enumerate(prof_keys)
                if (name, k) not in self._memo
            )
        order = sorted(miss_idx)
        sub_uniq = np.asarray(uniq)[order]
        sub_keys = [prof_keys[u] for u in order]
        rows = [[int(v) for v in row] for row in sub_uniq]

        batch_groups: dict[int, tuple] = {}  # id(conn) -> (conn, members)
        unary_groups: dict[int, tuple] = {}  # id(conn) -> (conn, members)
        locals_: list = []  # (name, est) — no connection (in-proc direct)
        retry: list = []  # members re-routed after a mid-pass UNIMPLEMENTED

        def route(name, est, conn):
            if conn is not None and conn_breaker_engaged(conn):
                # breaker-open server: the cluster answers -1 for this
                # pass with ZERO executor/wire cost (stays unconfirmed,
                # so the pass is degraded and never replayable)
                return
            if conn is not None and conn_supports_batch(conn) is not False:
                batch_groups.setdefault(id(conn), (conn, []))[1].append(
                    (name, est)
                )
            elif conn is not None and hasattr(conn, "call_future"):
                unary_groups.setdefault(id(conn), (conn, []))[1].append(
                    (name, est)
                )
            else:
                locals_.append((name, est))

        for name, est, conn in fetch:
            route(name, est, conn)

        def fetch_batch(conn, members):
            # NOTE: the registry's profile matrix is np.unique'd ACROSS
            # namespaces, so this path sends no per-row namespaces — the
            # server's ResourceQuota plugin stays inert here exactly as
            # it does on the registry's unary fallback (which also sends
            # namespace=""). Namespace-aware callers that want the
            # member-quota cap populate MaxAvailableReplicasBatchRequest.
            # namespaces per row; wire parity with the unary path is
            # asserted in tests/test_estimator_batch.py.
            from .service import MaxAvailableReplicasBatchRequest

            dims = list(members[0][1].dims_provider())
            return conn.call(
                "MaxAvailableReplicasBatch",
                MaxAvailableReplicasBatchRequest(
                    clusters=[name for name, _ in members],
                    dims=dims,
                    rows=rows,
                ),
            )

        def fetch_unary_channel(conn, members):
            """The pipelined fallback: ONE task per server channel slides a
            bounded window of per-profile calls over it (grpc futures) —
            latency hides without flooding the connection's HTTP/2 stream
            limit the way a task per cluster would."""
            from collections import deque

            from .service import MaxAvailableReplicasRequest

            width = fallback_width()
            out = {
                name: np.full(len(rows), UNAUTHENTIC, np.int32)
                for name, _ in members
            }

            def resolve(entry):
                name, u, fut = entry
                try:
                    out[name][u] = fut.result().max_replicas
                except Exception:  # noqa: BLE001 — per-RPC failure = -1
                    pass

            inflight: deque = deque()
            for name, est in members:
                dims = list(est.dims_provider())
                for u, row in enumerate(sub_uniq):
                    req = MaxAvailableReplicasRequest(
                        cluster=name,
                        resource_request={
                            d: int(q) for d, q in zip(dims, row) if q > 0
                        },
                    )
                    if len(inflight) >= width:
                        resolve(inflight.popleft())
                    try:
                        inflight.append(
                            (name, u,
                             conn.call_future("MaxAvailableReplicas", req))
                        )
                    except Exception:  # noqa: BLE001 — submit failure = -1
                        pass
            while inflight:
                resolve(inflight.popleft())
            return out

        def fetch_single(name, est):
            conn = getattr(est, "conn", None)
            if conn is not None and hasattr(est, "query_profiles"):
                dims = list(est.dims_provider())
                return est.query_profiles(dims, sub_uniq)
            # local estimator: generation read BEFORE computing so a
            # concurrent member event makes the answer look stale (see
            # EstimatorService.max_available_replicas_batch)
            gen = int(getattr(est.snapshot, "generation", 0))
            return (
                np.asarray(
                    est.max_available_replicas(None, sub_uniq), np.int32
                ),
                gen,
            )

        def merge_vals(name, vals, gen) -> None:
            if np.asarray(vals).min(initial=0) < 0:
                # the adapter reports per-RPC wire failures as -1 rows —
                # transient, never memoized (a pinned -1 would shadow the
                # member until the next hard invalidation)
                return
            self._memoize(name, sub_keys, vals, gen)

        futs = {}
        for conn, members in batch_groups.values():
            self._count_rpc("batch")
            futs[pool.submit(fetch_batch, conn, members)] = (
                "batch", (conn, members),
            )
        for conn, members in unary_groups.values():
            self._count_rpc("unary", len(members) * len(rows))
            futs[pool.submit(fetch_unary_channel, conn, members)] = (
                "unary", (conn, members),
            )
        for name, est in locals_:
            if getattr(est, "conn", None) is not None:
                self._count_rpc("unary", len(rows))
            futs[pool.submit(fetch_single, name, est)] = ("single", name)
        done, not_done = _fwait(futs, timeout=remaining())
        for f in not_done:
            # a straggler answers -1 this pass only (it stays unconfirmed
            # and unmemoized) — per-column completeness: it cannot block
            # the clusters that DID answer from memoizing
            f.cancel()
        for f in done:
            kind, meta = futs[f]
            try:
                result = f.result()
            except UnsupportedMethodError:
                if kind == "batch":
                    # negotiated mid-pass: pin the fallback on the
                    # connection (the gRPC conn already did; the in-proc
                    # seam needs it set here) and re-fan these clusters
                    # over the unary path — once per connection lifetime
                    conn, members = meta
                    conn.supports_batch = False
                    retry.append((conn, members))
                continue
            except Exception:  # noqa: BLE001 — wire failure = -1 this pass
                continue
            if kind == "batch":
                _conn, members = meta
                answered = {res.cluster: res for res in result.results}
                for name, _est in members:
                    res = answered.get(name)
                    if res is None:
                        continue  # unhosted: structural -1, never memoized
                    self._memoize(
                        name, sub_keys, res.max_replicas, res.generation
                    )
            elif kind == "unary":
                for name, vals in result.items():
                    merge_vals(name, vals, None)
            else:
                vals, gen = result
                merge_vals(meta, vals, gen)
        if retry:
            futs = {}
            for conn, members in retry:
                if hasattr(conn, "call_future"):
                    self._count_rpc("unary", len(members) * len(rows))
                    futs[pool.submit(fetch_unary_channel, conn, members)] = (
                        "unary", (conn, members),
                    )
                else:
                    for name, est in members:
                        self._count_rpc("unary", len(rows))
                        futs[pool.submit(fetch_single, name, est)] = (
                            "single", name,
                        )
            done, not_done = _fwait(futs, timeout=remaining())
            for f in not_done:
                f.cancel()
            for f in done:
                kind, meta = futs[f]
                try:
                    result = f.result()
                except Exception:  # noqa: BLE001
                    continue
                if kind == "unary":
                    for name, vals in result.items():
                        merge_vals(name, vals, None)
                else:
                    vals, gen = result
                    merge_vals(meta, vals, gen)

    def _memoize(self, name, prof_keys, values, gen) -> None:
        if gen is not None and self._gen.get(name) not in (None, int(gen)):
            # the server's snapshot moved between our last fetch and this
            # partial one: entries OUTSIDE this response are at the old
            # generation — drop them so they re-fetch instead of serving
            # stale values next to fresh ones
            self._drop_cluster(name)
        self._epoch += 1
        for key, val in zip(prof_keys, values):
            self._memo[(name, key)] = int(val)
        if gen is not None:
            self._gen[name] = int(gen)
        else:
            # fallback server: no generation protocol — entries stay valid
            # until the next invalidate() epoch, then re-fetch (the
            # reference's full-refresh shape)
            self._gen.pop(name, None)
        self._confirm(name)
