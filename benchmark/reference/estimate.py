"""Plain reference of the scheduler-estimator's answer and of its merge
into the availability the cells' bindings are divided over.

Independent of ``karmada_tpu``: numpy only. It states the semantics of
Karmada's estimator server and of the scheduler's merge
(pkg/estimator/server/estimate.go; pkg/scheduler/core/util.go:54-104):

- node-sum: a member's estimator answers, per request profile, the sum over
  its nodes of min over the requested dims of floor(max(free, 0) / request),
  allowed pods being one of the dims (each replica asks for one pod);
- merge: the scheduler takes the minimum over its estimators' answers, an
  answer of -1 (no estimator for the member, or none reachable) ignored;
  what no estimator touched is clamped to spec.replicas.

``place`` is ``divide.place`` with that merged table in the general
estimate's stead; the division is ``divide``'s own.
"""

from __future__ import annotations

import numpy as np

from . import divide


def node_sum(node_free: np.ndarray, requests: np.ndarray) -> np.ndarray:
    """int64[P, C] for ``node_free`` int64[C, N, R] and ``requests``
    int64[P, R] (0 = dim not requested)."""
    free = np.maximum(node_free.astype(np.int64), 0)
    out = np.zeros((requests.shape[0], free.shape[0]), np.int64)
    for p in range(requests.shape[0]):
        per_node = None
        for d in range(requests.shape[1]):
            req = int(requests[p, d])
            if req > 0:
                ratio = free[:, :, d] // req
                per_node = ratio if per_node is None else np.minimum(per_node, ratio)
        if per_node is not None:  # a profile that asks for nothing fits nowhere
            out[p] = np.minimum(per_node.sum(axis=1), divide.MAX_INT32)
    return out


def min_merge(table: np.ndarray, answers: np.ndarray) -> np.ndarray:
    """Cell by cell: an answer of -1 keeps the table's cell, a table cell of
    -1 takes the answer, else the minimum."""
    merged = np.where(table < 0, answers, np.minimum(table, answers))
    return np.where(answers < 0, table, merged)


def place(replicas, requests, prof_idx, prev, fresh, cap, node_free=None,
          rows: int = 2048):
    """The reference for a batch of bindings with no taint anywhere: the
    general estimate over ``cap`` int64[C, R], min-merged with the node-sum
    over ``node_free`` int64[C, N, R] (None: estimators off), divided.
    Returns (assignment int64[B, C], unschedulable bool[B])."""
    table = divide.estimate(cap, requests)
    if node_free is not None:
        table = min_merge(table, node_sum(node_free, requests))
    outs, uns = [], []
    for s in range(0, len(replicas), rows):
        sl = slice(s, s + rows)
        cand = np.ones((len(replicas[sl]), len(cap)), bool)
        avail = divide.merge(replicas[sl], table[prof_idx[sl]])
        o, u = divide.divide_dynamic(replicas[sl], cand, avail, prev[sl], fresh[sl])
        outs.append(o)
        uns.append(u)
    return np.concatenate(outs), np.concatenate(uns)
