"""Numpy estimator oracle: node-level MaxAvailableReplicas and its merge
with the general estimate (ISSUE 27's identity referent).

The engine's estimator-fed fleet path claims, per (request profile, member):
the scheduler-estimator's answer is the sum over the member's nodes of the
minimum over the requested dims of ``floor(free / request)``, allowed pods
counting as a dim (server/estimate.go:59-112); that answer is min-merged
with the general (ResourceSummary) estimate, an answer of -1 ignored; and
an estimate no estimator touched is clamped to ``spec.replicas``
(core/util.go:54-104). This module IS that rule written plainly: a loop
over members, a loop over dims, int64 throughout. Nothing here is shared
with ``estimator/accurate.py`` or ``ops/estimate.py``, so a drift in the
kernel's vectorisation or in the fold's sentinel algebra shows up as an
oracle mismatch and not as a shared bug.

``place`` composes the merged table with the per-binding numpy divider
(refimpl.divider_np) so a whole estimator-fed wave can be verified end to
end.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from .divider_np import assign_batch_np

MAX_INT32 = 2**31 - 1
NO_ANSWER = -1


def node_sum(node_free: np.ndarray, requests: np.ndarray) -> np.ndarray:
    """int64[P]: replicas of each request profile one member's nodes still
    hold. ``node_free`` int64[N, R] (free resources a node, the pods column
    its allowed pods; a negative reads as 0), ``requests`` int64[P, R]
    (0 = dim not requested). A profile that requests nothing fits nowhere
    (0), and the sum is clamped to MAX_INT32."""
    free = np.maximum(np.asarray(node_free, np.int64), 0)
    req = np.asarray(requests, np.int64)
    out = np.zeros(len(req), np.int64)
    for p in range(len(req)):
        asked = [d for d in range(req.shape[1]) if req[p, d] > 0]
        if not asked:
            continue
        per_node = free[:, asked[0]] // req[p, asked[0]]
        for d in asked[1:]:
            per_node = np.minimum(per_node, free[:, d] // req[p, d])
        out[p] = min(int(per_node.sum()), MAX_INT32)
    return out


def estimator_table(
    members: Sequence[Optional[np.ndarray]], requests: np.ndarray
) -> np.ndarray:
    """int64[P, C]: every member's ``node_sum``, NO_ANSWER for a member
    with no estimator (``None`` in ``members``)."""
    out = np.full((len(requests), len(members)), NO_ANSWER, np.int64)
    for c, node_free in enumerate(members):
        if node_free is not None:
            out[:, c] = node_sum(node_free, requests)
    return out


def general_table(
    free: np.ndarray, requests: np.ndarray, has_summary: np.ndarray
) -> np.ndarray:
    """int64[P, C] summary-level estimate: min over requested dims of
    ``floor(max(free, 0) / request)``; MAX_INT32 where nothing is
    requested; NO_ANSWER for a member that reports no summary."""
    free = np.maximum(np.asarray(free, np.int64), 0)
    req = np.asarray(requests, np.int64)
    out = np.full((len(req), len(free)), MAX_INT32, np.int64)
    for p in range(len(req)):
        for d in range(req.shape[1]):
            if req[p, d] > 0:
                out[p] = np.minimum(out[p], free[:, d] // req[p, d])
    out[:, ~np.asarray(has_summary, bool)] = NO_ANSWER
    return out


def merge_tables(*tables: np.ndarray) -> np.ndarray:
    """Min across estimators, an answer of NO_ANSWER ignored; MAX_INT32
    where none answered."""
    out = np.full(tables[0].shape, MAX_INT32, np.int64)
    for t in tables:
        out = np.where(t == NO_ANSWER, out, np.minimum(out, t))
    return out


def available(
    replicas: np.ndarray, prof_idx: np.ndarray, merged: np.ndarray
) -> np.ndarray:
    """int64[B, C] per binding: its profile's merged row, an untouched
    sentinel clamped to ``spec.replicas``; a zero-replica binding reads
    0 everywhere."""
    reps = np.asarray(replicas, np.int64)[:, None]
    rows = merged[np.asarray(prof_idx)]
    rows = np.where(rows >= MAX_INT32, reps, rows)
    return np.where(reps == 0, 0, rows)


def place(
    strategy: np.ndarray,
    replicas: np.ndarray,
    prof_idx: np.ndarray,
    candidates: np.ndarray,
    static_w: np.ndarray,
    prev: np.ndarray,
    fresh: np.ndarray,
    merged: np.ndarray,
) -> tuple[np.ndarray, np.ndarray]:
    """The estimator-fed wave, end to end: (assignment int32[B, C],
    unschedulable bool[B]) of the numpy divider over ``available``."""
    avail = available(replicas, prof_idx, merged)
    return assign_batch_np(
        np.asarray(strategy, np.int32), np.asarray(replicas, np.int32),
        np.asarray(candidates, bool), np.asarray(static_w, np.int32),
        np.minimum(avail, MAX_INT32).astype(np.int32),
        np.asarray(prev, np.int32), np.asarray(fresh, bool),
    )
