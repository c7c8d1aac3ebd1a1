"""Plain reference of the replica division the cells hold the program to.

Independent of ``karmada_tpu``: numpy only, inputs made by the benchmark's
own generator. It states the semantics of Karmada's scheduler for Divided /
dynamic-weight placements (pkg/scheduler/core: generic_scheduler.go
AssignReplicas, assignment.go, division_algorithm.go; estimator
general.go; pkg/util/helper/binding.go Dispenser.TakeByWeight):

- filter: a cluster is a candidate unless it carries a NoSchedule taint the
  placement does not tolerate; a cluster already in the previous result
  stays a candidate (taint_toleration.go leniency);
- estimate: per cluster, min over the requested dims of
  floor(max(allocatable - allocated, 0) / request); each replica also asks
  for one pod; a cluster that reports no summary gives no answer, and an
  unanswered estimate is clamped to spec.replicas (core/util.go:54-104);
- divide: steady scale-up dispenses only the delta over current
  availability and keeps the previous result, scale-down re-divides over
  the FULL previous result, a fresh (reschedule-triggered) binding
  re-divides everything over availability credited with what it holds;
- dispense: floors of weight * replicas / total, the remainder one each in
  (weight desc, previous replicas desc, cluster index asc) order.

The arithmetic is exact (integers); the configurations state no precision,
so the control breaks a guarantee instead (benchmark/control.py).
"""

from __future__ import annotations

import numpy as np

MAX_INT32 = 2**31 - 1


def estimate(cap: np.ndarray, requests: np.ndarray,
             has_summary: np.ndarray | None = None) -> np.ndarray:
    """int64[P, C] replicas each cluster can still hold per request profile.

    ``cap`` int64[C, R] = allocatable - allocated; ``requests`` int64[P, R]
    (0 = dim not requested). -1 where the cluster gives no answer."""
    cap = np.maximum(cap.astype(np.int64), 0)
    p, c = requests.shape[0], cap.shape[0]
    out = np.full((p, c), MAX_INT32, np.int64)
    for d in range(requests.shape[1]):
        req = requests[:, d].astype(np.int64)
        ratio = cap[None, :, d] // np.maximum(req, 1)[:, None]
        out = np.where((req > 0)[:, None], np.minimum(out, ratio), out)
    if has_summary is not None:
        out = np.where(has_summary[None, :], out, -1)
    return out


def merge(replicas: np.ndarray, est: np.ndarray) -> np.ndarray:
    """calAvailableReplicas with one estimator: no answer (-1) or the
    untouched sentinel becomes spec.replicas."""
    out = np.where(est < 0, MAX_INT32, est)
    return np.where(out >= MAX_INT32, replicas[:, None].astype(np.int64), out)


def _dispense(num, w, last, init):
    b, c = w.shape
    total = w.sum(axis=1)
    safe_total = np.maximum(total, 1)
    floors = w * num[:, None] // safe_total[:, None]
    remain = num - floors.sum(axis=1)
    # rank of each cluster in (weight desc, last desc, index asc) order; the
    # `remain` first get one more. A full stable sort: plain, not fast.
    idx = np.arange(c, dtype=np.int64)
    lmax = int(last.max(initial=0)) + 1
    if (int(w.max(initial=0)) + 1) * lmax * c >= 2**62:
        raise OverflowError("weights exceed the packed sort key")
    key = (w * lmax + last) * c + (c - 1 - idx)[None, :]
    order = np.argsort(-key, axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.broadcast_to(idx, (b, c)).copy(), axis=1)
    bonus = (rank < remain[:, None]) & (w > 0)
    out = np.where((total > 0)[:, None], floors + bonus.astype(np.int64), 0)
    return init + out


def divide_dynamic(replicas, candidates, avail, prev, fresh):
    """Dynamic-weight AssignReplicas over [B, C] arrays.

    Returns (assignment int64[B, C], unschedulable bool[B])."""
    num = replicas.astype(np.int64)
    prev = prev.astype(np.int64)
    avail = np.where(candidates, avail, 0).astype(np.int64)
    prev_cand = np.where(candidates, prev, 0)
    assigned = prev_cand.sum(axis=1)
    fresh = fresh.astype(bool)
    down = ~fresh & (assigned > num)
    up = ~fresh & (assigned < num)
    noop = ~fresh & (assigned == num)
    target = np.where(up, num - assigned, num)
    w = np.where(fresh[:, None], avail + prev_cand,
                 np.where(down[:, None], prev, avail))
    init = np.where(up[:, None], prev_cand, 0)
    unsched = ~noop & (w.sum(axis=1) < target)
    w = np.where((noop | unsched)[:, None], 0, w)
    out = _dispense(target, w, init, init)
    out = np.where(noop[:, None], prev_cand, out)
    out = np.where((unsched | (num == 0))[:, None], 0, out)
    return out, unsched


def place(replicas, requests, prof_idx, tolerates, prev, fresh, cap, tainted,
          has_summary=None, rows: int = 2048):
    """The whole reference for a batch of bindings, in blocks of ``rows``.

    replicas int[B]; requests int64[P, R]; prof_idx int[B]; tolerates
    bool[B]; prev int[B, C]; fresh bool[B]; cap int64[C, R]; tainted
    bool[C]. Returns (assignment int64[B, C], unschedulable bool[B])."""
    table = estimate(cap, requests, has_summary)
    outs, uns = [], []
    for s in range(0, len(replicas), rows):
        sl = slice(s, s + rows)
        cand = (~tainted[None, :] | tolerates[sl, None]) | (prev[sl] > 0)
        avail = merge(replicas[sl], table[prof_idx[sl]])
        o, u = divide_dynamic(replicas[sl], cand, avail, prev[sl], fresh[sl])
        outs.append(o)
        uns.append(u)
    return np.concatenate(outs), np.concatenate(uns)
