"""Plain reference of FederatedResourceQuota enforcement in a scheduling
wave, for the cells that hold the program to it. Independent of
``karmada_tpu`` (and of its ``refimpl/``): numpy and Python only, inputs
made by the benchmark's own generator. It states the semantics of Karmada's
FederatedResourceQuota (pkg/apis/policy/v1alpha1
federatedresourcequota_types.go; the FederatedQuotaEnforcement gate):

- admission: a namespace's quota leaves ``overall - overallUsed`` of each
  resource it tracks. The wave's bindings are taken in the order they are
  presented; each asks for its delta (replicas beyond what it holds, times
  its request; a replica also occupies a pod). A binding that asks for
  nothing (it holds what it wants, or scales down) is not the quota's to
  deny: it is always admitted and adds nothing, as upstream's enforcement
  lets a delta that is not positive through. A binding that asks is
  admitted iff the demand of its namespace so far, its own included, fits
  what is left on every tracked resource. A denied binding keeps its place
  in line: what it asked stays counted, so a later, smaller one does not
  pass it. A binding of a namespace without a quota is always admitted.
  Admission is coupled over the WHOLE wave: a sample cannot be admitted
  alone;
- static assignments: ``spec.staticAssignments[]`` = a member + ``hard``;
  a binding of such a namespace can hold on that member at most
  ``min over the resources it requests of floor(hard / request)``
  replicas: one more estimator answer, min-merged with the general one;
- a denied binding is not scheduled (its answer carries no placement), an
  admitted one is divided by reference/divide.py under
  ``min(general estimate, static-assignment ceiling)``.

The arithmetic is exact (integers)."""

from __future__ import annotations

import numpy as np

from . import divide

#: what the program answers a denied binding with, as the reference's class
QUOTA = "quota exceeded"
UNLIMITED = 2**62


def admit(ns: np.ndarray, demand: np.ndarray,
          remaining: np.ndarray) -> np.ndarray:
    """bool[B] admitted, one binding at a time in presented order.

    ``ns`` int[B]: the quota's row of the binding's namespace, -1 = none;
    ``demand`` int64[B, R] >= 0; ``remaining`` int64[N, R], UNLIMITED where
    the quota does not track the resource."""
    left = [[int(v) for v in row] for row in remaining]
    asked = [[0] * remaining.shape[1] for _ in left]
    out = np.ones(len(ns), bool)
    for i, (n, row) in enumerate(zip(ns.tolist(), demand.tolist())):
        if n < 0 or not any(row):
            continue
        run = asked[n]
        ok = True
        for d, v in enumerate(row):
            run[d] += v  # the demand holds its place whether or not it fits
            if run[d] > left[n][d]:
                ok = False
        out[i] = ok
    return out


def ceiling(caps: np.ndarray, cap_row: np.ndarray,
            requests: np.ndarray) -> np.ndarray:
    """int64[B, C] replicas each member's static assignment lets a binding
    hold (divide.MAX_INT32 = no constraint). ``caps`` int64[Ncap, C, R]
    (UNLIMITED = none); ``cap_row`` int[B], -1 = the binding's namespace
    has no static assignment; ``requests`` int64[B, R]."""
    b, c = len(cap_row), caps.shape[1]
    out = np.full((b, c), divide.MAX_INT32, np.int64)
    for i in np.flatnonzero(cap_row >= 0):
        hard = caps[cap_row[i]]
        for d in range(requests.shape[1]):
            req = int(requests[i, d])
            if req <= 0:
                continue
            fit = np.where(hard[:, d] >= UNLIMITED, divide.MAX_INT32,
                           hard[:, d] // req)
            out[i] = np.minimum(out[i], fit)
    return out


def place(admitted, replicas, requests, prof_idx, prev, fresh, cap,
          cap_row=None, caps=None, rows: int = 2048) -> tuple:
    """The division of a sample's bindings (admission was over the wave).

    ``admitted`` bool[B]; replicas int[B]; requests int64[P, R]; prof_idx
    int[B]; prev int[B, C]; fresh bool[B]; cap int64[C, R] allocatable -
    allocated; ``cap_row`` / ``caps`` as ``ceiling`` takes them (None: no
    static assignment is applied). Returns (assignment int64[B, C], error
    class [B]: QUOTA, "unschedulable" or "")."""
    b, c = len(replicas), cap.shape[0]
    table = divide.estimate(cap, requests)
    outs, errors = [], []
    for s in range(0, b, rows):
        sl = slice(s, s + rows)
        est = table[prof_idx[sl]]
        if caps is not None:
            top = ceiling(caps, cap_row[sl], requests[prof_idx[sl]])
            est = np.where(
                top < divide.MAX_INT32,
                np.minimum(np.where(est < 0, divide.MAX_INT32, est), top),
                est)
        avail = divide.merge(replicas[sl], est)
        out, uns = divide.divide_dynamic(
            replicas[sl], np.ones((len(avail), c), bool), avail, prev[sl],
            fresh[sl])
        ok = admitted[sl]
        outs.append(np.where(ok[:, None], out, 0))
        errors += [
            QUOTA if not a else "unschedulable" if u else ""
            for a, u in zip(ok.tolist(), uns.tolist())]
    return np.concatenate(outs), errors
