"""Plain reference of Karmada's scheduler for a federation configured for
failover, the semantics the ``fed-100c-failover`` cells hold the program to.
Independent of ``karmada_tpu``: numpy and plain Python, inputs made by the
benchmark's own generator; it imports the dividers that are already here
(reference/divide.py, reference/policies.py) and nothing else.

What it states (Karmada docs, userguide/failover/{failover-overview,
application-failover}, userguide/scheduling/resource-propagating "Multiple
cluster affinity groups"; pkg/scheduler/scheduler.go
scheduleResourceBindingWithClusterAffinities, the in-tree filter plugins
ClusterAffinity, TaintToleration, ClusterEviction, APIEnablement):

- a placement holds ordered, named affinity groups (``clusterAffinities``).
  The scheduler takes them in order: filter the members by the group's
  selector and the other plugins, divide the binding over what is left, and
  keep the FIRST group whose division succeeds; a group without a
  candidate, or whose candidates cannot hold the replicas, sends the
  scheduler to the next one. When no group succeeds the binding carries
  the LAST group's failure. The answer names the group it was divided on
  (``status.schedulerObservedAffinityName``); a placement without groups
  has one unnamed group of every member;
- filter: a member is a candidate of a group when the group's label
  selector matches its labels (matchLabels: every pair equal); it carries
  no NoSchedule / NoExecute taint the placement's ``clusterTolerations``
  leave untolerated (a toleration by key with operator Exists tolerates
  that key's taints whatever their effect), or it already holds the
  binding (taint_toleration.go's leniency); the binding holds no
  graceful-eviction task on it (ClusterEviction: the member left
  ``spec.clusters`` when the task was made, and the scheduler keeps off it
  until the task is gone); it advertises the binding's API, or holds the
  binding and has not reported CompleteAPIEnablements;
- divide, on that group's candidates: Duplicated gives every candidate the
  replicas and succeeds where a candidate exists; Divided / Weighted by
  AvailableReplicas is divide.divide_dynamic and Divided / Aggregated
  policies.assign_aggregated: both fail where the candidates' weights
  (availability, credited with what the binding holds when it is
  rescheduled afresh; the full previous result on a scale-down) do not
  cover the target.

The arithmetic is exact integer arithmetic: the configuration states no
precision, so the control breaks a guarantee instead
(traffic/regionloss.py).
"""

from __future__ import annotations

import numpy as np

from . import divide, policies

NO_FIT = "no clusters fit"
NOT_ENOUGH = "not enough replicas"


def term_masks(placements: list, members: dict) -> list:
    """For each placement bool[T, C]: the members each group selects."""
    return [
        np.stack([policies.label_match(members["labels"], sel)
                  for _, sel in pl["terms"]])
        for pl in placements
    ]


def tolerated(placement: dict, tainted: np.ndarray,
              taint_keys: tuple) -> np.ndarray:
    """bool[C]: members whose taints the placement tolerates (all of them
    carry the taints ``taint_keys`` where ``tainted``)."""
    if all(k in placement["tolerates"] for k in taint_keys):
        return np.ones(len(tainted), bool)
    return ~tainted


def place(placements: list, kind, replicas, requests, prof_idx, prev, evict,
          fresh, cap, members: dict, tainted: np.ndarray,
          taint_keys: tuple = (), tasks: bool = True,
          first_group_only: bool = False):
    """Every binding of a batch under its own placement.

    ``placements``: [{"strategy": duplicated | dynamic | aggregated,
    "terms": [(name, {label: value} or None)], "tolerates": [taint key]}];
    ``kind`` int[B] indexes it; replicas int[B]; requests int64[P, R];
    prof_idx int[B]; prev int[B, C]; evict bool[B, C]: the members a
    binding holds an eviction task on; fresh bool[B]; cap int64[C, R] =
    allocatable - allocated; ``members``: {"labels": [dict], "api_enabled",
    "api_complete": bool[C]}; tainted bool[C]: the members that carry the
    NoExecute taints ``taint_keys``. ``tasks=False`` runs the same
    reference with every eviction task left out, ``first_group_only=True``
    with every group but the first left out (the cells' control).

    Returns (assignment int64[B, C], group int[B]: the index of the group
    the answer names, error [str]: "" or NO_FIT or NOT_ENOUGH, group0 bool[B]:
    whether the first group had a candidate at all)."""
    b, c = prev.shape
    masks = term_masks(placements, members)
    avail = divide.merge(replicas, divide.estimate(cap, requests)[prof_idx])
    avail = np.where(np.asarray(replicas)[:, None] == 0, 0, avail)
    out = np.zeros((b, c), np.int64)
    group = np.zeros(b, np.int64)
    errors = [""] * b
    group0 = np.zeros(b, bool)
    for i in range(b):
        pl = placements[int(kind[i])]
        held = prev[i] > 0
        base = (
            (members["api_enabled"] | (held & ~members["api_complete"]))
            & (tolerated(pl, tainted, taint_keys) | held)
        )
        if tasks:
            base = base & ~evict[i]
        n = int(replicas[i])
        terms = masks[int(kind[i])]
        if first_group_only:
            terms = terms[:1]
        for t, selected in enumerate(terms):
            cand = base & selected
            group[i] = t
            if t == 0:
                group0[i] = cand.any()
            if not cand.any():
                errors[i] = NO_FIT
                continue
            if pl["strategy"] == "duplicated":
                row, short = np.where(cand, n, 0), False
            elif pl["strategy"] == "aggregated":
                row, short = policies.assign_aggregated(
                    n, cand, avail[i], prev[i], bool(fresh[i]))
            elif pl["strategy"] == "dynamic":
                rows, shorts = divide.divide_dynamic(
                    replicas[i:i + 1], cand[None, :], avail[i:i + 1],
                    prev[i:i + 1], fresh[i:i + 1])
                row, short = rows[0], bool(shorts[0])
            else:
                raise ValueError(f"strategy {pl['strategy']!r}")
            if short:
                errors[i] = NOT_ENOUGH
                continue
            out[i], errors[i] = row, ""
            break
    return out, group, errors, group0
