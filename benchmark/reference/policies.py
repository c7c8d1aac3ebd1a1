"""Plain reference of Karmada's scheduler for the documented policy kinds
side by side, the semantics the ``fed-100c-policies`` cells hold the program
to. Independent of ``karmada_tpu``: numpy and plain Python, inputs made by
the benchmark's own generator; the one import is the dynamic-weight divider
that is already here (reference/divide.py).

What it states (Karmada docs, userguide/scheduling/resource-propagating;
pkg/scheduler/core/{generic_scheduler,assignment,division_algorithm,
select_clusters}.go, pkg/scheduler/core/spreadconstraint/, the in-tree
filter plugins):

- filter: a member is a candidate when the placement's ``clusterAffinity``
  label selector matches its labels (matchLabels: every pair equal), it
  advertises the binding's API (or already holds the binding and has not
  reported CompleteAPIEnablements: api_enablement.go's leniency) and, for a
  placement with a spread constraint by region / zone / provider, it names
  that field (the SpreadConstraint filter plugin);
- score: 100 for a member that already holds the binding (ClusterLocality),
  else 0; availability is the general estimate (divide.estimate / merge),
  credited with what the member already holds (group_clusters.go:344);
- SelectClusters, for the two constraint shapes the cells use:
  [cluster min/max]: the members in (score desc, credited availability
  desc, index asc) order, the first max-groups of them, then swap-repair
  (select_clusters_by_cluster.go: from the last kept member up, exchange
  it for the most available member left out while the kept sum is short of
  the replicas); fewer members than min-groups, or still short, is a
  FitError. [region min/max + cluster min/max]: members grouped by region
  in that order; a region's score (group_clusters.go, Divided): walk its
  members until max(cluster min, region min) of them are counted and their
  availability reaches ceil(replicas / region min): 1000 x min(target,
  sum) + the mean score of the members counted; the region search
  (select_groups.go): depth-first over regions sorted (members asc, score
  desc, name asc), a path is kept when its members reach the cluster
  min-groups and its length lies in [min, max]; the kept paths rank by
  (score sum desc, members desc, discovery order), and a shorter path that
  is a prefix of the winner replaces it; then the best member of every
  chosen region and, from the rest of those regions in (score, credited)
  order, as many as the cluster max-groups leaves room for
  (select_clusters_by_region.go). A Duplicated placement would ignore
  availability (a region scores 1000 a member that can hold all replicas);
  Divided / Weighted with a static weight list ignores spread constraints
  altogether (select_clusters.go:63-78);
- assign: Duplicated: every selected member gets ``replicas``. Static
  weights: a member's weight is the largest among the rules that name it,
  members without a positive weight get nothing, all weights zero means
  every candidate weighs 1; floors of weight x replicas / total, the
  remainder one each in (weight desc, previous replicas desc, index asc)
  order (division_algorithm.go:38-72, binding.go TakeByWeight). Dynamic
  weight: divide.divide_dynamic. Aggregated: the same three cases (steady
  scale-up dispenses the delta and keeps what is there, scale-down
  re-divides over the FULL previous result, a fresh binding re-divides all
  over availability credited with what it holds), but over the shortest
  prefix of the members, taken in (already holding, on scale-up; weight
  desc; index asc) order, whose weights cover the target
  (assignment.go:146-173).

Departures from upstream, the same the program makes: ties that upstream
breaks by name or at random are broken by member index (names sort as
indices do here); a spread constraint by zone or provider alone is a
FitError (select_clusters.go:58 supports cluster and region); the region
path does not check that the selected members can hold the replicas (the
division reports that). The arithmetic is exact integer arithmetic: the
configuration states no precision, so the control breaks a guarantee
instead (traffic/policydrift.py).
"""

from __future__ import annotations

import math

import numpy as np

from . import divide

LOCALITY_SCORE = 100
GROUP_WEIGHT = 1000


# -- filter -----------------------------------------------------------------


def label_match(labels: list, selector: dict | None) -> np.ndarray:
    """bool[C]: members whose labels carry every pair of ``selector``."""
    if not selector:
        return np.ones(len(labels), bool)
    return np.asarray(
        [all(lb.get(k) == v for k, v in selector.items()) for lb in labels],
        bool)


def candidates(placement: dict, members: dict, prev: np.ndarray) -> np.ndarray:
    """bool[C] for one binding under one placement (prev int[C])."""
    held = prev > 0
    ok = label_match(members["labels"], placement.get("affinity_labels"))
    ok = ok & (members["api_enabled"] | (held & ~members["api_complete"]))
    for field, _, _ in placement.get("spread", ()):
        if field in ("region", "zone", "provider"):
            ok = ok & np.asarray([bool(v) for v in members[field]], bool)
    return ok


# -- SelectClusters ---------------------------------------------------------


def _ordered(cand: np.ndarray, score, credited) -> list:
    return sorted(np.flatnonzero(cand).tolist(),
                  key=lambda j: (-int(score[j]), -int(credited[j]), j))


def _by_cluster(order: list, credited, lo: int, hi: int, need: int | None):
    if len(order) < max(lo, 1):
        return None
    n = min(hi, len(order)) if hi > 0 else len(order)
    kept, left = order[:n], order[n:]
    if need is None:
        return kept
    at = len(kept) - 1
    while sum(int(credited[j]) for j in kept) < need and at >= 0:
        if left:
            best = max(range(len(left)), key=lambda k: int(credited[left[k]]))
            if int(credited[left[best]]) > int(credited[kept[at]]):
                kept[at], left[best] = left[best], kept[at]
        at -= 1
    if sum(int(credited[j]) for j in kept) < need:
        return None
    return kept


def _region_score(members: list, score, credited, duplicated: bool,
                  replicas: int, region_min: int, cluster_min: int) -> int:
    if duplicated:
        able = [j for j in members if int(credited[j]) >= replicas]
        if not able:
            return 0
        return (len(able) * GROUP_WEIGHT
                + sum(int(score[j]) for j in able) // len(able))
    target = math.ceil(replicas / max(region_min, 1))
    floor = max(cluster_min, region_min)
    got = points = counted = 0
    for j in members:
        got += int(credited[j])
        points += int(score[j])
        counted += 1
        if counted >= floor and got >= target:
            break
    if got < target:
        return got * GROUP_WEIGHT + points // max(len(members), 1)
    return target * GROUP_WEIGHT + points // max(counted, 1)


def _choose_regions(groups: list, lo: int, hi: int, members_min: int) -> list:
    """``groups``: [(name, members, score)]. The chosen names, best first."""
    if hi <= 0:
        hi = len(groups)
    pool = sorted(groups, key=lambda g: (g[1], -g[2], g[0]))
    found, path = [], []

    def walk(total: int, begin: int) -> None:
        if total >= members_min and lo <= len(path) <= hi:
            best_first = sorted(path, key=lambda g: (-g[2], g[0]))
            found.append((best_first, sum(g[2] for g in path),
                          sum(g[1] for g in path), len(found)))
            return
        if len(path) >= hi:
            return
        for i in range(begin, len(pool)):
            path.append(pool[i])
            walk(total + pool[i][1], i + 1)
            if len(pool) == lo:
                # select_groups.go:180-182 leaves the loop without popping;
                # every frame above leaves on the same test
                return
            path.pop()

    walk(0, 0)
    if not found:
        return []
    found.sort(key=lambda p: (-p[1], -p[2], p[3]))
    best = found[0][0]
    for cand, _, _, _ in found[1:]:
        if len(cand) < len(best) and all(
                best[i][0] == g[0] for i, g in enumerate(cand)):
            best = cand
    return [g[0] for g in best]


def select_clusters(cand: np.ndarray, score, credited, region_of: list,
                    spread: list, replicas: int, duplicated: bool = False):
    """The members one binding may be assigned to: bool[C], or None for a
    FitError. ``spread``: [(field, min_groups, max_groups)]."""
    by = {field: (lo, hi) for field, lo, hi in spread}
    order = _ordered(cand, score, credited)
    need = None if duplicated else replicas
    if "region" in by:
        r_lo, r_hi = by["region"]
        c_lo, c_hi = by.get("cluster", (0, 0))
        regions: dict = {}
        for j in order:
            if region_of[j]:
                regions.setdefault(region_of[j], []).append(j)
        if len(regions) < max(r_lo, 1):
            return None
        chosen = _choose_regions(
            [(name, len(ms), _region_score(
                ms, score, credited, duplicated, replicas, r_lo, c_lo))
             for name, ms in regions.items()], r_lo, r_hi, c_lo)
        if not chosen:
            return None
        picked = [regions[name][0] for name in chosen]
        rest = [j for name in chosen for j in regions[name][1:]]
        room = min(len(picked) + len(rest), c_hi) - len(picked)
        if room > 0:
            rest.sort(key=lambda j: (-int(score[j]), -int(credited[j]), j))
            picked += rest[:room]
    elif "cluster" in by:
        picked = _by_cluster(order, credited, *by["cluster"], need)
        if picked is None:
            return None
    else:
        return None
    out = np.zeros(len(cand), bool)
    out[picked] = True
    return out if out.any() else None


# -- AssignReplicas ---------------------------------------------------------


def take_by_weight(num: int, weight: np.ndarray, last: np.ndarray) -> np.ndarray:
    """Dispenser.TakeByWeight for one binding: int64[C]."""
    total = int(weight.sum())
    out = np.zeros(len(weight), np.int64)
    if total == 0:
        return out
    out[:] = weight * num // total
    order = sorted(np.flatnonzero(weight > 0).tolist(),
                   key=lambda j: (-int(weight[j]), -int(last[j]), j))
    for j in order[: num - int(out.sum())]:
        out[j] += 1
    return out


def assign_static(replicas: int, cand, weights: np.ndarray, prev) -> np.ndarray:
    w = np.where(cand, np.maximum(weights, 0), 0).astype(np.int64)
    if w.sum() == 0:
        w = cand.astype(np.int64)
    return take_by_weight(replicas, w, np.where(cand, prev, 0))


def assign_aggregated(replicas: int, cand, avail, prev, fresh: bool):
    """(int64[C], unschedulable) for one Divided / Aggregated binding."""
    c = len(cand)
    prev = prev.astype(np.int64)
    held = np.where(cand, prev, 0)
    avail = np.where(cand, avail, 0).astype(np.int64)
    zero = np.zeros(c, np.int64)
    if replicas == 0:
        return zero, False
    assigned = int(held.sum())
    first = np.zeros(c, bool)
    if fresh:
        weight, target, init = avail + held, replicas, zero
    elif assigned > replicas:
        weight, target, init = prev, replicas, zero
    elif assigned < replicas:
        weight, target, init = avail, replicas - assigned, held
        first = held > 0
    else:
        return held, False
    if int(weight.sum()) < target:
        return zero, True
    order = sorted(range(c), key=lambda j: (not first[j], -int(weight[j]), j))
    kept, covered = np.zeros(c, np.int64), 0
    for j in order:
        if covered >= target:
            break
        kept[j] = weight[j]
        covered += int(weight[j])
    return init + take_by_weight(target, kept, init), False


# -- the whole reference -----------------------------------------------------


def place(placements: list, kind, replicas, requests, prof_idx, prev, fresh,
          cap, members: dict, constraints: bool = True):
    """Every binding of a batch under its own placement.

    ``placements``: [{"strategy": duplicated | static | dynamic |
    aggregated, "affinity_labels": {..} or None, "weights": int[C] (static),
    "spread": [(field, min, max)]}]; ``kind`` int[B] indexes it; replicas
    int[B]; requests int64[P, R]; prof_idx int[B]; prev int[B, C]; fresh
    bool[B]; cap int64[C, R] = allocatable - allocated; ``members``:
    {"labels": [dict], "region" / "zone" / "provider": [str],
    "api_enabled", "api_complete": bool[C]}. ``constraints=False`` runs the
    same reference with every ``spread`` left out (the cells' control).

    Returns (assignment int64[B, C], placed bool[B], selected bool[B, C]):
    ``placed`` is False for a FitError (no candidate, or constraints that
    cannot be met) and for a binding whose members cannot hold its
    replicas; ``selected`` is the set the binding was divided over (what a
    zero-replica binding, which is assigned nothing, still answers)."""
    b, c = prev.shape
    avail = divide.merge(replicas, divide.estimate(cap, requests)[prof_idx])
    # a binding without replicas asks the estimators nothing: its
    # availability is its replicas, 0 (core/util.go:54-104)
    avail = np.where(np.asarray(replicas)[:, None] == 0, 0, avail)
    out = np.zeros((b, c), np.int64)
    placed = np.zeros(b, bool)
    selected = np.zeros((b, c), bool)
    for i in range(b):
        pl = placements[int(kind[i])]
        strategy = pl["strategy"]
        cand = candidates(pl, members, prev[i])
        spread = pl.get("spread", ()) if constraints else ()
        if spread and strategy != "static" and cand.any():
            score = np.where(prev[i] > 0, LOCALITY_SCORE, 0)
            cand = select_clusters(
                cand, score, avail[i] + prev[i], members["region"], spread,
                int(replicas[i]), duplicated=strategy == "duplicated")
            if cand is None:
                continue
        if not cand.any():
            continue
        n = int(replicas[i])
        if strategy == "duplicated":
            row, short = np.where(cand, n, 0), False
        elif strategy == "static":
            row, short = assign_static(n, cand, pl["weights"], prev[i]), False
        elif strategy == "aggregated":
            row, short = assign_aggregated(
                n, cand, avail[i], prev[i], bool(fresh[i]))
        else:
            rows, shorts = divide.divide_dynamic(
                replicas[i:i + 1], cand[None, :], avail[i:i + 1],
                prev[i:i + 1], fresh[i:i + 1])
            row, short = rows[0], bool(shorts[0])
        if not short:
            out[i], placed[i], selected[i] = row, True, cand
    return out, placed, selected
