"""What a ``cl2load`` deployment draws (benchmark/gen.py for the members): the
Deployments of ClusterLoader2's load test in its three groups (small /
medium / big = 5 / 30 / 250 pods), each group's previous results, and the
ring of scale phases, in which a share of every group holds a rescaled copy
of its Deployment. Plain numpy, nothing of the program: the driver
(drivers/cl2load.py) turns these into the program's API objects, the
reference (reference/divide.py) reads the same arrays. Parameters come from
the configuration's ``groups`` and ``bindings_mix`` and from the traffic.

Like the rest of a deployment, all of it is content: the same for every
seed, dealt to the positions and to the members in the seed's order.
"""

from __future__ import annotations

import math

import numpy as np

from . import gen
from .reference import divide


def _content(stream: int) -> np.random.Generator:
    return np.random.default_rng([0x6B61726D, 0x434C32, stream])


def group_sizes(cfg: dict) -> tuple:
    """(int[G] pods a Deployment of each group, int[G] Deployments in it)."""
    groups = cfg["groups"]
    return (np.asarray([int(g["pods"]) for g in groups], np.int64),
            np.asarray([int(g["deployments"]) for g in groups], np.int64))


def scale_range(cfg: dict, pods: int) -> tuple:
    """The replicas a rescaled Deployment of ``pods`` may take, inclusive:
    ClusterLoader2's MultiplyInt of the size by 1 -/+ the scale factor,
    which truncates (5 pods: 2-7)."""
    f = float(cfg["scale_factor"])
    return int(pods * (1 - f)), int(pods * (1 + f))


def bindings(cfg: dict, seed: int, fleet: dict, profiles: np.ndarray) -> dict:
    """The resident Deployments, by position: ``group``, ``replicas`` (the
    group's size), ``prof_idx``, ``fresh``; previous results as the
    sibling's (gen.bindings) for a group whose ``prev`` is ``sampled``
    (``n_prev`` sites in ``prev_sites`` / ``prev_counts``), and for one
    whose ``prev`` is ``divided`` the reference's own division of its size
    over the fleet's own allocation (row ``wide_of`` of ``wide_prev``,
    int64[W, C]; every member it names)."""
    pods, counts = group_sizes(cfg)
    b, c = int(counts.sum()), int(cfg["clusters"])
    if b != int(cfg["deployments"]):
        raise ValueError(f"the groups hold {b} Deployments, the file says "
                         f"{cfg['deployments']}")
    k = cfg["bindings_mix"]
    smax = int(k["prev_sites_max"])
    r = _content(0)
    group = np.repeat(np.arange(len(pods)), counts)
    out = {
        "group": group,
        "replicas": pods[group],
        "prof_idx": r.integers(0, len(profiles), b),
        "has_prev": r.random(b) < float(k["prev_fraction"]),
        "prev_sites": r.integers(0, c, (b, smax)),
        "prev_counts": r.integers(1, int(k["prev_count_max"]) + 1, (b, smax)),
        "n_prev": r.integers(1, smax + 1, b),
        "fresh": r.random(b) < float(k["fresh_fraction"]),
    }
    out["n_prev"] = np.where(out["has_prev"], out["n_prev"], 0)
    out["prev_sites"] = gen.member_order(cfg, seed)[out["prev_sites"]]
    divided = np.asarray([g["prev"] == "divided" for g in cfg["groups"]])
    wide = np.flatnonzero(divided[group] & out["has_prev"])
    w, _ = divide.place(
        out["replicas"][wide], profiles, out["prof_idx"][wide],
        np.zeros(len(wide), bool), np.zeros((len(wide), c), np.int64),
        np.zeros(len(wide), bool), fleet["allocatable"] - fleet["allocated"],
        np.zeros(c, bool))
    out["wide_of"] = np.full(b, -1, np.int64)
    out["wide_of"][wide] = np.arange(len(wide))
    out["n_prev"][wide] = (w > 0).sum(axis=1)
    rows = gen.rng(seed, "bindings").permutation(b)
    bd = {key: v[rows] for key, v in out.items()}
    bd["wide_prev"] = w
    return bd


def prev_dict(bd: dict, i: int, names: list) -> dict:
    """The previous result of position ``i`` as a binding holds it."""
    j = int(bd["wide_of"][i])
    if j >= 0:
        w = bd["wide_prev"][j]
        return {names[s]: int(w[s]) for s in np.flatnonzero(w).tolist()}
    return {names[bd["prev_sites"][i, s]]: int(bd["prev_counts"][i, s])
            for s in range(bd["n_prev"][i])}


def prev_dense(bd: dict, rows: np.ndarray, c: int) -> np.ndarray:
    """int64[len(rows), C] previous result of the given positions."""
    out = gen.prev_dense(bd, rows, c)
    wide = bd["wide_of"][rows]
    at = np.flatnonzero(wide >= 0)
    out[at] = bd["wide_prev"][wide[at]]
    return out


def scale_ring(cfg: dict, traffic: dict, bd: dict, seed: int) -> list:
    """One scale phase a ring step: [{"rows": int[S] positions (sorted),
    "replicas": int[S]}]. In step k a ``scale_share`` of every group
    (rounded) holds a rescaled copy, its replicas uniform over
    ``scale_range`` of the group's size; the rows are drawn anew every step
    from the content stream and dealt to positions in the seed's order."""
    pods, counts = group_sizes(cfg)
    share = float(traffic["scale_share"])
    b = int(counts.sum())
    # position of each content row under the seed's permutation
    where = np.empty(b, np.int64)
    where[gen.rng(seed, "bindings").permutation(b)] = np.arange(b)
    starts = np.concatenate([[0], np.cumsum(counts)])
    r = _content(1)
    ring = []
    for _ in range(int(traffic["ring"])):
        rows, reps = [], []
        for g, (s, n) in enumerate(zip(pods.tolist(), counts.tolist())):
            m = int(math.floor(share * n + 0.5))
            lo, hi = scale_range(cfg, s)
            rows.append(where[starts[g] + r.choice(n, m, replace=False)])
            reps.append(r.integers(lo, hi + 1, m))
        rows, reps = np.concatenate(rows), np.concatenate(reps)
        order = np.argsort(rows)
        ring.append({"rows": rows[order], "replicas": reps[order]})
    return ring


def step_replicas(bd: dict, step: dict) -> np.ndarray:
    """int64[B]: every position's replicas in a ring step."""
    out = bd["replicas"].copy()
    out[step["rows"]] = step["replicas"]
    return out


def wide_rows(cfg: dict, bd: dict, replicas: np.ndarray) -> np.ndarray:
    """bool[B]: the rows past the fleet table's row bounds the
    configuration names (``row_bounds``: replicas, previous sites)."""
    bound = cfg["row_bounds"]
    return ((replicas > int(bound["replicas"]))
            | (bd["n_prev"] > int(bound["prev_sites"])))
