"""Node-level content of the estimator deployments: what each member's
scheduler-estimator sees (free cpu / memory / pods of every node) and the
churn that moves it, as plain numpy arrays made from ``--seed``. Like
``gen.py`` it imports nothing of the program, and like there the CONTENT is
the same for every seed: ``--seed`` permutes which member holds which load
and which node which row, so every seed gives the same multiset of node
states in another order (and lands in the same compiled shapes).

A node runs pods sized from the configuration's request profiles: a pod of
size k asks for k units, a unit being the smallest profile. A node's state
is (units in use, pods running); its free resources follow from the node's
shape. Within a member the load is spread over the nodes with a Zipf-like
skew (weight of the node at rank i is (i + 1) ** -s, s drawn per member),
water-filled under the node's capacity so that the member's CPU utilisation
comes out at its drawn share.
"""

from __future__ import annotations

import numpy as np

from . import gen

#: the estimator's resource dims, a prefix of the scheduler's own
DIMS = gen.DIMS


def _content(salt: int) -> np.random.Generator:
    return np.random.default_rng([0x6B61726D, 5, salt])


def _shape(cfg: dict) -> tuple:
    """(free resources of an empty node int64[3], one unit int64[3], units a
    node holds, pods a node holds)."""
    node = cfg["fleet"]["node"]
    unit = cfg["request_profiles"][0]
    empty = np.asarray([int(node["cpu_cores"]) * 1000,
                        int(node["memory_gib"]) * gen.GIB,
                        int(node["pods"])], np.int64)
    per_unit = np.asarray([int(unit["cpu_milli"]),
                           int(unit["memory_mib"]) * gen.MIB, 0], np.int64)
    cap = int(min(empty[0] // per_unit[0], empty[1] // per_unit[1]))
    return empty, per_unit, cap, int(node["pods"])


def _water_fill(weights: np.ndarray, total: float, cap: int) -> np.ndarray:
    """float[C, N] loads proportional to ``weights`` under ``cap`` a node,
    summing to ``total`` a member (bisection on the scale)."""
    lo = np.zeros(len(weights))
    hi = np.full(len(weights), 1e12)
    for _ in range(60):
        mid = (lo + hi) / 2
        short = np.minimum(mid[:, None] * weights, cap).sum(axis=1) < total
        lo, hi = np.where(short, mid, lo), np.where(short, hi, mid)
    return np.minimum(hi[:, None] * weights, cap)


def _pods_for(units: np.ndarray, sizes: int, r: np.random.Generator) -> np.ndarray:
    """Pods a node runs for its units in use: sizes drawn 1..``sizes`` until
    the units are spent (the last pod takes what is left)."""
    left = units.copy()
    pods = np.zeros_like(units)
    while (left > 0).any():
        pods += left > 0
        left = np.maximum(left - r.integers(1, sizes + 1, left.shape), 0)
    return pods


def states(cfg: dict, traffic: dict | None, seed: int) -> list:
    """[(units int64[C, N], pods int64[C, N])]: the federation's own state
    and, with ``traffic``, ring/2 churn steps on from it. A step: in every
    member ``nodes_per_step`` nodes gain or lose 1..``pods_max`` pods of
    1..sizes units each, clamped to what a node holds."""
    c, n = int(cfg["clusters"]), int(cfg["fleet"]["nodes"])
    f = cfg["fleet"]
    _empty, _unit, cap, pod_cap = _shape(cfg)
    sizes = len(cfg["request_profiles"])
    r = _content(0)
    util = r.permutation(np.linspace(
        float(f["utilisation_min"]), float(f["utilisation_max"]), c))
    skew = r.uniform(float(f["skew_min"]), float(f["skew_max"]), c)
    ranks = np.argsort(r.random((c, n)), axis=1)  # which node takes which rank
    weights = (ranks + 1.0) ** -skew[:, None]
    units = np.floor(_water_fill(weights, util * n * cap, cap)).astype(np.int64)
    pods = np.minimum(_pods_for(units, sizes, r), pod_cap)
    out = [(units, pods)]
    if traffic is not None:
        k, pmax = int(traffic["nodes_per_step"]), int(traffic["pods_max"])
        r = _content(1)
        for _ in range(int(traffic["ring"]) // 2):
            units, pods = units.copy(), pods.copy()
            hit = np.argsort(r.random((c, n)), axis=1)[:, :k]
            rows = np.arange(c)[:, None]
            n_pods = r.integers(1, pmax + 1, (c, k))
            sign = r.choice([-1, 1], (c, k))
            dp = sign * n_pods
            du = sign * np.where(
                np.arange(pmax) < n_pods[:, :, None],
                r.integers(1, sizes + 1, (c, k, pmax)), 0).sum(axis=2)
            p_new = np.clip(pods[rows, hit] + dp, 0, min(pod_cap, cap))
            u_new = np.clip(units[rows, hit] + du, p_new, cap)
            u_new = np.where(p_new == 0, 0, u_new)
            units[rows, hit], pods[rows, hit] = u_new, p_new
            out.append((units, pods))
    # the seed's order: which member holds which content, which node which row
    members = gen.member_order(cfg, seed)
    node_perm = gen.rng(seed, "fleet").permutation(n)
    placed = []
    for u, p in out:
        pu, pp = np.empty_like(u), np.empty_like(p)
        pu[members], pp[members] = u[:, node_perm], p[:, node_perm]
        placed.append((pu, pp))
    return placed


def free(cfg: dict, state: tuple) -> np.ndarray:
    """int64[C, N, 3] free cpu (milli) / memory (bytes) / pods of every node."""
    empty, per_unit, _cap, _pods = _shape(cfg)
    units, pods = state
    out = empty[None, None, :] - units[:, :, None] * per_unit[None, None, :]
    out[:, :, 2] -= pods
    return out


def ring(cfg: dict, traffic: dict, seed: int) -> list:
    """``ring`` node states int64[C, N, 3]: ring/2 steps out and the same
    steps back, ending on the federation's own state, so every move of the
    ring, the wrap included, is ONE step (as ``gen.drift_ring``). States
    visited twice are the same array."""
    half = int(traffic["ring"]) // 2
    if half * 2 != int(traffic["ring"]) or half < 1:
        raise ValueError("a churn ring has an even number of elements")
    out = [free(cfg, s) for s in states(cfg, traffic, seed)]
    return out[1:] + out[half - 1::-1]


def federation(cfg: dict) -> dict:
    """The members as the scheduler sees them at the federation's own node
    state: names, allocatable int64[C, 3] (nodes x the node's shape)."""
    c, n = int(cfg["clusters"]), int(cfg["fleet"]["nodes"])
    empty = _shape(cfg)[0]
    return {
        "names": [f"member-{i:0{len(str(c - 1))}d}" for i in range(c)],
        "allocatable": np.tile(empty * n, (c, 1)),
    }


def summaries(allocatable: np.ndarray, node_free: np.ndarray) -> np.ndarray:
    """int64[C, 3] allocated: a member's ResourceSummary is the sum over
    its nodes, at every step."""
    return allocatable - node_free.sum(axis=1)
