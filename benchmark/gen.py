"""The one traffic generator: everything a cell feeds the program, as plain
numpy arrays made from ``--seed``. It imports nothing of the program; the
drivers turn these arrays into the program's API objects, and the reference
(benchmark/reference) reads the same arrays. Parameters come from the
cell's config and traffic files, never from code.

Copied arithmetic (originals stay in the program for a later PR to delete,
see PERF.md section 7): the binding mix of ``bench.build_headline_workload``
and the churn tier's drift in ``bench.run_engine_north_star``; the fixed
seeds there are the content stream here, and ``--seed`` permutes it.
"""

from __future__ import annotations

import numpy as np

GIB = 1 << 30
MIB = 1 << 20
#: canonical units: cpu in milli-cores, memory in bytes, pods a count
DIMS = ("cpu", "memory", "pods")

_STREAMS = {"fleet": 1, "bindings": 2, "traffic": 3, "check": 4}


def rng(seed: int, stream: str) -> np.random.Generator:
    """One independent stream per use, so adding a draw to one never
    shifts another. ``seed`` may exceed 2**31."""
    return np.random.default_rng([int(seed), _STREAMS[stream]])


def content(stream: str) -> np.random.Generator:
    """The stream a deployment's CONTENT is drawn from: the same for every
    seed. ``--seed`` then permutes it (which binding holds which row, which
    member which load) and draws the traffic, so every seed gives the same
    set of sizes in another order. Content drawn per seed moved the
    engine's entry demand by ~1.5%, into another 64k bucket of its entry
    buffer, and each new seed paid a 42 s compile in set-up (PERF.md 6)."""
    return np.random.default_rng([0x6B61726D, _STREAMS[stream]])


def member_order(cfg: dict, seed: int) -> np.ndarray:
    """int[C]: the member index each content cluster takes under ``seed``."""
    return rng(seed, "fleet").permutation(int(cfg["clusters"]))


def fleet(cfg: dict, seed: int) -> dict:
    """Member clusters at the source's own size: every cluster holds
    ``nodes`` nodes of one shape and about ``running_pods`` pods of other
    tenants (distinct counts within +-``running_pods_spread`` of it, so
    that no two clusters tie), each asking for ``running_pod``."""
    c = int(cfg["clusters"])
    f = cfg["fleet"]
    node, nodes = f["node"], int(f["nodes"])
    pod = f["running_pod"]
    allocatable = np.tile(np.asarray([
        nodes * int(node["cpu_cores"]) * 1000,
        nodes * int(node["memory_gib"]) * GIB,
        nodes * int(node["pods"]),
    ], np.int64), (c, 1))
    mid, half = int(f["running_pods"]), float(f["running_pods_spread"])
    running = np.empty(c, np.int64)
    running[member_order(cfg, seed)] = content("fleet").choice(
        np.arange(int(mid * (1 - half)), int(mid * (1 + half)) + 1), c,
        replace=False)
    per_pod = np.asarray(
        [int(pod["cpu_milli"]), int(pod["memory_mib"]) * MIB, 1], np.int64)
    return {
        # zero-padded, so that sorted by name is sorted by index
        "names": [f"member-{i:0{len(str(c - 1))}d}" for i in range(c)],
        "allocatable": allocatable,
        "allocated": running[:, None] * per_pod[None, :],
    }


def request_profiles(cfg: dict) -> np.ndarray:
    """int64[P, 3] per-replica requests (cpu milli, memory bytes, 1 pod)."""
    return np.asarray(
        [[int(p["cpu_milli"]), int(p["memory_mib"]) * MIB, 1]
         for p in cfg["request_profiles"]], np.int64)


def bindings(cfg: dict, seed: int) -> dict:
    """The resident ResourceBindings of an engine deployment."""
    r = content("bindings")
    b, c = int(cfg["bindings"]), int(cfg["clusters"])
    k = cfg["bindings_mix"]
    smax = int(k["prev_sites_max"])
    out = {
        "replicas": r.integers(k["replicas_min"], k["replicas_max"] + 1, b),
        "prof_idx": r.integers(0, len(cfg["request_profiles"]), b),
        "has_prev": r.random(b) < float(k["prev_fraction"]),
        "prev_sites": r.integers(0, c, (b, smax)),
        "prev_counts": r.integers(1, int(k["prev_count_max"]) + 1, (b, smax)),
        "n_prev": r.integers(1, smax + 1, b),
        "fresh": r.random(b) < float(k["fresh_fraction"]),
    }
    out["n_prev"] = np.where(out["has_prev"], out["n_prev"], 0)
    out["prev_sites"] = member_order(cfg, seed)[out["prev_sites"]]
    rows = rng(seed, "bindings").permutation(b)
    return {k: v[rows] for k, v in out.items()}


def prev_dense(bind: dict, rows: np.ndarray, c: int) -> np.ndarray:
    """int64[len(rows), C] previous result of the given rows (a site drawn
    twice keeps its last count, as a dict built in order would)."""
    out = np.zeros((len(rows), c), np.int64)
    for j, i in enumerate(rows):
        n = int(bind["n_prev"][i])
        out[j, bind["prev_sites"][i, :n]] = bind["prev_counts"][i, :n]
    return out


def disjoint_sets(n_total: int, ring: int, rows: int, seed: int) -> np.ndarray:
    """int64[ring, rows]: ``ring`` disjoint sorted row sets from the seed."""
    if ring * rows > n_total:
        raise ValueError(f"{ring} sets of {rows} rows do not fit {n_total}")
    perm = rng(seed, "traffic").permutation(n_total)[: ring * rows]
    return np.sort(perm.reshape(ring, rows), axis=1)


def _walk(fl: dict, traffic: dict, cfg: dict, seed: int, n: int) -> list:
    """The fleet's own allocation and ``n`` drift steps on from it: every
    member's allocation moves by a whole number in [-max_steps, max_steps]
    of allocatable // step_divisor per dim a step, clamped to
    [0, allocatable]. The steps are content, like the rest: the same for
    every seed, handed to the members in the seed's order."""
    alloc = fl["allocatable"]
    unit = np.maximum(1, alloc // int(traffic["step_divisor"]))
    m = int(traffic["max_steps"])
    steps = np.empty((n,) + alloc.shape, np.int64)
    steps[:, member_order(cfg, seed)] = content("traffic").integers(
        -m, m + 1, (n,) + alloc.shape)
    out = [fl["allocated"]]
    for k in range(n):
        out.append(np.clip(out[-1] + steps[k] * unit, 0, alloc))
    return out


def drift_ring(fl: dict, traffic: dict, cfg: dict, seed: int) -> list:
    """``ring`` allocated-arrays: ring/2 steps out and the same steps back,
    ending on the fleet's own allocation, so every move of the ring, the
    wrap from its last element to its first included, is ONE step.
    (A ring of independent steps wraps with a move of ring steps at once;
    what that one wave costs turned out to depend on the seed.)"""
    half = int(traffic["ring"]) // 2
    if half * 2 != int(traffic["ring"]) or half < 1:
        raise ValueError("a drift ring has an even number of elements")
    out = _walk(fl, traffic, cfg, seed, half)
    # a1 .. a_half, a_(half-1) .. a0
    return out[1:] + out[half - 1::-1]


def drift_pair(fl: dict, traffic: dict, cfg: dict, seed: int) -> np.ndarray:
    """int64[2, C, R]: the fleet's own allocation and one drift step from
    it, the two states a cluster's status report alternates between."""
    return np.stack(_walk(fl, traffic, cfg, seed, 1))


def sample_rows(n_total: int, n: int, seed: int, salt: int) -> np.ndarray:
    """Sorted sample of rows to compare, drawn from the seed."""
    r = np.random.default_rng([int(seed), _STREAMS["check"], int(salt)])
    return np.sort(r.choice(n_total, min(n, n_total), replace=False))


def sample_waves(n_est: int, n: int, seed: int) -> set:
    """Which waves of the window keep their answers for the comparison."""
    r = np.random.default_rng([int(seed), _STREAMS["check"], 1 << 20])
    return set(int(x) for x in r.choice(max(n_est, 1), min(n, max(n_est, 1)),
                                        replace=False))


def deployments(cfg: dict, seed: int) -> np.ndarray:
    """int64[N] template replicas of a plane's resident Deployments."""
    k = cfg["deployments"]
    reps = content("bindings").integers(
        k["replicas_min"], k["replicas_max"] + 1, int(k["count"]))
    return rng(seed, "bindings").permutation(reps)
