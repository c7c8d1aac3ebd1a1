"""What a ``quota`` deployment adds to the generator's arrays (benchmark/gen.py):
the tenant namespaces and which one each binding lives in, which of them
carry a FederatedResourceQuota and which of those ``staticAssignments``, each
quota's ``overall`` and each assignment's ``hard``, every binding's delta
demand, and the ring of usage states (``status.overallUsed`` as the status
controller would recompute it between rounds, and the quota raise). Plain
numpy and Python, nothing of the program: the driver (drivers/quota.py)
turns these into the program's API objects, the reference
(reference/quota.py) reads the same values. Parameters come from the
configuration's ``tenants`` and from the traffic mix.

Like the rest of a deployment, all of it is content: the same for every
seed, dealt to the bindings and to the members in the seed's order.
"""

from __future__ import annotations

import numpy as np

from . import gen

#: ``spec.overall`` tracks these two dims (the docs' example has both);
#: pods stay unlimited
DIMS_LIMITED = (0, 1)
UNLIMITED = 2**62


def _content(stream: int) -> np.random.Generator:
    return np.random.default_rng([0x6B61726D, 96 + stream])


def names(cfg: dict) -> list:
    """Zero-padded, so that sorted by name is sorted by index."""
    n = int(cfg["tenants"]["namespaces"])
    return [f"tenant-{i:0{len(str(n - 1))}d}" for i in range(n)]


def tenants(cfg: dict, seed: int) -> dict:
    """{"ns": int[B] the namespace of each binding (Zipf over the rank: the
    namespace of rank 0 is the hottest), "quota_row": int[N] a namespace's
    row among the quota'd ones sorted by name (-1 = no quota), "cap_row":
    int[N] its row among those with static assignments (-1 = none),
    "cap_members": int[Ncap, M] the members each assignment list names,
    "cap_k": int[Ncap, M] each one's size factor}."""
    t = cfg["tenants"]
    n, b, c = int(t["namespaces"]), int(cfg["bindings"]), int(cfg["clusters"])
    weights = 1.0 / np.arange(1, n + 1) ** float(t["zipf_s"])
    ns_content = _content(0).choice(n, b, p=weights / weights.sum())
    # the seed permutes the bindings' content (gen.bindings): the namespace
    # goes with its binding
    rows = gen.rng(seed, "bindings").permutation(b)
    hot, drawn = int(t["quotad_hot"]), int(t["quotad_drawn"])
    quotad = np.zeros(n, bool)
    quotad[:hot] = True
    quotad[hot + _content(1).choice(n - hot, drawn, replace=False)] = True
    quota_row = np.where(quotad, np.cumsum(quotad) - 1, -1)
    capped = np.zeros(n, bool)
    capped[_content(2).choice(
        np.flatnonzero(quotad), int(t["capped"]), replace=False)] = True
    cap_row = np.where(capped, np.cumsum(capped) - 1, -1)
    n_cap, m = int(capped.sum()), int(t["cap_members"])
    order = gen.member_order(cfg, seed)
    r = _content(3)
    members = np.stack([order[r.choice(c, m, replace=False)]
                        for _ in range(n_cap)])
    k = r.integers(int(t["cap_k_min"]), int(t["cap_k_max"]) + 1, (n_cap, m))
    return {"ns": ns_content[rows], "quota_row": quota_row,
            "cap_row": cap_row, "cap_members": members, "cap_k": k}


def held(bind: dict, c: int, block: int = 8192) -> np.ndarray:
    """int64[B]: the replicas each binding's previous result holds (a site
    drawn twice counts once, with its last count, as the driver's dict)."""
    b = len(bind["n_prev"])
    out = np.zeros(b, np.int64)
    for s in range(0, b, block):
        rows = np.arange(s, min(s + block, b))
        out[rows] = gen.prev_dense(bind, rows, c).sum(axis=1)
    return out


def demand(bind: dict, profiles: np.ndarray, c: int) -> tuple:
    """(int64[B, R] delta demand, int64[B, R] usage): what a binding asks
    of its namespace's quota in a wave (replicas beyond what it holds,
    times its request; each replica occupies a pod) and what it holds
    (the usage controller's formula: held replicas times the request)."""
    req = profiles[bind["prof_idx"]]
    has = held(bind, c)
    delta = np.maximum(bind["replicas"].astype(np.int64) - has, 0)
    return delta[:, None] * req, has[:, None] * req


def caps(cfg: dict, tn: dict) -> np.ndarray:
    """int64[Ncap, C, R] the ``hard`` limits of each assignment list over
    the members' columns (UNLIMITED where the list does not name the
    member or the dim): ``cap_k`` times the tenants' ``cap_unit``."""
    t = cfg["tenants"]
    c = int(cfg["clusters"])
    unit = np.asarray([int(t["cap_unit"]["cpu_milli"]),
                       int(t["cap_unit"]["memory_mib"]) * gen.MIB], np.int64)
    n_cap = len(tn["cap_members"])
    out = np.full((n_cap, c, len(gen.DIMS)), UNLIMITED, np.int64)
    for i in range(n_cap):
        for j, k in zip(tn["cap_members"][i], tn["cap_k"][i]):
            out[i, j, list(DIMS_LIMITED)] = int(k) * unit
    return out


def ring(cfg: dict, traffic: dict, tn: dict, dem: np.ndarray,
         used: np.ndarray) -> dict:
    """The ring of quota states: {"overall": int64[ring, Nq, R] each
    quota's limit (UNLIMITED for pods), "used": int64[ring, Nq, R] its
    ``status.overallUsed``, "remaining": int64[ring, Nq, R] what a wave may
    still admit, "content": int64[Nq, R] the namespace's content demand}.

    ``overall`` = the usage at step 0 + ``overall_share`` of the content
    demand; from ``raise_at`` until ``lower_at`` the ``raise_hot`` hottest
    namespaces' is ``raise_share`` of their content demand higher. Usage
    walks ring/2 steps out and the same steps back (a move: a whole number
    in [-usage_max_steps, usage_max_steps] of usage_step_pct% of the
    content demand a namespace), so every move of the ring, the wrap
    included, is one step."""
    t = cfg["tenants"]
    n_ring = int(traffic["ring"])
    half = n_ring // 2
    qrow = tn["quota_row"][tn["ns"]]
    nq = int(tn["quota_row"].max()) + 1
    r = dem.shape[1]
    content = np.zeros((nq, r), np.int64)
    used0 = np.zeros((nq, r), np.int64)
    inq = qrow >= 0
    np.add.at(content, qrow[inq], dem[inq])
    np.add.at(used0, qrow[inq], used[inq])
    m = int(traffic["usage_max_steps"])
    moves = _content(4).integers(-m, m + 1, (half, nq))
    walk = np.concatenate([np.zeros((1, nq), np.int64),
                           np.cumsum(moves, axis=0)])
    # u1 .. u_half, u_(half-1) .. u0, as gen.drift_ring orders a ring
    level = np.concatenate([walk[1:], walk[half - 1::-1]])
    pct = int(traffic["usage_step_pct"])
    share_num, share_den = _ratio(t["overall_share"])
    raise_num, raise_den = _ratio(traffic["raise_share"])
    base = used0 + content * share_num // share_den
    hot = np.flatnonzero(tn["quota_row"] >= 0)[: int(traffic["raise_hot"])]
    hot_rows = tn["quota_row"][hot]
    overall = np.empty((n_ring, nq, r), np.int64)
    usage = np.empty((n_ring, nq, r), np.int64)
    for k in range(n_ring):
        overall[k] = base
        if int(traffic["raise_at"]) <= k < int(traffic["lower_at"]):
            overall[k, hot_rows] += content[hot_rows] * raise_num // raise_den
        usage[k] = np.maximum(
            used0 + level[k][:, None] * content * pct // 100, 0)
    remaining = np.maximum(overall - usage, 0)
    for out in (overall, remaining):
        free = [d for d in range(r) if d not in DIMS_LIMITED]
        out[:, :, free] = UNLIMITED
    return {"overall": overall, "used": usage, "remaining": remaining,
            "content": content}


def _ratio(x) -> tuple:
    """A share written as a decimal, as an exact (numerator, 1000)."""
    return int(round(float(x) * 1000)), 1000


def steps(traffic: dict) -> str:
    """One letter a ring step: R the step the raise lands on, L the one
    it is taken back on, u every other (usage and availability move in
    all of them)."""
    return "".join(
        "R" if k == int(traffic["raise_at"]) else
        "L" if k == int(traffic["lower_at"]) else "u"
        for k in range(int(traffic["ring"])))


def sample_rows(strata: list, per: int, total: int, seed: int,
                salt: int, first: list | None = None) -> np.ndarray:
    """Sorted sample of ``total`` rows: ``per`` of each stratum (a bool[B]
    mask), topped up from all rows. ``first`` (one mask a stratum, or
    None): rows drawn before the stratum's others, up to half of ``per``
    (the rows a quota raise cleared, in the wave after it)."""
    r = np.random.default_rng([int(seed), gen._STREAMS["check"], 7, int(salt)])
    b = len(strata[0])
    taken = np.zeros(b, bool)
    for i, mask in enumerate(strata):
        want = per
        if first is not None and first[i] is not None:
            pool = np.flatnonzero(first[i] & mask & ~taken)
            pick = r.choice(pool, min(per // 2, len(pool)), replace=False)
            taken[pick] = True
            want -= len(pick)
        pool = np.flatnonzero(mask & ~taken)
        taken[r.choice(pool, min(want, len(pool)), replace=False)] = True
    rest = np.flatnonzero(~taken)
    short = total - int(taken.sum())
    if short > 0:
        taken[r.choice(rest, min(short, len(rest)), replace=False)] = True
    return np.flatnonzero(taken)
