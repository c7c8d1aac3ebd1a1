"""What a ``tainted`` deployment draws (BASELINE.json config 5, the
descheduler's rebalance storm): heterogeneous members, a slice of them
carrying a ``NoSchedule`` taint, and resident bindings of which a share
tolerates it. Plain numpy, nothing of the program: the driver
(drivers/tainted.py) turns these into the program's API objects, the
reference (reference/divide.py) reads the same arrays. Parameters come from
the configuration's ``members`` and ``bindings_mix``.

Like the rest of a deployment, all of it is content: the same for every
seed, dealt to the members (gen.member_order) and to the positions
(gen.rng(seed, "bindings")) in the seed's order.
"""

from __future__ import annotations

import numpy as np

from . import gen


def _content(stream: int) -> np.random.Generator:
    return np.random.default_rng([0x6B61726D, 0x544E54, stream])


def members(cfg: dict, seed: int) -> dict:
    """The member clusters, by index: ``names``, ``allocatable`` and
    ``allocated`` int64[C, 3] in the generator's canonical units (gen.DIMS),
    ``tainted`` bool[C]. A member holds ``nodes_min``..``nodes_max`` nodes of
    one of ``node_cores`` cores, ``memory_gib_per_core`` GiB a core and
    ``pods_per_node`` pods a node; other tenants hold a share uniform over
    ``allocated_min``..``allocated_max`` of each dimension."""
    c = int(cfg["clusters"])
    m = cfg["members"]
    r = _content(0)
    nodes = r.integers(int(m["nodes_min"]), int(m["nodes_max"]) + 1, c)
    cores = r.choice(np.asarray(m["node_cores"], np.int64), c) * nodes
    frac = r.uniform(float(m["allocated_min"]), float(m["allocated_max"]), c)
    taint = r.random(c) < float(m["taint_fraction"])
    content = np.stack([
        cores * 1000,
        cores * int(m["memory_gib_per_core"]) * gen.GIB,
        nodes * int(m["pods_per_node"]),
    ], axis=1).astype(np.int64)
    held = np.floor(content * frac[:, None]).astype(np.int64)
    order = gen.member_order(cfg, seed)
    allocatable = np.empty_like(content)
    allocated = np.empty_like(held)
    tainted = np.empty(c, bool)
    allocatable[order], allocated[order], tainted[order] = content, held, taint
    return {
        # zero-padded, so that sorted by name is sorted by index
        "names": [f"member-{i:0{len(str(c - 1))}d}" for i in range(c)],
        "allocatable": allocatable,
        "allocated": allocated,
        "tainted": tainted,
    }


def bindings(cfg: dict, seed: int) -> dict:
    """The resident bindings: the sibling's arrays (gen.bindings: replicas,
    profile, previous result, trigger) and ``tolerates``, bool[B]: the
    binding's placement tolerates the members' taint."""
    b = int(cfg["bindings"])
    out = gen.bindings(cfg, seed)
    share = float(cfg["bindings_mix"]["tolerate_fraction"])
    out["tolerates"] = (_content(1).random(b) < share)[
        gen.rng(seed, "bindings").permutation(b)]
    return out


def tainted_prev(bd: dict, tainted: np.ndarray) -> np.ndarray:
    """bool[B]: the binding holds a previous site on a tainted member."""
    sites = np.arange(bd["prev_sites"].shape[1])[None, :]
    held = sites < bd["n_prev"][:, None]
    return (held & tainted[bd["prev_sites"]]).any(axis=1)
