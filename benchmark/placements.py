"""What a ``policies`` deployment adds to the generator's arrays
(benchmark/gen.py): where each member lies (region, zone, provider), its
labels, the placements the tenants wrote and which one each binding rides.
Plain numpy and Python, nothing of the program: the driver
(drivers/policies.py) turns these into the program's API objects, and the
reference (reference/policies.py) reads the same values. Parameters come
from the configuration's ``layout`` and ``placements``.

Like the rest of a deployment, all of it is content: the same for every
seed, dealt to the members and to the bindings in the seed's order.
"""

from __future__ import annotations

import numpy as np

from . import gen

_CONTENT = 0x6B61726D  # gen.content's own root, with streams of its own


def _content(stream: int) -> np.random.Generator:
    return np.random.default_rng([_CONTENT, stream])


def members(cfg: dict, seed: int) -> dict:
    """Each member's topology and labels. Content cluster k lies in region
    k // (zones x members a zone), zone (k // members a zone) mod zones of
    it, at provider k mod ``providers``, and is a canary when k mod
    ``canary_every`` is the last residue; member ``gen.member_order``[k]
    takes it, as it takes content cluster k's load."""
    lay = cfg["layout"]
    c = int(cfg["clusters"])
    per_zone, zones = int(lay["members_per_zone"]), int(lay["zones_per_region"])
    if int(lay["regions"]) * zones * per_zone != c:
        raise ValueError("layout: regions x zones x members is not `clusters`")
    every = int(lay["canary_every"])
    region, zone, provider, labels = [""] * c, [""] * c, [""] * c, [None] * c
    for k, m in enumerate(gen.member_order(cfg, seed).tolist()):
        r, z = k // (zones * per_zone), (k // per_zone) % zones
        region[m] = f"region-{r}"
        zone[m] = f"region-{r}-zone-{z}"
        provider[m] = f"provider-{k % int(lay['providers'])}"
        labels[m] = {"env": "canary" if k % every == every - 1 else "prod"}
    return {
        "region": region, "zone": zone, "provider": provider,
        "labels": labels,
        # every member serves the bindings' API and has reported all of its
        # enablements (the builders' default)
        "api_enabled": np.ones(c, bool), "api_complete": np.ones(c, bool),
    }


def placements(cfg: dict, seed: int) -> list:
    """The tenants' placements, in the configuration's order, as the
    reference takes them: strategy, label selector, spread constraints and,
    for a static weight list, the weight of every member (0 = not named)."""
    c = int(cfg["clusters"])
    order = gen.member_order(cfg, seed)
    out = []
    for n, p in enumerate(cfg["placements"]):
        pl = {
            "name": p["name"], "strategy": p["strategy"],
            "affinity_labels": p.get("affinity_labels"),
            "spread": [(s["by"], int(s["min_groups"]), int(s["max_groups"]))
                       for s in p.get("spread_constraints", ())],
        }
        if p["strategy"] == "static":
            r = _content(16 + n)
            named = order[r.choice(c, int(p["weight_members"]), replace=False)]
            pl["weights"] = np.zeros(c, np.int64)
            pl["weights"][named] = r.integers(
                int(p["weight_min"]), int(p["weight_max"]) + 1, len(named))
        out.append(pl)
    return out


def kinds(cfg: dict, seed: int) -> np.ndarray:
    """int[B]: the placement each binding rides. The counts are exact
    (``share`` x bindings, the rounding's remainder to the first kinds);
    which binding takes which is content, in the seed's order."""
    b = int(cfg["bindings"])
    shares = np.asarray([float(p["share"]) for p in cfg["placements"]])
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError("placements: the shares do not sum to 1")
    counts = np.floor(shares * b + 1e-9).astype(np.int64)
    counts[: b - int(counts.sum())] += 1
    content = _content(8).permutation(np.repeat(np.arange(len(counts)), counts))
    return content[gen.rng(seed, "bindings").permutation(b)]


def sample_rows(kind: np.ndarray, n_kinds: int, per_kind: int, n: int,
                seed: int, salt: int) -> np.ndarray:
    """Sorted sample of ``n`` rows to compare, stratified: ``per_kind`` rows
    of every placement first (all of a kind that has fewer), the rest drawn
    from all the other rows."""
    r = np.random.default_rng([int(seed), 4, int(salt), 7])
    picked = [r.choice(rows, min(per_kind, len(rows)), replace=False)
              for rows in (np.flatnonzero(kind == k) for k in range(n_kinds))]
    taken = np.concatenate(picked)
    rest = np.setdiff1d(np.arange(len(kind)), taken)
    more = r.choice(rest, max(0, min(n - len(taken), len(rest))), replace=False)
    return np.sort(np.concatenate([taken, more]))
