"""What a ``failover`` deployment adds to the generator's arrays
(benchmark/gen.py, benchmark/placements.py): the members' region and zone
as labels, the tenants' placements with their ORDERED affinity terms and
tolerations, which one each binding rides and where its home is, previous
sites that lie inside the primary term, the zones that run full, and the
steps of a region's loss (who is tainted, whose sites become eviction
tasks). Plain numpy and Python, nothing of the program: the driver
(drivers/failover.py) turns these into the program's API objects, and the
reference (reference/failover.py) reads the same values. Parameters come
from the configuration's ``layout``, ``placements`` and ``fleet.hot_zones``
and from the traffic mix.

Like the rest of a deployment, all of it is content: the same for every
seed, dealt to the members and to the bindings in the seed's order; the
seed also draws which two regions are lost.
"""

from __future__ import annotations

import numpy as np

from . import gen, placements as base

#: the taint a NotReady member carries, and its effect once the taint
#: manager acts (Karmada docs, userguide/failover/failover-overview)
NOT_READY = "cluster.karmada.io/not-ready"
NO_EXECUTE = "NoExecute"


def _content(stream: int) -> np.random.Generator:
    return np.random.default_rng([0x6B61726D, 64 + stream])


def region_index(members: dict) -> np.ndarray:
    """int[C]: the content region r of each member ("region-r")."""
    return np.asarray([int(r.rsplit("-", 1)[1]) for r in members["region"]])


def members(cfg: dict, seed: int) -> dict:
    """placements.members plus the labels the affinity terms select on: a
    member's region and zone are labels too (a clusterAffinity's
    labelSelector), and ``zone_of`` / ``region_of`` as indices."""
    mb = base.members(cfg, seed)
    for lb, region, zone in zip(mb["labels"], mb["region"], mb["zone"]):
        lb["region"], lb["zone"] = region, zone
    mb["region_of"] = region_index(mb)
    mb["zone_of"] = np.asarray(
        [int(z.rsplit("-", 1)[1]) for z in mb["zone"]])
    return mb


def home_zone(cfg: dict, region: int) -> int:
    """The zone a 3-term placement of ``region`` calls home."""
    return region % int(cfg["layout"]["home_zone_mod"])


def placements(cfg: dict) -> list:
    """The tenants' placements as the reference takes them, one for each
    home region of a kind with ``per_home_region`` (their terms name that
    region, its home zone, or the next region), one for every other kind:
    {"name", "group" (index in the configuration), "home" (region or -1),
    "strategy", "terms": [(affinity name, label selector or None)],
    "tolerates": [taint keys]}."""
    regions = int(cfg["layout"]["regions"])
    out = []
    for k, p in enumerate(cfg["placements"]):
        homes = range(regions) if p.get("per_home_region") else (-1,)
        for home in homes:
            terms = []
            for t in p["terms"]:
                sel = {
                    "every-member": None,
                    "home-region": {"region": f"region-{home}"},
                    "next-region": {
                        "region": f"region-{(home + 1) % regions}"},
                    "home-zone": {
                        "zone":
                            f"region-{home}-zone-{home_zone(cfg, home)}"},
                }[t["members"]]
                terms.append((t["name"], sel))
            out.append({
                "name": p["name"] + (f"/region-{home}" if home >= 0 else ""),
                "group": k, "home": home, "strategy": p["strategy"],
                "terms": terms,
                "tolerates": list(p.get("tolerations", ())),
            })
    return out


def kinds(cfg: dict, seed: int, pls: list) -> np.ndarray:
    """int[B]: the placement (index into ``placements(cfg)``) each binding
    rides. The kinds' counts are exact (placements.kinds); inside a kind the
    home regions are dealt evenly, in content order."""
    group = base.kinds(cfg, seed)
    regions = int(cfg["layout"]["regions"])
    first = {}
    for n, pl in enumerate(pls):
        first.setdefault(pl["group"], n)
    out = np.empty(len(group), np.int64)
    for k, p in enumerate(cfg["placements"]):
        rows = np.flatnonzero(group == k)
        if p.get("per_home_region"):
            homes = _content(k).permutation(np.arange(len(rows)) % regions)
            out[rows] = first[k] + homes
        else:
            out[rows] = first[k]
    return out


def home_prev(bind: dict, kind: np.ndarray, pls: list, mb: dict) -> dict:
    """The bindings with the previous sites of every multi-term row moved
    inside its primary term's members (its home REGION for a row whose
    primary is the home zone): a tenant with a primary group runs there."""
    out = dict(bind)
    sites = bind["prev_sites"].copy()
    region_of = mb["region_of"]
    by_region = [np.flatnonzero(region_of == r)
                 for r in range(int(region_of.max()) + 1)]
    for n, pl in enumerate(pls):
        if pl["home"] < 0:
            continue
        rows = np.flatnonzero(kind == n)
        inside = by_region[pl["home"]]
        sites[rows] = inside[sites[rows] % len(inside)]
    out["prev_sites"] = sites
    return out


def hot_members(cfg: dict, mb: dict) -> np.ndarray:
    """bool[C]: the members of the zone of every region that runs full."""
    return mb["zone_of"] == int(cfg["fleet"]["hot_zones"]["zone"])


def hot_free_units(cfg: dict, region: int, g: int) -> int:
    """Drift units a hot member of ``region`` has free at ring step ``g``:
    the configured swing, a region's phase ``phase_step`` steps on from the
    region before it, so the hot zones run dry in different waves."""
    hot = cfg["fleet"]["hot_zones"]
    swing = hot["free_units_swing"]
    return int(swing[(g + region * int(hot["phase_step"])) % len(swing)])


def ring(fl: dict, traffic: dict, cfg: dict, seed: int, mb: dict) -> list:
    """gen.drift_ring's allocations, with every hot member's set to its
    allocatable less the swing's units (all dims alike: a full member is
    full of pods, which take cpu and memory with them)."""
    allocs = gen.drift_ring(fl, traffic, cfg, seed)
    alloc = fl["allocatable"]
    unit = np.maximum(1, alloc // int(traffic["step_divisor"]))
    hot = np.flatnonzero(hot_members(cfg, mb))
    out = []
    for g, a in enumerate(allocs):
        a = a.copy()
        free = np.asarray(
            [hot_free_units(cfg, int(mb["region_of"][j]), g) for j in hot])
        a[hot] = alloc[hot] - free[:, None] * unit[hot]
        out.append(a)
    return out


def steps(traffic: dict) -> str:
    """The ring's step kinds, one letter a step: h(ealthy), L(oss),
    d(uring), r(ecovered)."""
    s = str(traffic["steps"]).replace(" ", "")
    if len(s) != int(traffic["ring"]) or set(s) - set("hLdr"):
        raise ValueError("steps: one of h, L, d, r for each ring element")
    return s


def lost_regions(cfg: dict, seed: int, n: int) -> list:
    """The ``n`` different regions lost along the ring, from the seed."""
    r = gen.rng(seed, "traffic")
    return [int(x) for x in r.permutation(int(cfg["layout"]["regions"]))[:n]]


def lost_at(traffic: dict, cfg: dict, seed: int) -> list:
    """For each ring step the region that is NotReady in it, or -1."""
    kinds_ = steps(traffic)
    lost = lost_regions(cfg, seed, kinds_.count("L"))
    out, cur, n = [], -1, 0
    for k in kinds_:
        if k == "L":
            cur, n = lost[n], n + 1
        elif k in "hr":
            cur = -1
        out.append(cur)
    return out


def loss(bind: dict, kind: np.ndarray, pls: list, mb: dict, region: int,
         app_rows: int, seed: int) -> dict:
    """What the taint manager and the application-failover controller
    leave behind when ``region`` is NotReady: ``tainted`` bool[C]; for every
    binding ``n_prev`` / ``prev_sites`` / ``prev_counts`` with the sites it
    no longer holds taken out, ``evict_sites`` int[B, smax] (-1 = unused)
    with those sites as its eviction tasks and ``n_evict``; ``changed``
    bool[B]: the bindings presented anew; ``app`` bool[B]: those of them
    whose one task lies on a HEALTHY member (application failover).

    A binding that does not tolerate the taint loses every previous site
    in the region; ``app_rows`` more bindings (drawn from the seed among
    those that keep all their sites) lose the first of their sites."""
    tainted = mb["region_of"] == region
    b, smax = bind["prev_sites"].shape
    tolerant = np.asarray([bool(pl["tolerates"]) for pl in pls])[kind]
    live = np.arange(smax)[None, :] < bind["n_prev"][:, None]
    # a site drawn twice is one site (a dict built in order): only its last
    # occurrence counts, as gen.prev_dense has it
    sites = bind["prev_sites"]
    last = np.ones((b, smax), bool)
    for k in range(smax):
        for k2 in range(k + 1, smax):
            last[:, k] &= ~(live[:, k2] & (sites[:, k2] == sites[:, k]))
    live &= last
    gone = live & tainted[sites] & ~tolerant[:, None]
    first = np.argmax(live, axis=1)  # a row's first site (column of it)
    first_site = sites[np.arange(b), first]
    pool = np.flatnonzero(~gone.any(axis=1) & live.any(axis=1)
                          & ~tainted[first_site])
    r = np.random.default_rng([int(seed), 3, 17, int(region)])
    app = np.zeros(b, bool)
    app[r.choice(pool, min(int(app_rows), len(pool)), replace=False)] = True
    gone[np.flatnonzero(app), first[app]] = True
    keep = live & ~gone
    out = {
        "tainted": tainted, "changed": gone.any(axis=1), "app": app,
        "n_prev": keep.sum(axis=1), "n_evict": gone.sum(axis=1),
        "prev_sites": np.zeros((b, smax), np.int64),
        "prev_counts": np.zeros((b, smax), np.int64),
        "evict_sites": np.full((b, smax), -1, np.int64),
    }
    for src, mask, dst in (("prev_sites", keep, "prev_sites"),
                           ("prev_counts", keep, "prev_counts"),
                           ("prev_sites", gone, "evict_sites")):
        order = np.argsort(~mask, axis=1, kind="stable")
        packed = np.take_along_axis(bind[src], order, axis=1)
        n = mask.sum(axis=1)
        fill = -1 if dst == "evict_sites" else 0
        out[dst] = np.where(np.arange(smax)[None, :] < n[:, None], packed, fill)
    return out


def evict_dense(step: dict | None, rows: np.ndarray, c: int) -> np.ndarray:
    """bool[len(rows), C]: the members each row holds an eviction task on."""
    out = np.zeros((len(rows), c), bool)
    if step is not None:
        ev = step["evict_sites"][rows]
        r, k = np.nonzero(ev >= 0)
        out[r, ev[r, k]] = True
    return out


def sample_rows(kind: np.ndarray, pls: list, n_groups: int, cfg: dict,
                step: dict | None, seed: int, salt: int) -> np.ndarray:
    """Sorted sample of rows to compare, stratified: ``rows_per_kind`` rows
    of every kind of placement, at least ``hot_home_rows`` of the 3-term
    kind's from placements whose home zone runs full and, in a step with a
    lost region, at least ``tolerant_held_rows`` of the tolerant kind's
    from rows that hold a site there and ``task_rows`` rows holding an
    application-failover task; the rest drawn from all the other rows."""
    check = cfg["check"]
    per_kind, n = int(check["rows_per_kind"]), int(check["rows_per_wave"])
    r = np.random.default_rng([int(seed), 4, int(salt), 11])
    group = np.asarray([pl["group"] for pl in pls])[kind]
    hot_zone = int(cfg["fleet"]["hot_zones"]["zone"])
    hot_home = np.asarray([
        len(pl["terms"]) == 3 and home_zone(cfg, pl["home"]) == hot_zone
        for pl in pls])[kind]

    def draw(pool, k):
        return r.choice(pool, min(int(k), len(pool)), replace=False)

    picked = [draw(np.flatnonzero(hot_home), check["hot_home_rows"])]
    if step is not None:
        tolerant = np.asarray([bool(pl["tolerates"]) for pl in pls])[kind]
        b, smax = step["prev_sites"].shape
        live = np.arange(smax)[None, :] < step["n_prev"][:, None]
        held = (live & step["tainted"][step["prev_sites"]]).any(axis=1)
        picked.append(draw(np.flatnonzero(tolerant & held),
                           check["tolerant_held_rows"]))
        picked.append(draw(np.flatnonzero(step["app"]), check["task_rows"]))
    taken = np.unique(np.concatenate(picked))
    for k in range(n_groups):
        have = int((group[taken] == k).sum())
        pool = np.setdiff1d(np.flatnonzero(group == k), taken)
        taken = np.concatenate([taken, draw(pool, max(0, per_kind - have))])
    rest = np.setdiff1d(np.arange(len(kind)), taken)
    more = draw(rest, max(0, n - len(taken)))
    return np.sort(np.concatenate([taken, more]))
