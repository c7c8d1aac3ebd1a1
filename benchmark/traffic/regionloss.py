"""Traffic kind ``regionloss`` (failover deployments): a descheduler or
WorkloadRebalancer round over a federation whose tenants configured
failover, before, while and after a region is NotReady. A ring of steps
built in set-up, each a snapshot and a list of bindings:

- ``h`` healthy: every member Ready, the bindings' first objects;
- ``L`` loss: a region's members carry ``cluster.karmada.io/not-ready:
  NoExecute``; every binding that held a site there and does not tolerate
  the taint is presented anew, those sites moved from its previous result
  to its eviction tasks (what the taint manager leaves); ``app_failover_rows``
  more bindings hold one task on a healthy member (application failover);
- ``d`` during: the same objects and taints as ``L``, only capacities move;
- ``r`` recovered: taints lifted, tasks drained, the first objects again.

Capacities drift one step in every wave (gen.drift_ring, closed over the
ring) and one zone a region runs full (failover.ring). A wave is
``update_snapshot(next)`` + ``schedule(all bindings)`` + one read of the
first Duplicated row's ``clusters`` and ``affinity_name``, timed together.
The comparison is against reference/failover.py, stratified by placement,
with floors under the rows that fall back, that capacity sends on, that an
eviction task decides and that a toleration keeps in place; the control is
that reference answering every row from its FIRST group, one wave stale."""

from __future__ import annotations

import time

import numpy as np

from .. import failover, gen
from ..reference import failover as reference
from . import drift

DRIVER = "failover"


def error_class(error: str) -> str:
    """The program's error string as the reference's class."""
    if not error:
        return ""
    return reference.NOT_ENOUGH if "not enough" in error else reference.NO_FIT


class Traffic(drift.Traffic):
    def __init__(self, dep, params: dict, log):
        super().__init__(dep, params, log)
        self.steps = failover.steps(params)
        self.armed = False  # a wave of the window was kept: keep the rest
        self.kinds_kept: set = set()

    # -- the generator's part ------------------------------------------------

    def generate(self) -> None:
        dep, cfg, seed = self.dep, self.dep.cfg, self.dep.seed
        self.allocs = failover.ring(
            dep.fleet, self.params, cfg, seed, dep.members)
        self.lost = failover.lost_at(self.params, cfg, seed)
        self.loss = {
            r: failover.loss(dep.bind, dep.kind, dep.placements, dep.members,
                             r, int(self.params["app_failover_rows"]), seed)
            for r in sorted(set(self.lost) - {-1})}

    def step_loss(self, g: int):
        """The loss arrays of ring step ``g``, None for a healthy step."""
        return self.loss.get(self.lost[g % self.ring])

    # -- set-up ----------------------------------------------------------------

    def build(self) -> None:
        from karmada_tpu.api.cluster import Taint
        from karmada_tpu.scheduler import ClusterSnapshot

        t0 = time.perf_counter()
        self.generate()
        dep = self.dep
        names = dep.fleet["names"]
        self.snaps = []
        for g, a in enumerate(self.allocs):
            dep.set_allocated(a)
            for cl, region in zip(dep.clusters, dep.members["region_of"]):
                cl.spec.taints = [
                    Taint(key=failover.NOT_READY, effect=failover.NO_EXECUTE)
                ] if region == self.lost[g] else []
            self.snaps.append(ClusterSnapshot(dep.clusters))
        # the bindings of each lost region: the first objects, but for the
        # evicted ones, which come as the taint manager left them
        lists = {-1: dep.problems}
        for region, step in self.loss.items():
            problems = list(dep.problems)
            for i in np.flatnonzero(step["changed"]).tolist():
                problems[i] = dep.problem(
                    i, dep.policies[i],
                    {names[step["prev_sites"][i, k]]:
                     int(step["prev_counts"][i, k])
                     for k in range(step["n_prev"][i])},
                    tuple(names[j] for j in
                          step["evict_sites"][i, :step["n_evict"][i]]))
            lists[region] = problems
            self.log(
                f"setup loss region={region} tainted={int(step['tainted'].sum())} "
                f"presented_anew={int(step['changed'].sum())} "
                f"app_failover={int(step['app'].sum())} "
                f"tasks_max={int(step['n_evict'].max())}")
        self.problems = [lists[r] for r in self.lost]
        strategies = np.asarray([p["strategy"] for p in dep.placements])
        dup = np.flatnonzero(strategies[dep.kind] == "duplicated")
        if not len(dup):
            raise ValueError("no Duplicated row: nothing reads the bitsets")
        self.first_dup = int(dup[0])
        self.log(f"setup ring_build_s={time.perf_counter() - t0:.2f} "
                 f"steps={self.steps} lost={self.lost}")

    # -- the window --------------------------------------------------------------

    def prepare(self, g: int) -> None:
        """The snapshots and the lists are ready. Once the window has kept
        one wave, the wave that has just run is kept too if it is the first
        of its step kind, so the comparison sees every kind."""
        if self.armed and g > 0:
            kind = self.steps[(g - 1) % self.ring]
            if kind not in self.kinds_kept and (g - 1) not in self.kept:
                self.keep(g - 1)

    def wave(self, g: int, annotate) -> int:
        engine = self.dep.engine
        k = g % self.ring
        before = engine.solve_batches
        with annotate("harness.update_snapshot"):
            if not engine.update_snapshot(self.snaps[k]):
                raise RuntimeError("update_snapshot refused a step")
        with annotate("harness.schedule"):
            self.last = engine.schedule(self.problems[k])
        with annotate("harness.read_duplicated"):
            row = self.last[self.first_dup]
            if not row.clusters or not row.affinity_name:
                raise RuntimeError("the first Duplicated row has no answer")
        if engine.solve_batches - before != 1:
            self.dep.guard(engine.solve_batches - before, f"wave {g}")
        return self.per_wave

    def _check_rows(self, g: int) -> np.ndarray:
        dep = self.dep
        return failover.sample_rows(
            dep.kind, dep.placements, len(dep.cfg["placements"]), dep.cfg,
            self.step_loss(g), dep.seed, g)

    def keep(self, g: int) -> None:
        """Copy out the answers of wave ``g`` on the rows to compare."""
        rows = self._check_rows(g)
        res = self.last
        self.kept[g] = (rows, [
            (error_class(res[i].error), res[i].affinity_name,
             dict(res[i].clusters)) for i in rows.tolist()])
        self.kinds_kept.add(self.steps[g % self.ring])
        self.armed = True

    def free(self) -> None:
        self.snaps = self.problems = self.last = None

    # -- the comparison (after the window, program state freed) ------------------

    def inputs(self, g: int, rows: np.ndarray) -> dict:
        """What the reference reads for wave ``g`` on ``rows``."""
        dep = self.dep
        fl, bd = dep.fleet, dep.bind
        c = len(fl["names"])
        step = self.step_loss(g)
        held = bd if step is None else {**bd, **{
            k: step[k] for k in ("n_prev", "prev_sites", "prev_counts")}}
        return dict(
            placements=dep.placements, kind=dep.kind[rows],
            replicas=bd["replicas"][rows], requests=dep.profiles,
            prof_idx=bd["prof_idx"][rows],
            prev=gen.prev_dense(held, rows, c),
            evict=failover.evict_dense(step, rows, c),
            fresh=bd["fresh"][rows],
            cap=fl["allocatable"] - self.allocs[g % self.ring],
            members=dep.members,
            tainted=(np.zeros(c, bool) if step is None else step["tainted"]),
            taint_keys=(failover.NOT_READY,),
        )

    def expected(self, g: int, rows: np.ndarray, at: int | None = None,
                 **how) -> tuple:
        """What wave ``g`` has to answer on ``rows``: ([(error class,
        affinity name, {member: n})], group int[B], first group had a
        candidate bool[B]). ``at``: the wave whose capacities and taints to
        read (the control's stale wave); the bindings stay wave ``g``'s."""
        names = self.dep.fleet["names"]
        args = self.inputs(g, rows)
        if at is not None:
            stale = self.inputs(at, rows)
            args.update(cap=stale["cap"], tainted=stale["tainted"])
        out, group, errors, group0 = reference.place(**args, **how)
        answers = []
        for j in range(len(rows)):
            terms = self.dep.placements[int(args["kind"][j])]["terms"]
            name = terms[int(group[j])][0] if len(terms) > 1 else ""
            answers.append((
                errors[j], name,
                {names[k]: int(out[j, k]) for k in np.flatnonzero(out[j])}))
        return answers, group, group0

    def control_collected(self, waves: int) -> tuple:
        """The CONTROL: the reference's own answers with every row answered
        from its FIRST affinity group and one wave stale (what a program
        that dropped the ordered groups, or kept the choice of another
        wave's making, would give)."""
        kept = {}
        seen = set()
        picks = gen.sample_waves(
            waves, int(self.dep.cfg["check"]["waves"]), self.dep.seed)
        first = min(picks)
        for g in list(range(first, first + self.ring)) + [waves - 1]:
            kind = self.steps[g % self.ring]
            if g != waves - 1 and (kind in seen):
                continue
            seen.add(kind)
            rows = self._check_rows(g)
            kept[g] = (rows, self.expected(
                g, rows, at=max(g, 1) - 1, first_group_only=True)[0])
        return kept, 0

    def check(self, collected: tuple) -> dict:
        kept, undivided = collected
        check = self.dep.cfg["check"]
        pls = self.dep.placements
        n_kinds = len(self.dep.cfg["placements"])
        group_of = np.asarray([pl["group"] for pl in pls])
        rows_compared = mismatched = capacity = 0
        per_kind, fallback, decided, tolerated, kinds_seen = [], [], [], [], set()
        for g, (rows, got) in sorted(kept.items()):
            want, group, group0 = self.expected(g, rows)
            bad = sum(1 for a, w in zip(got, want) if a != w)
            counts = np.bincount(
                group_of[self.dep.kind[rows]], minlength=n_kinds)
            fell = int((group >= 1).sum())
            by_capacity = int(((group >= 1) & group0).sum())
            kind = self.steps[g % self.ring]
            kinds_seen.add(kind)
            line = (f"check wave={g} step={kind} rows={len(rows)} "
                    f"mismatched={bad} fallback={fell} "
                    f"capacity_fallback={by_capacity} "
                    f"by_kind={counts.tolist()}")
            step = self.step_loss(g)
            if step is not None:
                without, _, _ = self.expected(g, rows, tasks=False)
                moved = sum(1 for w, o in zip(want, without) if w != o)
                names = self.dep.fleet["names"]
                lost = {names[j] for j in np.flatnonzero(step["tainted"])}
                tolerant = np.asarray(
                    [bool(pl["tolerates"]) for pl in pls])[self.dep.kind[rows]]
                stays = sum(
                    1 for t, a in zip(tolerant, got) if t and lost & set(a[2]))
                fallback.append(fell)
                decided.append(moved)
                tolerated.append(stays)
                line += (f" eviction_decided={moved} tolerated={stays} "
                         f"holding_a_task="
                         f"{int((step['n_evict'][rows] > 0).sum())}")
            self.log(line)
            rows_compared += len(rows)
            mismatched += bad
            capacity += by_capacity
            per_kind.append(int(counts.min()))

        def floor(values, name):
            return {"value": min(values, default=0), "limit": int(check[name]),
                    "better": "higher"}

        return {
            "mismatched_rows": {"value": mismatched, "limit": 0},
            "undivided_rows": {"value": undivided, "limit": 0},
            "rows_compared": {"value": rows_compared,
                              "limit": int(check["rows_per_wave"]),
                              "better": "higher"},
            "step_kinds_compared": {"value": len(kinds_seen),
                                    "limit": len(set(self.steps)),
                                    "better": "higher"},
            "rows_of_each_kind": floor(per_kind, "rows_per_kind"),
            # a run that ignores the ordered groups, the capacity predicate,
            # the eviction tasks or the tolerations cannot pass: each
            # decides at least this many of the compared rows
            "fallback_decided_rows": floor(fallback, "fallback_decided_rows"),
            "capacity_fallback_rows": {
                "value": capacity, "limit": int(check["capacity_fallback_rows"]),
                "better": "higher"},
            "eviction_decided_rows": floor(decided, "eviction_decided_rows"),
            "tolerated_rows": floor(tolerated, "tolerated_rows"),
            "_failed": undivided,
        }
