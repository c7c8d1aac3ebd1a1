"""Traffic kind ``rebalancer`` (plane deployments): the members' load moves
and a WorkloadRebalancer re-divides a set of resident Deployments over it.

Before a wave, the members due to report (``ring`` groups, one a wave, so a
member reports every ``ring`` waves) swap in the other of their two
prebuilt node states; the plane's own status collection reads them inside
the wave. The wave applies one WorkloadRebalancer naming a seeded set of
``rows`` Deployments (a ring of ``ring`` disjoint sets) and settles. The
rebalanced rows are divided afresh over the availability of THAT wave, so
a store that is a turn behind holds other placements and other Works."""

from __future__ import annotations

import time

import numpy as np

from .. import gen
from ..drivers.plane import NS
from ..reference import divide

DRIVER = "plane"


def _work_replicas(work):
    """The replicas a Work carries for its Deployment: in the rendered
    manifest, or as the patch over the shared template."""
    for r in work.spec.workload:
        if r.kind == "Deployment":
            return r.spec.get("replicas")
    ref = work.spec.workload_template
    return ref.patch.get("replicas") if ref is not None else None


class Traffic:
    def __init__(self, dep, params: dict, log):
        self.dep, self.params, self.log = dep, params, log
        self.ring = int(params["ring"])
        self.per_wave = int(params["rows"])
        self.waves_run = 0
        self.wave_clock: list = []  # clock value of each wave run

    def generate(self) -> None:
        dep = self.dep
        self.sets = gen.disjoint_sets(
            len(dep.reps), self.ring, self.per_wave, dep.seed)
        # [version, C, R]; member i reports in the waves g = i mod ring
        self.allocs = gen.drift_pair(dep.fleet, self.params, dep.cfg, dep.seed)
        self.group = np.arange(len(dep.fleet["names"])) % self.ring

    def build(self) -> None:
        from karmada_tpu.api.core import ObjectMeta
        from karmada_tpu.controllers.extras import (
            ObjectReferenceSelector,
            WorkloadRebalancer,
            WorkloadRebalancerSpec,
        )

        t0 = time.perf_counter()
        self.generate()
        selectors = [
            [ObjectReferenceSelector(kind="Deployment", name=f"d{i}")
             for i in s]
            for s in self.sets.tolist()
        ]
        # one object a wave (a rebalancer runs once); the selector lists
        # are shared, so the pool is cheap
        self.pool = [
            WorkloadRebalancer(
                meta=ObjectMeta(name=f"bench-rebalance-{g}"),
                spec=WorkloadRebalancerSpec(workloads=selectors[g % self.ring]),
            )
            for g in range(int(self.params["pool"]))
        ]
        # [version][member] -> its node list
        self.nodes = [self.dep.node_states(a) for a in self.allocs]
        self.reporting = [np.flatnonzero(self.group == k).tolist()
                          for k in range(self.ring)]
        self.log(f"setup ring_build_s={time.perf_counter() - t0:.2f}")

    # -- the window --------------------------------------------------------

    def versions(self, g: int) -> np.ndarray:
        """int[C]: which of its two states each member shows in wave g."""
        turn, k = divmod(g, self.ring)
        return (turn + (self.group <= k)) % 2

    def prepare(self, g: int) -> None:
        dep = self.dep
        dep.clock[0] += float(self.params["clock_step_s"])
        self.wave_clock.append(dep.clock[0])
        nodes = self.nodes[(g // self.ring + 1) % 2]
        for i in self.reporting[g % self.ring]:
            dep.members[i].nodes = nodes[i]

    def wave(self, g: int, annotate) -> int:
        cp = self.dep.cp
        with annotate("harness.apply"):
            if g >= len(self.pool):
                raise RuntimeError("the rebalancer pool ran out")
            cp.store.apply(self.pool[g])
        with annotate("harness.settle"):
            cp.settle()
        self.waves_run = g + 1
        return self.per_wave

    def keep(self, g: int) -> None:
        """The store after the window is what is compared; nothing to copy."""

    def free(self) -> None:
        self.pool = self.nodes = None

    # -- the comparison ----------------------------------------------------

    def replay(self, waves: int) -> np.ndarray:
        """The reference over the cold wave and the first ``waves`` waves,
        warm-up included: the assignment int64[N, C] the store has to hold."""
        dep = self.dep
        fl = dep.fleet
        n, c = len(dep.reps), len(fl["names"])
        tainted = np.zeros(c, bool)

        def solve(rows, prev, fresh, allocated):
            out, uns = divide.place(
                dep.reps[rows], dep.profiles, np.zeros(len(rows), np.int64),
                np.zeros(len(rows), bool), prev, np.full(len(rows), fresh),
                fl["allocatable"] - allocated, tainted)
            if uns.any():
                raise RuntimeError("the reference finds rows unschedulable")
            return out

        state = solve(np.arange(n), np.zeros((n, c), np.int64), False,
                      self.allocs[0])
        pick = np.arange(c)
        for g in range(waves):
            s = self.sets[g % self.ring]
            state[s] = solve(s, state[s], True,
                             self.allocs[self.versions(g), pick])
        return state

    def due(self, waves: int) -> np.ndarray:
        """float[N]: clock of the last rebalancer that named each binding in
        the first ``waves`` waves (nan: none did)."""
        out = np.full(len(self.dep.reps), np.nan)
        for g in range(waves):
            out[self.sets[g % self.ring]] = self.wave_clock[g]
        return out

    def collect(self) -> dict:
        """What the store holds, as plain arrays: per binding whether the
        scheduler has seen its latest generation, when it was asked to
        reschedule and when it last was, its assignment, and the replicas
        each member's Work carries for it (-1: no such Work)."""
        cp, names = self.dep.cp, self.dep.fleet["names"]
        index = {nm: j for j, nm in enumerate(names)}
        n, c = len(self.dep.reps), len(names)
        seen = np.zeros(n, bool)
        asked = np.full(n, np.nan)
        done = np.full(n, np.nan)
        got = np.zeros((n, c), np.int64)
        works = np.full((n, c), -1, np.int64)
        for i in range(n):
            rb = cp.store.get("ResourceBinding", f"{NS}/d{i}-deployment")
            if rb is None:
                continue
            seen[i] = rb.status.scheduler_observed_generation == rb.meta.generation
            if rb.spec.reschedule_triggered_at is not None:
                asked[i] = rb.spec.reschedule_triggered_at
            if rb.status.last_scheduled_time is not None:
                done[i] = rb.status.last_scheduled_time
            for tc in rb.spec.clusters:
                got[i, index[tc.name]] = tc.replicas
        for work in cp.store.list("Work"):
            ns, name = work.meta.namespace, work.meta.name
            reps = _work_replicas(work)
            if (ns.startswith("karmada-es-") and name.startswith(f"{NS}.d")
                    and name.endswith("-deployment") and reps is not None):
                works[int(name[len(NS) + 2:-len("-deployment")]),
                      index[ns[len("karmada-es-"):]]] = reps
        return {"seen": seen, "asked": asked, "done": done, "got": got,
                "works": works, "waves": self.waves_run}

    def control_collected(self, waves: int) -> dict:
        """The CONTROL: a plane that acknowledges the last turn of the ring
        (every stamp as a sound run leaves it) and acts on none of it: the
        bindings and Works of a turn before. The guarantee broken: every
        touched binding re-divided over the availability of its wave."""
        self.wave_clock = [
            self.dep.clock[0] + float(self.params["clock_step_s"]) * (g + 1)
            for g in range(waves)]
        stale = self.replay(max(0, waves - self.ring))
        due = self.due(waves)
        n = len(self.dep.reps)
        return {"seen": np.ones(n, bool), "asked": due, "done": due,
                "got": stale, "works": np.where(stale > 0, stale, -1),
                "waves": waves}

    def check(self, held: dict) -> dict:
        reps = self.dep.reps
        want = self.replay(held["waves"])
        due = self.due(held["waves"])
        named = ~np.isnan(due)
        # named by a rebalancer and not rescheduled since
        unanswered = named & ~((held["asked"] == due) & (held["done"] >= due))
        got, works = held["got"], held["works"]
        return {
            "mismatched_rows": {
                "value": int((got != want).any(axis=1).sum()), "limit": 0},
            "stale_bindings": {
                "value": int((~held["seen"] | unanswered).sum()), "limit": 0},
            "wrong_replica_sums": {
                "value": int((got.sum(axis=1) != reps).sum()), "limit": 0},
            # a Work in every cluster the reference assigns, carrying that
            # share, and none anywhere else
            "wrong_works": {
                "value": int((works != np.where(want > 0, want, -1)).sum()),
                "limit": 0},
            "rows_compared": {"value": len(reps), "limit": len(reps),
                              "better": "higher"},
            "_failed": int((got.sum(axis=1) == 0).sum()),
        }
