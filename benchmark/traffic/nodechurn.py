"""Traffic kind ``nodechurn`` (estimator deployments): pods land and leave
on every member's nodes between two rounds. A ring of node states built in
set-up; ``prepare`` swaps the ring's prebuilt NodeSnapshots into the
members' estimators; a wave is everything the program does to take the new
state in and answer: ``update_snapshot(summaries)``, the registry's
``invalidate`` (what a Cluster event does in the plane), and
``schedule(all problems)``, which confirms the generations, uploads the
moved node state, re-estimates every node, folds the answers into the
resident profile table and divides every row. Timed together."""

from __future__ import annotations

import math
import time

import numpy as np

from .. import gen, nodes
from ..reference import estimate
from . import drift

DRIVER = "estimator"


class Traffic(drift.Traffic):
    def generate(self) -> None:
        self.node_free = nodes.ring(self.dep.cfg, self.params, self.dep.seed)
        alloc = self.dep.fleet["allocatable"]
        self.allocs = [nodes.summaries(alloc, f) for f in self.node_free]

    def build(self) -> None:
        from karmada_tpu.estimator.accurate import NodeSnapshot
        from karmada_tpu.scheduler import ClusterSnapshot

        t0 = time.perf_counter()
        self.generate()
        self.snaps, self.node_snaps = [], []
        for a, state in zip(self.allocs, self.node_free):
            self.dep.set_allocated(a)
            self.snaps.append(ClusterSnapshot(self.dep.clusters))
            # one object a ring element and member: a fresh generation each
            self.node_snaps.append(
                [NodeSnapshot.from_arrays(free, nodes.DIMS) for free in state])
        self.log(f"setup ring_build_s={time.perf_counter() - t0:.2f}")

    # -- the window --------------------------------------------------------

    def prepare(self, g: int) -> None:
        """Object swaps only: no array is built in the window."""
        for est, snap in zip(self.dep.estimators, self.node_snaps[g % self.ring]):
            est.snapshot = snap

    def wave(self, g: int, annotate) -> int:
        engine = self.dep.engine
        with annotate("harness.update_snapshot"):
            if not engine.update_snapshot(self.snaps[g % self.ring]):
                raise RuntimeError("update_snapshot refused a churn step")
            self.dep.registry.invalidate()
        with annotate("harness.schedule"):
            self.last = engine.schedule(self.dep.problems)
        return self.per_wave

    def free(self) -> None:
        super().free()
        self.node_snaps = None

    # -- the comparison (after the window, program state freed) ------------

    def expected(self, g: int, rows: np.ndarray, estimators: bool = True) -> list:
        """What wave ``g`` has to answer on ``rows``: (divided, {name: n}),
        at that wave's node state and summaries."""
        fl, bd = self.dep.fleet, self.dep.bind
        names = fl["names"]
        k = g % self.ring
        out, uns = estimate.place(
            bd["replicas"][rows], self.dep.profiles, bd["prof_idx"][rows],
            gen.prev_dense(bd, rows, len(names)), bd["fresh"][rows],
            fl["allocatable"] - self.allocs[k],
            self.node_free[k] if estimators else None)
        return [
            (not uns[j],
             {names[c]: int(out[j, c]) for c in np.flatnonzero(out[j])}
             if not uns[j] else {})
            for j in range(len(rows))
        ]

    def control_collected(self, waves: int) -> tuple:
        """The CONTROL: the reference's own answers with the estimators'
        answers left out, every compared wave at its own summaries (what a
        program that dropped back to ResourceSummary availability, or
        folded nothing, would give)."""
        picks = gen.sample_waves(
            waves, int(self.dep.cfg["check"]["waves"]), self.dep.seed)
        kept = {}
        for g in sorted(picks | {waves - 1}):
            rows = self._check_rows(g)
            kept[g] = (rows, self.expected(g, rows, estimators=False))
        return kept, 0

    def check(self, collected: tuple) -> dict:
        kept, undivided = collected
        rows_compared = mismatched = decided = 0
        for g, (rows, got) in sorted(kept.items()):
            want = self.expected(g, rows)
            without = self.expected(g, rows, estimators=False)
            bad = sum(1 for a, w in zip(got, want) if a != w)
            moved = sum(1 for w, o in zip(want, without) if w != o)
            self.log(f"check wave={g} rows={len(rows)} mismatched={bad} "
                     f"estimator_decided={moved}")
            rows_compared += len(rows)
            mismatched += bad
            decided += moved
        check = self.dep.cfg["check"]
        return {
            "mismatched_rows": {"value": mismatched, "limit": 0},
            "undivided_rows": {"value": undivided, "limit": 0},
            "rows_compared": {"value": rows_compared,
                              "limit": int(check["rows_per_wave"]),
                              "better": "higher"},
            # a data set on which the estimator decides nothing is the
            # drift cell with extra steps: not this cell, so not correct
            "estimator_decided_rows": {
                "value": decided,
                "limit": math.ceil(
                    float(check["estimator_decided_share"]) * rows_compared),
                "better": "higher"},
            "_failed": undivided,
        }
