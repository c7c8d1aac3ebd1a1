"""Traffic kind ``drift`` (engine deployments): the full-storm pass. A ring
of availability snapshots built in set-up; a wave is
``update_snapshot(next)`` + ``schedule(all problems)``, timed together."""

from __future__ import annotations

import time

import numpy as np

from .. import gen
from ..reference import divide

DRIVER = "engine"


class Traffic:
    def __init__(self, dep, params: dict, log):
        self.dep, self.params, self.log = dep, params, log
        self.ring = int(params["ring"])
        self.per_wave = int(dep.cfg["bindings"])
        self.kept: dict = {}
        self.last = None

    def generate(self) -> None:
        self.allocs = gen.drift_ring(
            self.dep.fleet, self.params, self.dep.cfg, self.dep.seed)

    def build(self) -> None:
        from karmada_tpu.scheduler import ClusterSnapshot

        t0 = time.perf_counter()
        self.generate()
        self.snaps = []
        for a in self.allocs:
            self.dep.set_allocated(a)
            self.snaps.append(ClusterSnapshot(self.dep.clusters))
        self.log(f"setup ring_build_s={time.perf_counter() - t0:.2f}")

    # -- the window --------------------------------------------------------

    def prepare(self, g: int) -> None:
        """Nothing: the snapshots are ready."""

    def wave(self, g: int, annotate) -> int:
        engine = self.dep.engine
        with annotate("harness.update_snapshot"):
            if not engine.update_snapshot(self.snaps[g % self.ring]):
                raise RuntimeError("update_snapshot refused a drift")
        with annotate("harness.schedule"):
            self.last = engine.schedule(self.dep.problems)
        return self.per_wave

    def _check_rows(self, g: int) -> np.ndarray:
        return gen.sample_rows(
            self.per_wave, int(self.dep.cfg["check"]["rows_per_wave"]),
            self.dep.seed, g)

    def keep(self, g: int) -> None:
        """Copy out the answers of wave ``g`` on the rows to compare."""
        rows = self._check_rows(g)
        res = self.last
        self.kept[g] = (rows, [(res[i].success, dict(res[i].clusters))
                               for i in rows.tolist()])

    def collect(self) -> tuple:
        """(kept answers, rows the last wave left without a placement)."""
        return self.kept, sum(1 for r in self.last if not r.success)

    def free(self) -> None:
        self.snaps = self.last = None

    # -- the comparison (after the window, program state freed) ------------

    def expected(self, g: int, rows: np.ndarray) -> list:
        """What wave ``g`` has to answer on ``rows``: (divided, {name: n})."""
        fl, bd = self.dep.fleet, self.dep.bind
        names = fl["names"]
        c = len(names)
        out, uns = divide.place(
            bd["replicas"][rows], self.dep.profiles, bd["prof_idx"][rows],
            np.zeros(len(rows), bool), gen.prev_dense(bd, rows, c),
            bd["fresh"][rows], fl["allocatable"] - self.allocs[g % self.ring],
            np.zeros(c, bool))
        return [
            (not uns[j],
             {names[k]: int(out[j, k]) for k in np.flatnonzero(out[j])}
             if not uns[j] else {})
            for j in range(len(rows))
        ]

    def control_collected(self, waves: int) -> tuple:
        """The CONTROL: every compared wave answered with the reference of
        the wave before it, answers one step stale (what would tempt a
        delta-path PR: skip re-dividing when availability 'hardly' moved)."""
        picks = gen.sample_waves(
            waves, int(self.dep.cfg["check"]["waves"]), self.dep.seed)
        kept = {}
        for g in sorted(picks | {waves - 1}):
            rows = self._check_rows(g)
            kept[g] = (rows, self.expected(max(g, 1) - 1, rows))
        return kept, 0

    def check(self, collected: tuple) -> dict:
        kept, undivided = collected
        rows_compared = mismatched = 0
        for g, (rows, got) in sorted(kept.items()):
            want = self.expected(g, rows)
            bad = sum(1 for a, w in zip(got, want) if a != w)
            self.log(f"check wave={g} rows={len(rows)} mismatched={bad}")
            rows_compared += len(rows)
            mismatched += bad
        floor = int(self.dep.cfg["check"]["rows_per_wave"])
        return {
            "mismatched_rows": {"value": mismatched, "limit": 0},
            "rows_compared": {"value": rows_compared, "limit": floor,
                              "better": "higher"},
            "_failed": undivided,
        }
