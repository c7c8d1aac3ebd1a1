"""Traffic kind ``policydrift`` (policies deployments): ``drift``'s
full-storm pass over a federation whose tenants use the documented policy
kinds side by side. A ring of availability snapshots built in set-up; a
wave is ``update_snapshot(next)`` + ``schedule(all problems)`` + one read of
the ``clusters`` of the first Duplicated row, which dispatches the lazy
feasibility-bitset pass for the batch and fetches its bitsets (decoding the
other Duplicated rows' dicts is the consumer's and stays out). Timed
together. The comparison is stratified by placement, against
reference/policies.py, and the control is that reference without its spread
constraints."""

from __future__ import annotations

import time

import numpy as np

from .. import gen, placements
from ..reference import policies
from . import drift

DRIVER = "policies"


class Traffic(drift.Traffic):
    def __init__(self, dep, params: dict, log):
        super().__init__(dep, params, log)
        self._t_first = None
        self._slots_first = None
        # the same bound from set-up's first pass on: a program that mints a
        # slot a selection fails there, before its compiles for the table
        dep.slots_spare = int(params["slot_growth_limit"])

    def build(self) -> None:
        super().build()
        strategies = [p["strategy"] for p in self.dep.placements]
        dup = np.flatnonzero(
            np.asarray(strategies)[self.dep.kind] == "duplicated")
        if not len(dup):
            raise ValueError("no Duplicated row: nothing reads the bitsets")
        self.first_dup = int(dup[0])

    # -- the window --------------------------------------------------------

    def prepare(self, g: int) -> None:
        """The snapshots are ready. While the ring may still be warming
        (the harness gives it 8 turns), end a set-up that cannot settle:
        a program that interns every spread selection as a placement grows
        its placement table with each snapshot generation and compiles for
        each new size, a wave of minutes, for hours."""
        if g >= 8 * self.ring:
            return
        now, slots = time.perf_counter(), self.dep.slot_count()
        if self._t_first is None:
            self._t_first, self._slots_first = now, slots
        grown = slots - self._slots_first
        if grown > int(self.params["slot_growth_limit"]):
            raise SystemExit(
                f"benchmark.traffic.policydrift: the placement table grew by "
                f"{grown} slots (to {slots}) over {g} snapshot generations "
                f"with no new placement; this program keeps a spread "
                f"selection as a placement, so the ring cannot settle: the "
                f"cell cannot run on it")
        if now - self._t_first > float(self.params["warmup_wall_limit_s"]):
            raise SystemExit(
                f"benchmark.traffic.policydrift: {g} warm-up waves took "
                f"{now - self._t_first:.0f} s; the ring does not settle")

    def wave(self, g: int, annotate) -> int:
        n = super().wave(g, annotate)
        with annotate("harness.read_duplicated"):
            if not self.last[self.first_dup].clusters:
                raise RuntimeError("the first Duplicated row has no cluster")
        return n

    def _check_rows(self, g: int) -> np.ndarray:
        check = self.dep.cfg["check"]
        return placements.sample_rows(
            self.dep.kind, len(self.dep.placements),
            int(check["rows_per_kind"]), int(check["rows_per_wave"]),
            self.dep.seed, g)

    # -- the comparison (after the window, program state freed) ------------

    def expected(self, g: int, rows: np.ndarray,
                 constraints: bool = True) -> list:
        """What wave ``g`` has to answer on ``rows``: (placed, {name: n})."""
        dep = self.dep
        fl, bd = dep.fleet, dep.bind
        names = fl["names"]
        out, placed, _ = policies.place(
            dep.placements, dep.kind[rows], bd["replicas"][rows],
            dep.profiles, bd["prof_idx"][rows],
            gen.prev_dense(bd, rows, len(names)), bd["fresh"][rows],
            fl["allocatable"] - self.allocs[g % self.ring], dep.members,
            constraints=constraints)
        return [
            (bool(placed[j]),
             {names[k]: int(out[j, k]) for k in np.flatnonzero(out[j])}
             if placed[j] else {})
            for j in range(len(rows))
        ]

    def control_collected(self, waves: int) -> tuple:
        """The CONTROL: the reference's own answers with every spread
        constraint left out (what a program that dropped the Select stage,
        or kept a selection of another snapshot's making that happens to
        be all ones, would give), every compared wave at its own
        snapshot."""
        picks = gen.sample_waves(
            waves, int(self.dep.cfg["check"]["waves"]), self.dep.seed)
        kept = {}
        for g in sorted(picks | {waves - 1}):
            rows = self._check_rows(g)
            kept[g] = (rows, self.expected(g, rows, constraints=False))
        return kept, 0

    def check(self, collected: tuple) -> dict:
        kept, undivided = collected
        check = self.dep.cfg["check"]
        n_kinds = len(self.dep.placements)
        rows_compared = mismatched = 0
        decided, per_kind = [], []
        for g, (rows, got) in sorted(kept.items()):
            want = self.expected(g, rows)
            without = self.expected(g, rows, constraints=False)
            bad = sum(1 for a, w in zip(got, want) if a != w)
            moved = sum(1 for w, o in zip(want, without) if w != o)
            counts = np.bincount(self.dep.kind[rows], minlength=n_kinds)
            self.log(f"check wave={g} rows={len(rows)} mismatched={bad} "
                     f"selection_decided={moved} by_kind={counts.tolist()}")
            rows_compared += len(rows)
            mismatched += bad
            decided.append(moved)
            per_kind.append(int(counts.min()))
        return {
            "mismatched_rows": {"value": mismatched, "limit": 0},
            "undivided_rows": {"value": undivided, "limit": 0},
            "rows_compared": {"value": rows_compared,
                              "limit": int(check["rows_per_wave"]),
                              "better": "higher"},
            "rows_of_each_kind": {"value": min(per_kind, default=0),
                                  "limit": int(check["rows_per_kind"]),
                                  "better": "higher"},
            # a run in which the constraints decide nothing is the drift
            # cell with more strategies: both sides could ignore
            # spreadConstraints and agree
            "selection_decided_rows": {
                "value": min(decided, default=0),
                "limit": int(check["selection_decided_rows"]),
                "better": "higher"},
            "_failed": undivided,
        }
