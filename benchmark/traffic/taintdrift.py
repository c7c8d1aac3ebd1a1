"""Traffic kind ``taintdrift`` (tainted deployments): ``drift``'s full-storm
pass over members a slice of which carry a ``NoSchedule`` taint. A ring of
availability snapshots built in set-up (gen.drift_ring; the taints stand); a
wave is ``update_snapshot(next)`` + ``schedule(all problems)``, timed
together.

The comparison is against reference/divide.py with the rows' tolerations
and the members' taints: in the seeded waves and the last, ``rows_per_wave``
rows stratified over four groups (tolerating or not x holding a previous
site on a tainted member or not). Floors, read from the reference's own
answers: rows the taint decides (their answer differs where no member is
tainted), rows kept on a tainted previous site by the filter's leniency
without tolerating it, and answers past ``wide_answer_sites`` sites. The
control is that reference answering with the taint filter left out."""

from __future__ import annotations

import numpy as np

from .. import gen, tainted
from ..reference import divide
from . import drift

DRIVER = "tainted"


class Traffic(drift.Traffic):
    def _check_rows(self, g: int) -> np.ndarray:
        """The rows to compare in wave ``g``, sorted: ``rows_per_wave`` in
        equal strata by (tolerates, holds a tainted previous site)."""
        dep = self.dep
        bd = dep.bind
        tol = bd["tolerates"]
        on_taint = tainted.tainted_prev(bd, dep.fleet["tainted"])
        per = int(dep.cfg["check"]["rows_per_wave"]) // 4
        r = np.random.default_rng([int(dep.seed), 4, int(g), 0x544E54])
        picked = []
        for t in (False, True):
            for p in (False, True):
                pool = np.flatnonzero((tol == t) & (on_taint == p))
                picked.append(r.choice(pool, min(per, len(pool)),
                                       replace=False))
        return np.sort(np.concatenate(picked))

    def _reference(self, g: int, rows: np.ndarray, taints: bool) -> tuple:
        """(assignment int64[len(rows), C], unschedulable bool[len(rows)])
        of wave ``g`` on ``rows``; ``taints`` False leaves the filter out."""
        fl, bd = self.dep.fleet, self.dep.bind
        c = len(fl["names"])
        return divide.place(
            bd["replicas"][rows], self.dep.profiles, bd["prof_idx"][rows],
            bd["tolerates"][rows], gen.prev_dense(bd, rows, c),
            bd["fresh"][rows], fl["allocatable"] - self.allocs[g % self.ring],
            fl["tainted"] if taints else np.zeros(c, bool))

    def _answers(self, out: np.ndarray, uns: np.ndarray) -> list:
        names = self.dep.fleet["names"]
        return [
            (not uns[j],
             {names[k]: int(out[j, k]) for k in np.flatnonzero(out[j])}
             if not uns[j] else {})
            for j in range(len(out))
        ]

    def expected(self, g: int, rows: np.ndarray, taints: bool = True) -> list:
        """What wave ``g`` has to answer on ``rows``: (divided, {name: n})."""
        return self._answers(*self._reference(g, rows, taints))

    def control_collected(self, waves: int) -> tuple:
        """The CONTROL: every compared wave answered by the reference with
        the taint filter left out (what a fleet table that drops the taint
        plane at 5,000 members, or a toleration, would answer)."""
        picks = gen.sample_waves(
            waves, int(self.dep.cfg["check"]["waves"]), self.dep.seed)
        kept = {}
        for g in sorted(picks | {waves - 1}):
            rows = self._check_rows(g)
            kept[g] = (rows, self.expected(g, rows, taints=False))
        return kept, 0

    def check(self, collected: tuple) -> dict:
        kept, undivided = collected
        check = self.dep.cfg["check"]
        fl, bd = self.dep.fleet, self.dep.bind
        c = len(fl["names"])
        mismatched = 0
        per_wave, decided, lenient, wide = [], [], [], []
        for g, (rows, got) in sorted(kept.items()):
            out, uns = self._reference(g, rows, True)
            want = self._answers(out, uns)
            bad = sum(1 for a, w in zip(got, want) if a != w)
            free, free_uns = self._reference(g, rows, False)
            held = out > 0
            prev = gen.prev_dense(bd, rows, c)
            decided.append(int(((free != out).any(axis=1)
                                | (free_uns != uns)).sum()))
            lenient.append(int((~bd["tolerates"][rows] & (
                held & (prev > 0) & fl["tainted"][None, :]).any(axis=1)).sum()))
            wide.append(int((held.sum(axis=1)
                             > int(check["wide_answer_sites"])).sum()))
            self.log(f"check wave={g} rows={len(rows)} mismatched={bad} "
                     f"taint_decided={decided[-1]} lenient={lenient[-1]} "
                     f"wide_answers={wide[-1]} "
                     f"sites_max={int(held.sum(axis=1).max(initial=0))}")
            mismatched += bad
            per_wave.append(len(rows))

        def floor(values, name):
            return {"value": min(values, default=0),
                    "limit": int(check[name]), "better": "higher"}

        return {
            "mismatched_rows": {"value": mismatched, "limit": 0},
            "rows_compared": floor(per_wave, "rows_per_wave"),
            "taint_decided_rows": floor(decided, "taint_decided_rows"),
            "lenient_rows": floor(lenient, "lenient_rows"),
            "wide_answer_rows": floor(wide, "wide_answer_rows"),
            "_failed": undivided,
        }
