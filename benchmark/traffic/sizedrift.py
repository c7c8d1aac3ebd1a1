"""Traffic kind ``sizedrift`` (cl2load deployments): ClusterLoader2's scale
phase under drifting member load. A ring of steps built in set-up, each a
snapshot and a list of bindings: the availability moves as ``drift-full``'s
does (gen.drift_ring), and in step k a seeded share of every group holds a
rescaled copy of its Deployment (cl2load.scale_ring): a new object asking
for another size over its base object's previous result; every other
position holds its base object. A wave is ``update_snapshot(next)`` +
``schedule(all bindings)`` + one read of the first big Deployment's
``clusters``, timed together; ``prepare`` swaps nothing in that set-up did
not build.

The comparison is against reference/divide.py: in the seeded wave, the
first wave of two other ring steps after it and the last wave, EVERY row
past the row bounds the configuration names and every other row of a group
whose previous results are divided, plus ``rows_per_wave`` others
stratified by group and by rescaled or not; floors under the wide rows,
the rescaled copies and the big copies scaled below their base size. The
control is that reference answering the wide rows with their previous
result cut to its 32 largest sites and their replicas to 128."""

from __future__ import annotations

import time

import numpy as np

from .. import cl2load, gen
from ..reference import divide
from ..reference.failover import NOT_ENOUGH
from . import drift
from .regionloss import error_class

DRIVER = "cl2load"
#: ring steps a run compares besides the seeded wave's own
OTHER_STEPS = 2


class Traffic(drift.Traffic):
    def __init__(self, dep, params: dict, log):
        self.dep, self.params, self.log = dep, params, log
        self.ring = int(params["ring"])
        self.per_wave = int(dep.cfg["deployments"])
        self.kept: dict = {}
        self.last = None
        self.armed = False  # a wave of the window was kept: keep the rest
        self.steps_kept: set = set()

    # -- the generator's part ------------------------------------------------

    def generate(self) -> None:
        dep = self.dep
        super().generate()
        self.scales = cl2load.scale_ring(dep.cfg, self.params, dep.bind,
                                         dep.seed)
        self.reps = [cl2load.step_replicas(dep.bind, s) for s in self.scales]
        self.wide = [cl2load.wide_rows(dep.cfg, dep.bind, r)
                     for r in self.reps]

    # -- set-up ----------------------------------------------------------------

    def build(self) -> None:
        from karmada_tpu.scheduler import ClusterSnapshot

        t0 = time.perf_counter()
        self.generate()
        dep = self.dep
        self.snaps = []
        for a in self.allocs:
            dep.set_allocated(a)
            self.snaps.append(ClusterSnapshot(dep.clusters))
        self.problems = []
        for step in self.scales:
            problems = list(dep.problems)
            for i, n in zip(step["rows"].tolist(), step["replicas"].tolist()):
                problems[i] = dep.problem(i, n, dep.problems[i].prev)
            self.problems.append(problems)
        big = np.flatnonzero(dep.bind["group"] == len(dep.cfg["groups"]) - 1)
        self.first_big = int(big[0])
        self.log(f"setup ring_build_s={time.perf_counter() - t0:.2f} "
                 f"scaled={[len(s['rows']) for s in self.scales]} "
                 f"wide={[int(w.sum()) for w in self.wide]}")

    # -- the window --------------------------------------------------------------

    def prepare(self, g: int) -> None:
        """The snapshots and the lists are ready. Once the window has kept
        one wave, the wave that has just run is kept too if its ring step
        is not yet among the kept ones, until the seeded wave's step and
        OTHER_STEPS more are."""
        if self.armed and g > 0 and len(self.steps_kept) <= OTHER_STEPS:
            if (g - 1) % self.ring not in self.steps_kept:
                self.keep(g - 1)

    def wave(self, g: int, annotate) -> int:
        engine = self.dep.engine
        k = g % self.ring
        with annotate("harness.update_snapshot"):
            if not engine.update_snapshot(self.snaps[k]):
                raise RuntimeError("update_snapshot refused a step")
        with annotate("harness.schedule"):
            self.last = engine.schedule(self.problems[k])
        with annotate("harness.read_big"):
            if not self.last[self.first_big].clusters:
                raise RuntimeError("the first big Deployment has no answer")
        return self.per_wave

    def _check_rows(self, g: int) -> np.ndarray:
        """The positions to compare in wave ``g``, sorted: every wide row
        and every row of a divided group, and ``rows_per_wave`` others in
        equal strata by (group, rescaled)."""
        dep, k = self.dep, g % self.ring
        bd, cfg = dep.bind, dep.cfg
        wide = self.wide[k]
        divided = np.asarray([x["prev"] == "divided" for x in cfg["groups"]])
        whole = wide | divided[bd["group"]]
        scaled = np.zeros(len(wide), bool)
        scaled[self.scales[k]["rows"]] = True
        strata = [(grp, s) for grp in np.flatnonzero(~divided).tolist()
                  for s in (False, True)]
        per = int(cfg["check"]["rows_per_wave"]) // len(strata)
        r = np.random.default_rng([int(dep.seed), 4, int(g), 0x434C32])
        picked = [np.flatnonzero(whole)]
        for grp, s in strata:
            pool = np.flatnonzero(~whole & (bd["group"] == grp)
                                  & (scaled == s))
            picked.append(r.choice(pool, min(per, len(pool)), replace=False))
        return np.sort(np.concatenate(picked))

    def keep(self, g: int) -> None:
        """Copy out the answers of wave ``g`` on the rows to compare."""
        rows = self._check_rows(g)
        res = self.last
        self.kept[g] = (rows, [(error_class(res[i].error),
                                dict(res[i].clusters)) for i in rows.tolist()])
        self.steps_kept.add(g % self.ring)
        self.armed = True

    def free(self) -> None:
        self.snaps = self.problems = self.last = None

    # -- the comparison (after the window, program state freed) ------------------

    def expected(self, g: int, rows: np.ndarray, narrow: bool = False) -> list:
        """What wave ``g`` has to answer on ``rows``: [(error class,
        {member: n})]. ``narrow``: the CONTROL's inputs, every wide row's
        previous result cut to its largest sites and its replicas to the
        replicas, as many as the configuration's ``row_bounds`` allow."""
        dep, k = self.dep, g % self.ring
        fl, bd = dep.fleet, dep.bind
        names = fl["names"]
        c = len(names)
        replicas = self.reps[k][rows].copy()
        prev = cl2load.prev_dense(bd, rows, c)
        if narrow:
            bound = dep.cfg["row_bounds"]
            cut = self.wide[k][rows]
            keep = int(bound["prev_sites"])
            order = np.argsort(-prev[cut], axis=1, kind="stable")
            drop = np.zeros_like(prev[cut], bool)
            np.put_along_axis(drop, order[:, keep:], True, axis=1)
            prev[cut] = np.where(drop, 0, prev[cut])
            replicas[cut] = np.minimum(replicas[cut], int(bound["replicas"]))
        out, uns = divide.place(
            replicas, dep.profiles, bd["prof_idx"][rows],
            np.zeros(len(rows), bool), prev, bd["fresh"][rows],
            fl["allocatable"] - self.allocs[k], np.zeros(c, bool))
        return [
            (NOT_ENOUGH if uns[j] else "",
             {names[m]: int(out[j, m]) for m in np.flatnonzero(out[j])}
             if not uns[j] else {})
            for j in range(len(rows))
        ]

    def waves_kept_by(self, waves: int) -> list:
        """The waves a run of ``waves`` waves compares: the seeded wave, the
        first waves after it at OTHER_STEPS more ring steps, the last."""
        picks = sorted(gen.sample_waves(
            waves, int(self.dep.cfg["check"]["waves"]), self.dep.seed))
        out, steps = [], set()
        for g in range(picks[0], waves):
            if len(steps) > OTHER_STEPS:
                break
            if g in picks or (out and g % self.ring not in steps):
                out.append(g)
                steps.add(g % self.ring)
        return sorted(set(out) | {waves - 1})

    def control_collected(self, waves: int) -> tuple:
        """The CONTROL: the reference's answers on the narrowed inputs (what
        a careless move of the wide rows onto the fleet table's row state,
        32 previous sites and 128 replicas a row, would give)."""
        kept = {}
        for g in self.waves_kept_by(waves):
            rows = self._check_rows(g)
            kept[g] = (rows, self.expected(g, rows, narrow=True))
        return kept, 0

    def check(self, collected: tuple) -> dict:
        kept, undivided = collected
        check = self.dep.cfg["check"]
        bd = self.dep.bind
        pods, _ = cl2load.group_sizes(self.dep.cfg)
        last_group = len(pods) - 1
        mismatched = scaled_total = scale_down = 0
        per_wave, wide_per_wave, steps = [], [], set()
        for g, (rows, got) in sorted(kept.items()):
            k = g % self.ring
            want = self.expected(g, rows)
            bad = sum(1 for a, w in zip(got, want) if a != w)
            wide = self.wide[k][rows]
            is_scaled = np.isin(rows, self.scales[k]["rows"])
            down = is_scaled & (bd["group"][rows] == last_group) & (
                self.reps[k][rows] < bd["replicas"][rows])
            wide_bad = sum(1 for a, w, x in zip(got, want, wide)
                           if x and a != w)
            self.log(f"check wave={g} step={k} rows={len(rows)} "
                     f"wide={int(wide.sum())} scaled={int(is_scaled.sum())} "
                     f"scale_down_wide={int(down.sum())} mismatched={bad} "
                     f"wide_mismatched={wide_bad}")
            mismatched += bad
            per_wave.append(len(rows))
            wide_per_wave.append(int(wide.sum()))
            scaled_total += int(is_scaled.sum())
            scale_down += int(down.sum())
            steps.add(k)

        def floor(value, name):
            return {"value": value, "limit": int(check[name]),
                    "better": "higher"}

        return {
            "mismatched_rows": {"value": mismatched, "limit": 0},
            "rows_compared": floor(min(per_wave, default=0), "rows_per_wave"),
            "ring_steps_compared": {"value": len(steps),
                                    "limit": OTHER_STEPS + 1,
                                    "better": "higher"},
            # a run that cuts the wide rows' previous results or replicas
            # cannot pass: every one of them is compared, in every wave
            "wide_decided_rows": floor(min(wide_per_wave, default=0),
                                       "wide_decided_rows"),
            "scaled_rows": floor(scaled_total, "scaled_rows"),
            "scale_down_wide_rows": floor(scale_down, "scale_down_wide_rows"),
            "_failed": undivided,
        }
