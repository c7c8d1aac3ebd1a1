"""Traffic kind ``quotachurn`` (quota deployments): a descheduler or
WorkloadRebalancer round over a multi-tenant federation whose platform team
gave each tenant namespace a FederatedResourceQuota, while the status
controller recomputes usage and a hot tenant's quota is raised between
rounds. A ring of steps built in set-up, each an availability snapshot
(``drift``'s ring, unchanged) and a quota state:

- ``u``: every quota'd namespace's ``overallUsed`` moved one step;
- ``R``: the same, and the hottest namespaces' ``overall`` is raised;
- ``L``: the same, and the raise is taken back.

A wave is ``set_quota(the step's QuotaSnapshot, generation = wave number)``
+ ``update_snapshot(next)`` + ``schedule(all bindings)`` + one read of the
first denied row's ``error`` and of the first admitted quota'd row's
``clusters``, timed together. ``prepare()`` (outside the wave) makes the
step's QuotaSnapshot from its packed state, with a ``remaining`` of its
own: the engine debits it.

The comparison is against reference/quota.py: admission over the WHOLE wave
(it is row-coupled), then the division of a stratified sample; the control
is the same reference with every quota left out."""

from __future__ import annotations

import time

import numpy as np

from .. import gen, quota
from ..reference import quota as reference
from . import drift

DRIVER = "quota"


def error_class(error: str) -> str:
    """The program's error string as the reference's class."""
    if not error:
        return ""
    return reference.QUOTA if "quota exceeded" in error else "unschedulable"


class Traffic(drift.Traffic):
    def __init__(self, dep, params: dict, log):
        super().__init__(dep, params, log)
        self.steps = quota.steps(params)
        self.armed = False  # a wave of the window was kept: keep the rest
        self.kinds_kept: set = set()
        self._t_first = None
        self.next_quota = None

    # -- the generator's part ------------------------------------------------

    def generate(self) -> None:
        super().generate()
        dep = self.dep
        dep.generate()
        tn = dep.tenants
        self.states = quota.ring(
            dep.cfg, self.params, tn, dep.demand, dep.usage)
        self.qrow = tn["quota_row"][tn["ns"]]
        self.cap_row = tn["cap_row"][tn["ns"]]
        # admission is a function of the ring step alone: every wave is a
        # quota generation of its own, with the step's full ``remaining``
        self.admitted = [
            reference.admit(self.qrow, dep.demand, rem)
            for rem in self.states["remaining"]]

    # -- set-up ----------------------------------------------------------------

    def build(self) -> None:
        super().build()
        t0 = time.perf_counter()
        dep = self.dep
        st = self.states
        self.packed = [
            dep.pack(st["overall"][k], st["used"][k], k)
            for k in range(self.ring)]
        for k, p in enumerate(self.packed):
            # the program packed what the generator meant (over the
            # generator's dims; a further dim of the snapshot is unlimited)
            r = st["remaining"].shape[2]
            if not (np.array_equal(p.remaining[:, :r], st["remaining"][k])
                    and (p.remaining[:, r:] >= quota.UNLIMITED).all()):
                raise RuntimeError(f"step {k}: build_quota_snapshot's "
                                   "remaining is not the generator's")
        denied = [int((~a).sum()) for a in self.admitted]
        self.first_denied = [int(np.flatnonzero(~a)[0]) for a in self.admitted]
        self.first_admitted = [
            int(np.flatnonzero(a & (self.qrow >= 0))[0])
            for a in self.admitted]
        self.log(f"setup quota_ring_s={time.perf_counter() - t0:.2f} "
                 f"steps={self.steps} denied_a_step={denied} "
                 f"quota_rows={int((self.qrow >= 0).sum())} "
                 f"cap_rows={int((self.cap_row >= 0).sum())}")
        dep.first_passes(self.packed[0])

    # -- the window --------------------------------------------------------------

    def prepare(self, g: int) -> None:
        """The step's QuotaSnapshot, fresh. Once the window has kept one
        wave, the wave that has just run is kept too if it is the first of
        its step kind, so the comparison sees a raise and a lowering. While
        the ring may still be warming (the harness gives it 8 turns), end
        a warm-up that does not settle."""
        if self.armed and g > 0:
            kind = self.steps[(g - 1) % self.ring]
            if kind not in self.kinds_kept and (g - 1) not in self.kept:
                self.keep(g - 1)
        self.next_quota = self.dep.quota_snapshot(
            self.packed[g % self.ring], g)
        if g >= 8 * self.ring:
            return
        now = time.perf_counter()
        if self._t_first is None:
            self._t_first = now
        if now - self._t_first > float(self.params["warmup_wall_limit_s"]):
            raise SystemExit(
                f"benchmark.traffic.quotachurn: {g} warm-up waves took "
                f"{now - self._t_first:.0f} s; the ring does not settle")

    def wave(self, g: int, annotate) -> int:
        engine = self.dep.engine
        k = g % self.ring
        with annotate("harness.set_quota"):
            engine.set_quota(self.next_quota)
        n = super().wave(g, annotate)
        with annotate("harness.read_rows"):
            denied = self.last[self.first_denied[k]]
            held = self.last[self.first_admitted[k]]
            if error_class(denied.error) != reference.QUOTA or not (
                    held.success and held.clusters):
                raise RuntimeError(
                    f"wave {g}: row {self.first_denied[k]} answers "
                    f"{denied.error!r} and row {self.first_admitted[k]} "
                    f"{held.error!r}; the reference denies the first and "
                    "admits the second")
        return n

    def _strata(self, k: int) -> list:
        adm, q, capped = self.admitted[k], self.qrow >= 0, self.cap_row >= 0
        return [~adm, adm & capped, adm & q & ~capped, ~q]

    def _check_rows(self, g: int) -> np.ndarray:
        check = self.dep.cfg["check"]
        k = g % self.ring
        first = None
        if self.steps[k] == "R":
            # the rows the raise cleared come first in the admitted strata
            cleared = ~self.admitted[(k - 1) % self.ring] & self.admitted[k]
            first = [None, cleared, cleared, None]
        return quota.sample_rows(
            self._strata(k), int(check["rows_per_stratum"]),
            int(check["rows_per_wave"]), self.dep.seed, g, first)

    def keep(self, g: int) -> None:
        """Copy out the answers of wave ``g`` on the rows to compare."""
        rows = self._check_rows(g)
        res = self.last
        self.kept[g] = (rows, [
            (error_class(res[i].error), dict(res[i].clusters))
            for i in rows.tolist()])
        self.kinds_kept.add(self.steps[g % self.ring])
        self.armed = True

    def collect(self) -> tuple:
        """(kept answers, rows the last wave left without a placement for
        another reason than their namespace's quota)."""
        return self.kept, sum(
            1 for r in self.last
            if not r.success and error_class(r.error) != reference.QUOTA)

    def free(self) -> None:
        super().free()
        self.packed = self.next_quota = None

    # -- the comparison (after the window, program state freed) ------------------

    def expected(self, g: int, rows: np.ndarray, quotas: bool = True,
                 caps: bool = True) -> list:
        """What wave ``g`` has to answer on ``rows``: [(error class,
        {member: n})]. ``quotas`` False: nobody is denied and no static
        assignment applies (the control); ``caps`` False: the assignments
        alone are left out."""
        dep = self.dep
        fl, bd = dep.fleet, dep.bind
        names = fl["names"]
        k = g % self.ring
        admitted = self.admitted[k][rows] if quotas else np.ones(
            len(rows), bool)
        out, errors = reference.place(
            admitted, bd["replicas"][rows], dep.profiles,
            bd["prof_idx"][rows], gen.prev_dense(bd, rows, len(names)),
            bd["fresh"][rows], fl["allocatable"] - self.allocs[k],
            self.cap_row[rows] if quotas and caps else None,
            dep.caps if quotas and caps else None)
        return [
            (errors[j],
             {names[c]: int(out[j, c]) for c in np.flatnonzero(out[j])}
             if not errors[j] else {})
            for j in range(len(rows))]

    def control_collected(self, waves: int) -> tuple:
        """The CONTROL: the reference's own answers with every quota left
        out (what a program that dropped admission and the cap fold, or a
        federation without FederatedQuotaEnforcement, would give), every
        compared wave at its own snapshot."""
        kept, seen = {}, set()
        picks = gen.sample_waves(
            waves, int(self.dep.cfg["check"]["waves"]), self.dep.seed)
        first = min(picks)
        for g in list(range(first, first + self.ring)) + [waves - 1]:
            kind = self.steps[g % self.ring]
            if g != waves - 1 and kind in seen:
                continue
            seen.add(kind)
            rows = self._check_rows(g)
            kept[g] = (rows, self.expected(g, rows, quotas=False))
        return kept, 0

    def check(self, collected: tuple) -> dict:
        kept, undivided = collected
        check = self.dep.cfg["check"]
        rows_compared = mismatched = 0
        decided, cut, capped, cleared, kinds_seen = [], [], [], [], set()
        n_q = int(self.qrow.max()) + 1
        for g, (rows, got) in sorted(kept.items()):
            k = g % self.ring
            want = self.expected(g, rows)
            without = self.expected(g, rows, caps=False)
            bad = sum(1 for a, w in zip(got, want) if a != w)
            adm = self.admitted[k]
            q = self.qrow >= 0
            both = (np.bincount(self.qrow[q & adm], minlength=n_q) > 0) & (
                np.bincount(self.qrow[q & ~adm], minlength=n_q) > 0)
            kind = self.steps[k]
            kinds_seen.add(kind)
            denied = int((~adm[rows]).sum())
            by_cap = sum(1 for w, o in zip(want, without) if w != o)
            line = (f"check wave={g} step={kind} rows={len(rows)} "
                    f"mismatched={bad} denied_in_sample={denied} "
                    f"denied_in_wave={int((~adm).sum())} "
                    f"fifo_cut_namespaces={int(both.sum())} "
                    f"cap_decided={by_cap}")
            if kind == "R":
                before = self.admitted[(k - 1) % self.ring]
                n = int((~before[rows] & adm[rows]).sum())
                cleared.append(n)
                line += f" raise_cleared={n}"
            self.log(line)
            rows_compared += len(rows)
            mismatched += bad
            decided.append(denied)
            cut.append(int(both.sum()))
            capped.append(by_cap)

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
            # a run that ignores the quotas, the order inside a namespace,
            # the static assignments or a raise cannot pass: each decides
            # at least this many of the compared rows (or namespaces)
            "quota_decided_rows": floor(decided, "quota_decided_rows"),
            "fifo_cut_namespaces": floor(cut, "fifo_cut_namespaces"),
            "cap_decided_rows": floor(capped, "cap_decided_rows"),
            "raise_cleared_rows": floor(cleared, "raise_cleared_rows"),
            "_failed": undivided,
        }
