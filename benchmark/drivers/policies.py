"""The ``policies`` deployments: an ``engine`` deployment (a TensorScheduler
over a resident backlog of BindingProblems) whose members have a place
(region, zone, provider) and labels, and whose tenants use the documented
policy kinds side by side: every binding rides one of the configuration's
``placements`` (Duplicated under a label-selector affinity, static and
dynamic weights, Aggregated, two of them under spread constraints). The
generator's part is benchmark/placements.py; the traffic module drives
``update_snapshot`` and ``schedule`` and reads a Duplicated row."""

from __future__ import annotations

import time

from .. import gen, placements
from . import engine


class Deployment(engine.Deployment):
    # how many placement slots the fleet table may hold beyond one a
    # placement before set-up ends; the traffic module sets it (policydrift:
    # its slot_growth_limit), None leaves set-up unguarded
    slots_spare = None

    def generate(self) -> None:
        if hasattr(self, "fleet"):
            return
        super().generate()
        self.members = placements.members(self.cfg, self.seed)
        self.placements = placements.placements(self.cfg, self.seed)
        self.kind = placements.kinds(self.cfg, self.seed)

    def _placement(self, pl: dict):
        """One of the tenants' placements as the program's API object."""
        from karmada_tpu.api.policy import (
            ClusterAffinity,
            LabelSelector,
            SpreadConstraint,
        )
        from karmada_tpu.utils import builders

        kw = {}
        if pl["affinity_labels"]:
            kw["cluster_affinity"] = ClusterAffinity(label_selector=LabelSelector(
                match_labels=dict(pl["affinity_labels"])))
        if pl["spread"]:
            kw["spread_constraints"] = [
                SpreadConstraint(spread_by_field=f, min_groups=lo, max_groups=hi)
                for f, lo, hi in pl["spread"]]
        if pl["strategy"] == "static":
            names = self.fleet["names"]
            return builders.static_weight_placement(
                {names[j]: int(w) for j, w in enumerate(pl["weights"]) if w},
                **kw)
        return {
            "duplicated": builders.duplicated_placement,
            "dynamic": builders.dynamic_weight_placement,
            "aggregated": builders.aggregated_placement,
        }[pl["strategy"]](**kw)

    def build(self):
        """The members and the resident backlog as the program's objects;
        returns the first snapshot."""
        from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot
        from karmada_tpu.utils.builders import new_cluster

        cfg, log = self.cfg, self.log
        t0 = time.perf_counter()
        self.generate()
        fl, bd, mb = self.fleet, self.bind, self.members
        self.clusters = [
            new_cluster(name, labels=mb["labels"][j], region=mb["region"][j],
                        zone=mb["zone"][j], provider=mb["provider"][j])
            for j, name in enumerate(fl["names"])]
        for cl, row in zip(self.clusters, fl["allocatable"].tolist()):
            cl.status.resource_summary.allocatable = dict(zip(gen.DIMS, row))
        self.set_allocated(fl["allocated"])
        snap = ClusterSnapshot(self.clusters)
        log(f"setup fleet_build_s={time.perf_counter() - t0:.2f}")

        t0 = time.perf_counter()
        policies = [self._placement(pl) for pl in self.placements]
        names = fl["names"]
        req = [{"cpu": int(p[0]), "memory": int(p[1])} for p in self.profiles]
        self.problems = [
            BindingProblem(
                key=f"b{i}",
                placement=policies[self.kind[i]],
                replicas=int(bd["replicas"][i]),
                requests=req[bd["prof_idx"][i]],
                gvk="apps/v1/Deployment",
                prev={
                    names[bd["prev_sites"][i, k]]: int(bd["prev_counts"][i, k])
                    for k in range(bd["n_prev"][i])
                },
                fresh=bool(bd["fresh"][i]),
            )
            for i in range(int(cfg["bindings"]))
        ]
        log(f"setup problem_build_s={time.perf_counter() - t0:.2f}")
        return snap

    def setup(self) -> None:
        from karmada_tpu.scheduler import TensorScheduler

        log = self.log
        self.engine = TensorScheduler(
            self.build(), chunk_size=int(self.cfg["chunk_size"]))
        for i in range(13):
            before = self.engine.solve_batches
            t0 = time.perf_counter()
            self.engine.schedule(self.problems)
            fresh = self.engine.last_pass_new_trace
            slots = self.slot_count()
            log(f"setup pass={i} s={time.perf_counter() - t0:.2f} "
                f"new_trace={fresh} slots={slots}")
            if self.engine.solve_batches - before != 1:
                raise SystemExit(
                    "benchmark.drivers.policies: a row left the fleet path "
                    f"({self.engine.solve_batches - before} solves for one "
                    "pass); the cell measures the fleet path")
            minted = slots - len(self.placements)
            if self.slots_spare is not None and minted > self.slots_spare:
                raise SystemExit(
                    f"benchmark.drivers.policies: after set-up's pass {i} the "
                    f"placement table holds {minted} slots more than the "
                    f"{len(self.placements)} placements the tenants wrote; "
                    "this program keeps a spread selection as a placement, "
                    "so the ring cannot settle: the cell cannot run on it")
            if i >= 1 and not fresh and not self.engine.cap_shrink_pending:
                break

    def slot_count(self) -> int:
        """The fleet table's placement slots: what a program that interns
        a spread selection as a placement lets grow."""
        table = getattr(self.engine, "_fleet", None)
        return len(getattr(table, "_cp_pl", ()))

    def state(self) -> str:
        return f"{super().state()} slots={self.slot_count()}"
