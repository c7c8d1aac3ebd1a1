"""The ``failover`` deployments: a ``policies`` deployment (members with a
place and labels, a TensorScheduler over a resident backlog of
BindingProblems) whose tenants configured failover: ordered
``clusterAffinities`` (primary, then backup), ``clusterTolerations`` for
the NotReady taints, and previous sites inside the primary group. The
generator's part is benchmark/failover.py; the traffic module
(traffic/regionloss.py) taints a region, presents the evicted bindings with
their eviction tasks, and drives ``update_snapshot`` and ``schedule``."""

from __future__ import annotations

import time

from .. import failover
from . import engine, policies


class Deployment(policies.Deployment):
    def generate(self) -> None:
        if hasattr(self, "fleet"):
            return
        engine.Deployment.generate(self)
        cfg, seed = self.cfg, self.seed
        self.members = failover.members(cfg, seed)
        self.placements = failover.placements(cfg)
        self.kind = failover.kinds(cfg, seed, self.placements)
        self.bind = failover.home_prev(
            self.bind, self.kind, self.placements, self.members)

    def _placement(self, pl: dict):
        """One of the tenants' placements as the program's API object."""
        from karmada_tpu.api.cluster import Toleration
        from karmada_tpu.api.policy import ClusterAffinityTerm, LabelSelector
        from karmada_tpu.utils import builders

        kw = {}
        if len(pl["terms"]) > 1:
            kw["cluster_affinities"] = [
                ClusterAffinityTerm(
                    affinity_name=name,
                    label_selector=LabelSelector(match_labels=dict(sel))
                    if sel else None)
                for name, sel in pl["terms"]]
        elif pl["terms"][0][1]:
            raise ValueError("a single group selects every member here")
        if pl["tolerates"]:
            # by key, operator Exists, no tolerationSeconds: whatever the
            # effect, for as long as the taint stays
            kw["cluster_tolerations"] = [
                Toleration(key=key, operator="Exists")
                for key in pl["tolerates"]]
        return {
            "duplicated": builders.duplicated_placement,
            "dynamic": builders.dynamic_weight_placement,
            "aggregated": builders.aggregated_placement,
        }[pl["strategy"]](**kw)

    def problem(self, i: int, policy, prev: dict, evict: tuple = ()):
        """Binding ``i`` as the program's scheduling unit."""
        from karmada_tpu.scheduler import BindingProblem

        bd = self.bind
        return BindingProblem(
            key=f"b{i}", placement=policy,
            replicas=int(bd["replicas"][i]),
            requests=self.requests[bd["prof_idx"][i]],
            gvk="apps/v1/Deployment", prev=prev, evict_clusters=evict,
            fresh=bool(bd["fresh"][i]),
        )

    def build(self):
        snap = super().build()
        # kept for the traffic module, which presents evicted bindings anew
        self.policies = [p.placement for p in self.problems]
        self.requests = [
            {"cpu": int(p[0]), "memory": int(p[1])} for p in self.profiles]
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
            log(f"setup pass={i} s={time.perf_counter() - t0:.2f} "
                f"new_trace={fresh} slots={self.slot_count()}")
            self.guard(self.engine.solve_batches - before, f"set-up's pass {i}")
            if i >= 1 and not fresh and not self.engine.cap_shrink_pending:
                break

    def guard(self, solves: int, where: str) -> None:
        """The cell measures the fleet path: a pass of more than one solve
        left it (a program that keeps multi-term or evicted bindings on its
        host path), and set-up ends there."""
        if solves != 1:
            raise SystemExit(
                f"benchmark.drivers.failover: a row left the fleet path in "
                f"{where} ({solves} solves for one pass): this program keeps "
                "bindings with ordered clusterAffinities or eviction tasks "
                "off its fleet table; the cell cannot run on it")
