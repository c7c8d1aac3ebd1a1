"""The ``tainted`` deployments: an ``engine`` deployment (a TensorScheduler
over a resident backlog of BindingProblems, benchmark/drivers/engine.py)
over heterogeneous members, a slice of which carry a ``NoSchedule`` taint,
with two placements: one plain, one that tolerates the taint. The
generator's part is benchmark/tainted.py; the traffic module
(traffic/taintdrift.py) drives ``update_snapshot`` and ``schedule``."""

from __future__ import annotations

import time

from .. import gen, tainted
from . import engine


class Deployment(engine.Deployment):
    def generate(self) -> None:
        if hasattr(self, "fleet"):
            return
        self.fleet = tainted.members(self.cfg, self.seed)
        self.bind = tainted.bindings(self.cfg, self.seed)
        self.profiles = gen.request_profiles(self.cfg)

    def setup(self) -> None:
        from karmada_tpu.api.cluster import Taint, Toleration
        from karmada_tpu.scheduler import (
            BindingProblem,
            ClusterSnapshot,
            TensorScheduler,
        )
        from karmada_tpu.utils.builders import (
            dynamic_weight_placement,
            new_cluster,
        )

        cfg, log = self.cfg, self.log
        t0 = time.perf_counter()
        self.generate()
        fl, bd = self.fleet, self.bind
        spec = cfg["members"]["taint"]
        taint = Taint(key=spec["key"], value=spec["value"],
                      effect=spec["effect"])
        self.clusters = [
            new_cluster(name, taints=[taint] if t else [])
            for name, t in zip(fl["names"], fl["tainted"].tolist())]
        for cl, row in zip(self.clusters, fl["allocatable"].tolist()):
            cl.status.resource_summary.allocatable = dict(zip(gen.DIMS, row))
        self.set_allocated(fl["allocated"])
        snap = ClusterSnapshot(self.clusters)
        log(f"setup fleet_build_s={time.perf_counter() - t0:.2f} "
            f"tainted={int(fl['tainted'].sum())}")

        t0 = time.perf_counter()
        # by key, operator Exists: whatever the taint's value
        placements = (
            dynamic_weight_placement(),
            dynamic_weight_placement(cluster_tolerations=[
                Toleration(key=spec["key"], operator="Exists")]),
        )
        names = fl["names"]
        req = [{"cpu": int(p[0]), "memory": int(p[1])} for p in self.profiles]
        self.problems = [
            BindingProblem(
                key=f"b{i}",
                placement=placements[int(bd["tolerates"][i])],
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
        log(f"setup problem_build_s={time.perf_counter() - t0:.2f} "
            f"tolerating={int(bd['tolerates'].sum())}")

        self.engine = TensorScheduler(snap, chunk_size=int(cfg["chunk_size"]))
        t0 = time.perf_counter()
        self.engine.schedule(self.problems)
        log(f"setup first_pass_s={time.perf_counter() - t0:.2f} "
            f"new_trace={self.engine.last_pass_new_trace}")
        for i in range(12):
            t0 = time.perf_counter()
            self.engine.schedule(self.problems)
            fresh = self.engine.last_pass_new_trace
            log(f"setup settle_pass={i} s={time.perf_counter() - t0:.2f} "
                f"new_trace={fresh}")
            if i >= 1 and not fresh and not self.engine.cap_shrink_pending:
                break
