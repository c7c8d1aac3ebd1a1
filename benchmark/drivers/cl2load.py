"""The ``cl2load`` deployments: an ``engine`` deployment (a TensorScheduler over
a resident backlog of BindingProblems, benchmark/drivers/engine.py) whose
bindings are ClusterLoader2's load-test Deployments in three groups of size.
The generator's part is benchmark/cl2load.py; the traffic module
(traffic/sizedrift.py) swaps rescaled copies in and drives
``update_snapshot`` and ``schedule``. The program routes every row as it
routes any batch: a row past the fleet table's row bounds takes the general
host path in the same pass."""

from __future__ import annotations

import time

from .. import cl2load, gen
from . import engine


class Deployment(engine.Deployment):
    def generate(self) -> None:
        if hasattr(self, "fleet"):
            return
        self.fleet = gen.fleet(self.cfg, self.seed)
        self.profiles = gen.request_profiles(self.cfg)
        self.bind = cl2load.bindings(
            self.cfg, self.seed, self.fleet, self.profiles)

    def problem(self, i: int, replicas: int, prev: dict):
        """The BindingProblem at position ``i`` asking for ``replicas``
        over the previous result ``prev``."""
        from karmada_tpu.scheduler import BindingProblem

        bd = self.bind
        return BindingProblem(
            key=f"b{i}",
            placement=self.placement,
            replicas=int(replicas),
            requests=self.requests[bd["prof_idx"][i]],
            gvk="apps/v1/Deployment",
            prev=prev,
            fresh=bool(bd["fresh"][i]),
        )

    def setup(self) -> None:
        from karmada_tpu.scheduler import ClusterSnapshot, TensorScheduler
        from karmada_tpu.utils.builders import (
            dynamic_weight_placement,
            new_cluster,
        )

        cfg, log = self.cfg, self.log
        t0 = time.perf_counter()
        self.generate()
        fl, bd = self.fleet, self.bind
        self.clusters = [new_cluster(name) for name in fl["names"]]
        for cl, row in zip(self.clusters, fl["allocatable"].tolist()):
            cl.status.resource_summary.allocatable = dict(zip(gen.DIMS, row))
        self.set_allocated(fl["allocated"])
        snap = ClusterSnapshot(self.clusters)
        log(f"setup fleet_build_s={time.perf_counter() - t0:.2f}")

        t0 = time.perf_counter()
        self.placement = dynamic_weight_placement()
        self.requests = [{"cpu": int(p[0]), "memory": int(p[1])}
                         for p in self.profiles]
        names = fl["names"]
        self.problems = [
            self.problem(i, bd["replicas"][i], cl2load.prev_dict(bd, i, names))
            for i in range(int(cfg["deployments"]))
        ]
        wide = cl2load.wide_rows(cfg, bd, bd["replicas"])
        log(f"setup problem_build_s={time.perf_counter() - t0:.2f} "
            f"wide_rows={int(wide.sum())} "
            f"prev_sites_max={int(bd['n_prev'].max())}")

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

    def free(self) -> None:
        super().free()
        self.placement = self.requests = None
