"""The ``engine`` deployments: a TensorScheduler over a resident backlog of
BindingProblems, built from the generator's arrays. The traffic module
(benchmark/traffic/<kind>.py) drives its entry points,
TensorScheduler.update_snapshot and TensorScheduler.schedule."""

from __future__ import annotations

import time

from .. import gen


class Deployment:
    def __init__(self, cfg: dict, seed: int, log):
        self.cfg, self.seed, self.log = cfg, seed, log

    def generate(self) -> None:
        """Everything drawn from the seed, as arrays (no program object):
        what set-up feeds the program and what the reference reads."""
        self.fleet = gen.fleet(self.cfg, self.seed)
        self.bind = gen.bindings(self.cfg, self.seed)
        self.profiles = gen.request_profiles(self.cfg)

    def setup(self) -> None:
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
        self.clusters = [new_cluster(name) for name in fl["names"]]
        for cl, row in zip(self.clusters, fl["allocatable"].tolist()):
            cl.status.resource_summary.allocatable = dict(zip(gen.DIMS, row))
        self.set_allocated(fl["allocated"])
        snap = ClusterSnapshot(self.clusters)
        log(f"setup fleet_build_s={time.perf_counter() - t0:.2f}")

        t0 = time.perf_counter()
        placement = dynamic_weight_placement()
        names = fl["names"]
        req = [{"cpu": int(p[0]), "memory": int(p[1])} for p in self.profiles]
        self.problems = [
            BindingProblem(
                key=f"b{i}",
                placement=placement,
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

    def set_allocated(self, allocated) -> None:
        for cl, row in zip(self.clusters, allocated.tolist()):
            cl.status.resource_summary.allocated = dict(zip(gen.DIMS, row))

    def state(self) -> str:
        e = self.engine
        return (f"new_trace={e.last_pass_new_trace} "
                f"shrink_pending={e.cap_shrink_pending}")

    def new_trace(self) -> bool:
        return bool(self.engine.last_pass_new_trace)

    def shrink_pending(self) -> bool:
        return bool(self.engine.cap_shrink_pending)

    def free(self) -> None:
        self.engine = self.problems = self.clusters = None
