"""The ``estimator`` deployments: an ``engine`` deployment (a TensorScheduler
over a resident backlog) with scheduler-estimators on, as
``ControlPlane.enable_accurate_estimators`` wires them: one in-process
``AccurateEstimator`` a member over a ``NodeSnapshot`` of its nodes, all in
one ``EstimatorRegistry`` whose batch estimator is the engine's
``extra_estimators``. The traffic module swaps node snapshots into the
estimators and drives ``update_snapshot``, the registry's ``invalidate`` and
``schedule``."""

from __future__ import annotations

import time

from .. import gen, nodes
from . import engine


class Deployment(engine.Deployment):
    def generate(self) -> None:
        if hasattr(self, "fleet"):
            return
        self.node_free = nodes.free(
            self.cfg, nodes.states(self.cfg, None, self.seed)[0])
        self.fleet = nodes.federation(self.cfg)
        self.fleet["allocated"] = nodes.summaries(
            self.fleet["allocatable"], self.node_free)
        self.bind = gen.bindings(self.cfg, self.seed)
        self.profiles = gen.request_profiles(self.cfg)

    def setup(self) -> None:
        from karmada_tpu.estimator.accurate import (
            AccurateEstimator,
            EstimatorRegistry,
            NodeSnapshot,
        )

        if not hasattr(NodeSnapshot, "from_arrays"):
            raise SystemExit(
                "benchmark.drivers.estimator: this program has no "
                "NodeSnapshot.from_arrays: it cannot take the members' node "
                "state as arrays, nor keep a batch with estimators on the "
                "fleet path; the cell cannot run on it")
        t0 = time.perf_counter()
        self.generate()
        self.registry = EstimatorRegistry()
        self.estimators = [
            AccurateEstimator(name, NodeSnapshot.from_arrays(free, nodes.DIMS))
            for name, free in zip(self.fleet["names"], self.node_free)
        ]
        for est in self.estimators:
            self.registry.register(est)
        self.log(f"setup estimators_build_s={time.perf_counter() - t0:.2f}")

        super().setup()  # members, problems, the engine and its first passes
        self.engine.extra_estimators = [
            self.registry.make_batch_estimator(self.fleet["names"])]
        for i in range(12):
            before = self.engine.solve_batches
            t0 = time.perf_counter()
            self.engine.schedule(self.problems)
            fresh = self.engine.last_pass_new_trace
            self.log(f"setup estimator_pass={i} s={time.perf_counter() - t0:.2f} "
                     f"new_trace={fresh}")
            if self.engine.solve_batches - before != 1:
                raise SystemExit(
                    "benchmark.drivers.estimator: the batch left the fleet "
                    f"path with estimators on ({self.engine.solve_batches - before} "
                    "solves for one pass); the cell measures the fleet path")
            if i >= 1 and not fresh and not self.engine.cap_shrink_pending:
                break

    def free(self) -> None:
        super().free()
        self.registry = self.estimators = None
