"""The ``plane`` deployments: the in-process control plane through its
normal entry points (cli.cmd_init, ControlPlane.join_cluster, store.apply,
ControlPlane.settle) under the harness's fake clock, holding the resident
Deployments under one PropagationPolicy. The traffic module
(benchmark/traffic/<kind>.py) applies its objects and settles."""

from __future__ import annotations

import time

from .. import gen

NS = "default"


class Deployment:
    def __init__(self, cfg: dict, seed: int, log):
        self.cfg, self.seed, self.log = cfg, seed, log
        self.clock = [10_000.0]

    def generate(self) -> None:
        """Everything drawn from the seed, as arrays (no program object)."""
        self.fleet = gen.fleet(self.cfg, self.seed)
        self.reps = gen.deployments(self.cfg, self.seed)
        self.profiles = gen.request_profiles(self.cfg)

    def node_states(self, allocated) -> list:
        """One aggregated node pool a member, holding ``allocated``."""
        from karmada_tpu.estimator.accurate import NodeState

        fl = self.fleet
        return [
            [NodeState(
                name=f"{name}-pool",
                allocatable=dict(zip(gen.DIMS, fl["allocatable"][i].tolist())),
                requested=dict(zip(gen.DIMS, allocated[i].tolist())),
            )]
            for i, name in enumerate(fl["names"])
        ]

    def setup(self) -> None:
        from karmada_tpu import cli
        from karmada_tpu.api import (
            PropagationPolicy,
            PropagationSpec,
            ResourceSelector,
        )
        from karmada_tpu.api.core import ObjectMeta
        from karmada_tpu.utils.builders import (
            dynamic_weight_placement,
            new_cluster,
            new_deployment,
        )
        from karmada_tpu.utils.member import MemberCluster

        cfg, log = self.cfg, self.log
        t0 = time.perf_counter()
        self.generate()
        self.cp = cp = cli.cmd_init(clock=lambda: self.clock[0])
        self.members = []
        for name, nodes in zip(self.fleet["names"],
                               self.node_states(self.fleet["allocated"])):
            member = MemberCluster(name)
            member.nodes = nodes
            self.members.append(member)
            cp.join_cluster(new_cluster(name), member)
        cp.settle()
        log(f"setup join_s={time.perf_counter() - t0:.2f}")

        t0 = time.perf_counter()
        cp.store.apply(PropagationPolicy(
            meta=ObjectMeta(name="bench-policy", namespace=NS),
            spec=PropagationSpec(
                resource_selectors=[
                    ResourceSelector(api_version="apps/v1", kind="Deployment")
                ],
                placement=dynamic_weight_placement(),
            ),
        ))
        prof = cfg["request_profiles"][0]
        for i, reps in enumerate(self.reps.tolist()):
            cp.store.apply(new_deployment(
                f"d{i}", namespace=NS, replicas=reps,
                cpu=f"{prof['cpu_milli']}m", memory=f"{prof['memory_mib']}Mi"))
        log(f"setup apply_s={time.perf_counter() - t0:.2f}")
        t0 = time.perf_counter()
        cp.settle()
        log(f"setup cold_wave_s={time.perf_counter() - t0:.2f}")

    def state(self) -> str:
        e = self.cp.scheduler._engine
        return (f"new_trace={getattr(e, 'last_pass_new_trace', None)} "
                f"shrink_pending={getattr(e, 'cap_shrink_pending', None)}")

    def new_trace(self) -> bool:
        """Always False: fresh traces are read from the program's compile
        counter by the runner. ``last_pass_new_trace`` is only reset by a
        full fleet pass, so after the cold wave it stays True through every
        small wave (PERF.md section 7)."""
        return False

    def shrink_pending(self) -> bool:
        e = self.cp.scheduler._engine
        return bool(e is not None and e.cap_shrink_pending)

    def free(self) -> None:
        self.cp = self.members = None
