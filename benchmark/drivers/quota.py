"""The ``quota`` deployments: an ``engine`` deployment (a TensorScheduler over
a resident backlog of BindingProblems, benchmark/drivers/engine.py) whose
bindings live in tenant namespaces, most of them under a
FederatedResourceQuota (``spec.overall``; some with ``staticAssignments``).
The generator's part is benchmark/quota.py; the traffic module
(traffic/quotachurn.py) moves the quotas' usage and limits between waves and
drives ``set_quota``, ``update_snapshot`` and ``schedule``. Every quota state
reaches the program through its own entry point: FederatedResourceQuota
objects packed by ``build_quota_snapshot``."""

from __future__ import annotations

import time

import numpy as np

from .. import gen, quota
from . import engine


class Deployment(engine.Deployment):
    def generate(self) -> None:
        if hasattr(self, "fleet"):
            return
        super().generate()
        cfg, c = self.cfg, int(self.cfg["clusters"])
        self.tenants = quota.tenants(cfg, self.seed)
        self.demand, self.usage = quota.demand(self.bind, self.profiles, c)
        self.caps = quota.caps(cfg, self.tenants)
        self.ns_names = quota.names(cfg)

    def frqs(self, overall: np.ndarray, used: np.ndarray) -> list:
        """One FederatedResourceQuota a quota'd namespace, its status
        reconciled (``status.overall`` = ``spec.overall``) with
        ``overallUsed`` as given; the static assignments are constant."""
        from karmada_tpu.api.core import ObjectMeta
        from karmada_tpu.api.policy import (
            FederatedResourceQuota,
            FederatedResourceQuotaSpec,
            FederatedResourceQuotaStatus,
            StaticClusterAssignment,
        )

        tn, names = self.tenants, self.fleet["names"]
        lim = [gen.DIMS[d] for d in quota.DIMS_LIMITED]
        out = []
        for n in np.flatnonzero(tn["quota_row"] >= 0).tolist():
            q = int(tn["quota_row"][n])
            spec = {res: int(overall[q, d])
                    for res, d in zip(lim, quota.DIMS_LIMITED)}
            static = []
            if tn["cap_row"][n] >= 0:
                hard = self.caps[int(tn["cap_row"][n])]
                static = [
                    StaticClusterAssignment(
                        cluster_name=names[j],
                        hard={res: int(hard[j, d])
                              for res, d in zip(lim, quota.DIMS_LIMITED)})
                    for j in sorted(tn["cap_members"][
                        int(tn["cap_row"][n])].tolist())]
            out.append(FederatedResourceQuota(
                meta=ObjectMeta(name="quota", namespace=self.ns_names[n]),
                spec=FederatedResourceQuotaSpec(
                    overall=spec, static_assignments=static),
                status=FederatedResourceQuotaStatus(
                    overall=dict(spec),
                    overall_used={res: int(used[q, d]) for res, d in
                                  zip(lim, quota.DIMS_LIMITED)}),
            ))
        return out

    def pack(self, overall: np.ndarray, used: np.ndarray, generation: int):
        """A quota state as the program packs it (build_quota_snapshot
        over the FederatedResourceQuota objects and the members' columns)."""
        from karmada_tpu.scheduler import build_quota_snapshot

        return build_quota_snapshot(
            self.frqs(overall, used), self.snap0, generation)

    def quota_snapshot(self, state, generation: int):
        """A fresh QuotaSnapshot of a packed state, with a ``remaining``
        of its own (the engine debits it)."""
        from karmada_tpu.scheduler.quota import QuotaSnapshot

        return QuotaSnapshot(
            dims=state.dims, ns_index=state.ns_index,
            remaining=state.remaining.copy(), cap_index=state.cap_index,
            cluster_caps=state.cluster_caps, generation=generation,
            cap_token=state.cap_token)

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
        self.snap0 = ClusterSnapshot(self.clusters)
        log(f"setup fleet_build_s={time.perf_counter() - t0:.2f}")

        t0 = time.perf_counter()
        placement = dynamic_weight_placement()
        names = fl["names"]
        req = [{"cpu": int(p[0]), "memory": int(p[1])} for p in self.profiles]
        ns = [self.ns_names[n] for n in self.tenants["ns"].tolist()]
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
                namespace=ns[i],
            )
            for i in range(int(cfg["bindings"]))
        ]
        log(f"setup problem_build_s={time.perf_counter() - t0:.2f}")
        self.engine = TensorScheduler(
            self.snap0, chunk_size=int(cfg["chunk_size"]))

    def first_passes(self, state) -> None:
        """Set-up's passes under the first quota state (every pass a
        generation of its own), until one compiles nothing. The cell
        measures admission as row state of the fleet table: a program that
        hands the table an admitted sub-list (its length follows the
        denied set, so the table's buffers do too and the ring does not
        settle) ends the set-up at the first pass."""
        from karmada_tpu.utils.tracing import tracer

        log, n = self.log, len(self.problems)
        for i in range(13):
            self.engine.set_quota(self.quota_snapshot(state, -(i + 1)))
            t0 = time.perf_counter()
            self.engine.schedule(self.problems)
            fresh = self.engine.last_pass_new_trace
            rows = [s["attrs"].get("rows") for s in tracer.dump()
                    if s["name"] == "scheduler.solve"][-1:]
            log(f"setup pass={i} s={time.perf_counter() - t0:.2f} "
                f"new_trace={fresh} table_rows={rows}")
            if rows != [n]:
                raise SystemExit(
                    f"benchmark.drivers.quota: the fleet table was handed "
                    f"{rows} rows of a batch of {n} in set-up's pass {i}: "
                    "this program partitions a quota'd batch before the "
                    "solve, so the batch's length follows the denied set "
                    "and the ring cannot settle; the cell cannot run on it")
            if i >= 1 and not fresh and not self.engine.cap_shrink_pending:
                break

    def free(self) -> None:
        super().free()
        self.snap0 = None
