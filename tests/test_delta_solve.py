"""ISSUE 20: incremental (dirty-row) scheduling — churn cost
proportional to churn size on the resident mesh state.

Coverage map:
- delta-vs-full placement identity across mesh sizes 1/2/4/8 (the
  conftest 8-virtual-CPU-device mesh), with the per-pass breakdown
  proving the delta path dispatched exactly the churn set;
- row-coupled kernel forcing: an armed preemption plane disables the
  delta solve entirely (full passes, identical placements), and a
  quota-bearing wave routes its changed rows through a COMPLETE scoped
  admission kernel — unchanged denials replay, the working remaining is
  debited for the changed rows' delta demand only;
- stale dirty sets: unknown keys are dropped (safe superset semantics),
  and a dirty set carried across an engine restart onto a different
  mesh shape degrades to a full pass, never a wrong placement;
- the controller plumbing: problem-cache identity <=> content, dirty
  keys accumulated per wave, and the descheduler's dry solve riding the
  delta path without debiting the live quota plane;
- chaos-seeded churn: a PR 7 fault-injection cluster kill lands mid
  churn sequence; placements must exclude the dead member, preserve
  totals, and match a fresh engine's full re-solve bit for bit.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from karmada_tpu import cli as _cli
from karmada_tpu.api import (
    PropagationPolicy,
    PropagationSpec,
    ResourceSelector,
)
from karmada_tpu.api.core import ObjectMeta
from karmada_tpu.api.policy import (
    FederatedResourceQuota,
    FederatedResourceQuotaSpec,
    Placement,
    ReplicaSchedulingStrategy,
)
from karmada_tpu.estimator.accurate import NodeState
from karmada_tpu.parallel.mesh import scheduling_mesh
from karmada_tpu.scheduler import (
    BindingProblem,
    ClusterSnapshot,
    TensorScheduler,
)
from karmada_tpu.scheduler.quota import QuotaSnapshot
from karmada_tpu.utils import faultinject
from karmada_tpu.utils.builders import (
    dynamic_weight_placement,
    new_cluster,
    new_deployment,
    synthetic_fleet,
)
from karmada_tpu.utils.member import MemberCluster
from karmada_tpu.utils.quantity import parse_resource_list

C = 48


@pytest.fixture(scope="module")
def snap():
    return ClusterSnapshot(synthetic_fleet(C, seed=7, taint_fraction=0.08))


def build_problems(snap, n, *, seed=3, with_dup=True, prefix="d"):
    """A mixed batch (the test_mesh_sharding shape): Divided rows with
    prev placements plus Duplicated and zero-replica rows, so the delta
    replay covers every result kind the mirrors encode."""
    pl = dynamic_weight_placement()
    pl_dup = Placement(
        replica_scheduling=ReplicaSchedulingStrategy(
            replica_scheduling_type="Duplicated"
        )
    )
    profiles = [
        parse_resource_list(
            {"cpu": f"{250 * (p + 1)}m", "memory": f"{512 * (p + 1)}Mi"}
        )
        for p in range(4)
    ]
    rng = np.random.default_rng(seed)
    names = snap.names
    out = []
    for i in range(n):
        if with_dup and i % 19 == 0:
            out.append(
                BindingProblem(
                    key=f"{prefix}{i}", placement=pl_dup,
                    replicas=int(rng.integers(0, 5)),
                    requests=profiles[i % 4], gvk="apps/v1/Deployment",
                )
            )
            continue
        prev = (
            {
                names[int(j)]: int(rng.integers(1, 20))
                for j in rng.choice(C, 3, replace=False)
            }
            if rng.random() < 0.7
            else {}
        )
        out.append(
            BindingProblem(
                key=f"{prefix}{i}", placement=pl,
                replicas=int(rng.integers(1, 100)),
                requests=profiles[i % 4], gvk="apps/v1/Deployment",
                prev=prev, fresh=bool(rng.random() < 0.05),
            )
        )
    return out


def churned(problems, rng, count):
    """Replace ``count`` random rows with new objects whose replicas
    changed (bounded so Divided rows stay on the same kernel shapes).
    Returns (new list, changed positions)."""
    idx = np.sort(rng.choice(len(problems), count, replace=False))
    out = list(problems)
    for i in idx:
        p = out[int(i)]
        out[int(i)] = dataclasses.replace(p, replicas=(p.replicas % 39) + 1)
    return out, idx


def full_solve(engine, problems):
    """The full-solve oracle side: a fresh engine over ``engine``'s
    snapshot and mesh holds no armed batch, so it walks the batch and
    dispatches every row."""
    fresh = TensorScheduler(
        engine.snapshot,
        mesh=engine.mesh if engine.mesh is not None else False,
        trace_manifest="",
    )
    fresh.fleet_threshold = engine.fleet_threshold
    return fresh.schedule(problems)


def decoded(results):
    return [
        (r.key, dict(r.clusters), r.success, r.error,
         tuple(sorted(r.feasible)))
        for r in results
    ]


def dirty_dispatched(engine) -> int:
    return int(engine._fleet.last_breakdown.get("dirty_rows", 0))


# --------------------------------------------------------------------------
# delta vs full identity, across mesh shapes
# --------------------------------------------------------------------------


class TestDeltaVsFullIdentity:
    @pytest.mark.parametrize("devices", (1, 2, 4, 8))
    def test_identity_across_mesh_sizes(self, snap, devices):
        """The same churn sequence through a delta engine and a
        fresh full-solve engine on every mesh shape the conftest
        virtual devices can host: placements bit-identical each round,
        and the delta engine's breakdown proves each round dispatched
        exactly the churn set."""
        mesh = scheduling_mesh(devices) if devices > 1 else None
        delta_eng = TensorScheduler(snap, mesh=mesh, trace_manifest="")
        full_eng = TensorScheduler(snap, mesh=mesh, trace_manifest="")
        delta_eng.fleet_threshold = 1
        full_eng.fleet_threshold = 1
        problems = build_problems(snap, 512)
        assert decoded(delta_eng.schedule(problems)) == decoded(
            full_solve(full_eng, problems)
        )
        rng = np.random.default_rng(100 + devices)
        for rnd in range(2):
            problems, idx = churned(problems, rng, 20)
            ref = decoded(full_solve(full_eng, problems))
            got = decoded(delta_eng.schedule(problems))
            assert got == ref, f"mesh={devices} round={rnd}"
            assert dirty_dispatched(delta_eng) == len(idx), (
                f"mesh={devices} round={rnd}: delta pass did not engage "
                "on exactly the churn set"
            )
        assert delta_eng._fleet is not None
        if devices > 1:
            assert delta_eng._fleet._mesh is mesh

    def test_identity_over_three_churn_rounds(self, snap):
        """Single-device, a smaller batch and three rounds: the replay
        reads the host mirrors the dense pass maintains, round after
        round."""
        delta_eng = TensorScheduler(snap, trace_manifest="")
        full_eng = TensorScheduler(snap, trace_manifest="")
        delta_eng.fleet_threshold = 1
        full_eng.fleet_threshold = 1
        problems = build_problems(snap, 300, prefix="r0_")
        delta_eng.schedule(problems)
        full_solve(full_eng, problems)
        rng = np.random.default_rng(7)
        for rnd in range(3):
            problems, idx = churned(problems, rng, 9)
            ref = decoded(full_solve(full_eng, problems))
            got = decoded(delta_eng.schedule(problems))
            assert got == ref, f"round={rnd}"
            assert dirty_dispatched(delta_eng) == len(idx)


# --------------------------------------------------------------------------
# row-coupled kernels force (scoped) full passes
# --------------------------------------------------------------------------


class TestCoupledKernelForcing:
    def test_armed_preemption_forces_full_pass(self, snap):
        """preempt_select ranks victims ACROSS rows: an armed scarcity
        plane must take the full path (dirty_rows == 0) with placements
        still identical; disarming re-enables the delta pass."""
        eng = TensorScheduler(snap, trace_manifest="")
        ref = TensorScheduler(snap, trace_manifest="")
        eng.fleet_threshold = 1
        ref.fleet_threshold = 1
        problems = build_problems(snap, 300, with_dup=False, prefix="p")
        eng.schedule(problems)
        full_solve(ref, problems)
        rng = np.random.default_rng(23)

        eng.set_preemption(lambda exclude: [])
        problems, idx = churned(problems, rng, 8)
        got = decoded(eng.schedule(problems))
        assert got == decoded(full_solve(ref, problems))
        assert dirty_dispatched(eng) == 0, (
            "armed preemption must force the full pass"
        )

        eng.set_preemption(None)
        problems, idx = churned(problems, rng, 8)
        got = decoded(eng.schedule(problems))
        assert got == decoded(full_solve(ref, problems))
        assert dirty_dispatched(eng) == len(idx)

    def test_quota_churn_runs_scoped_admission(self, snap):
        """quota_admit is row_coupled (per-namespace FIFO cumsum): a
        churned quota wave re-admits its changed rows through a COMPLETE
        kernel over their own sub-batch against the working remaining.
        Unchanged denials replay exactly; the debit covers only the
        changed rows' delta demand (the PR 14 working-remaining restore
        contract, extended to the delta path)."""
        dims = ["cpu", "memory", "pods"]
        problems = build_problems(snap, 320, with_dup=False, prefix="q")
        for i, p in enumerate(problems):
            p.namespace = "ns0" if i % 2 == 0 else "ns1"
            p.prev = {}  # fresh demand so admission actually gates
        # ns0 tight (denials), ns1 roomy (every churned row re-admits)
        remaining = np.array(
            [[200_000, 2 << 33, 500], [2**50, 2**50, 2**50]], np.int64
        )

        def quota():
            return QuotaSnapshot(
                dims=dims, ns_index={"ns0": 0, "ns1": 1},
                remaining=remaining.copy(), cap_index={},
                cluster_caps=np.zeros((0, C, 3), np.int64),
                generation=1, cap_token=0,
            )

        eng = TensorScheduler(snap, trace_manifest="")
        eng.fleet_threshold = 1
        eng.set_quota(quota())
        first = eng.schedule(problems)
        denied_before = {r.key for r in first if not r.success}
        assert denied_before, "quota never denied anything"
        r1 = eng.quota.remaining.copy()

        # churn ns1 (roomy) rows only: the denial partition is unchanged
        rng = np.random.default_rng(31)
        ns1_pos = [i for i, p in enumerate(problems) if p.namespace == "ns1"]
        idx = np.sort(rng.choice(ns1_pos, 10, replace=False))
        out = list(problems)
        for i in idx:
            p = out[int(i)]
            out[int(i)] = dataclasses.replace(
                p, replicas=(p.replicas % 39) + 1
            )
        second = eng.schedule(out)

        # unchanged denials replayed exactly, nothing new denied
        assert {r.key for r in second if not r.success} == denied_before
        # the tight namespace was not re-charged for replayed rows
        r2 = eng.quota.remaining
        assert np.array_equal(r2[0], r1[0])
        # the roomy namespace was debited EXACTLY the changed rows'
        # delta demand (prev == {} so delta == the new replica count)
        q = eng.quota
        expect = np.zeros(len(dims), np.int64)
        for i in idx:
            p = out[int(i)]
            expect += q.demand_row(p.requests, p.replicas)
        assert np.array_equal(r1[1] - r2[1], expect)
        # and the admission kernel actually ran scoped: a "Q" trace
        # whose row pad is the CHANGED sub-batch pow2, not the wave's
        sub_pad = 1 << max(0, (len(idx) - 1).bit_length())
        assert any(
            k[0] == "Q" and k[1] == sub_pad for k in eng._engine_traces
        )


# --------------------------------------------------------------------------
# stale dirty sets
# --------------------------------------------------------------------------


class TestStaleDirtySet:
    def test_unknown_dirty_keys_are_dropped(self, snap):
        """Dirty keys are advisory positions on top of the id diff: a
        key the wave does not carry only over-dispatches when it maps —
        an unknown key maps nowhere and must be ignored, results
        unchanged."""
        eng = TensorScheduler(snap, trace_manifest="")
        eng.fleet_threshold = 1
        problems = build_problems(snap, 300, prefix="s")
        base = decoded(eng.schedule(problems))
        again = decoded(
            eng.schedule(problems, dirty_keys={"ghost/one", "ghost/two"})
        )
        assert again == base
        # every named key was unknown: nothing was dispatched
        assert dirty_dispatched(eng) == 0

    def test_dirty_keys_force_redispatch_without_content_change(self, snap):
        """A caller-declared dirty key re-dispatches its row even when
        the problem object is identical — the safe-superset contract
        (estimator pings invalidate rows without touching the spec)."""
        eng = TensorScheduler(snap, trace_manifest="")
        eng.fleet_threshold = 1
        problems = build_problems(snap, 300, with_dup=False, prefix="f")
        base = decoded(eng.schedule(problems))
        dirty = {problems[3].key, problems[117].key}
        again = decoded(eng.schedule(problems, dirty_keys=dirty))
        assert again == base
        assert dirty_dispatched(eng) == len(dirty)

    def test_stale_dirty_set_across_mesh_shape_change(self, snap):
        """A controller restart carries its accumulated dirty set onto a
        freshly built engine with a DIFFERENT mesh shape: the first pass
        has no armed batch, so the stale set degrades to a full pass —
        identical placements, never a partial solve against a resident
        state that does not exist."""
        eng_a = TensorScheduler(
            snap, mesh=scheduling_mesh(2), trace_manifest=""
        )
        eng_a.fleet_threshold = 1
        problems = build_problems(snap, 512, prefix="m")
        eng_a.schedule(problems)
        rng = np.random.default_rng(47)
        problems, idx = churned(problems, rng, 12)
        ref = decoded(eng_a.schedule(problems))
        stale = {problems[int(i)].key for i in idx}

        eng_b = TensorScheduler(
            snap, mesh=scheduling_mesh(4), trace_manifest=""
        )
        eng_b.fleet_threshold = 1
        got = decoded(eng_b.schedule(problems, dirty_keys=stale))
        assert got == ref
        assert dirty_dispatched(eng_b) == 0  # full pass: no armed batch
        # the same stale set against the NOW-armed batch over-dispatches
        # exactly those rows — and answers the same placements
        got2 = decoded(eng_b.schedule(problems, dirty_keys=stale))
        assert got2 == ref
        assert dirty_dispatched(eng_b) == len(stale)


# --------------------------------------------------------------------------
# controller plumbing
# --------------------------------------------------------------------------


def small_plane():
    cp = _cli.cmd_init()
    members = {}
    for name, cpu in (("c0", 64), ("c1", 64), ("c2", 64)):
        caps = {"cpu": str(cpu), "memory": "100Gi", "pods": 1000}
        m = MemberCluster(name)
        m.nodes = [NodeState(
            name=f"{name}-n0", allocatable=parse_resource_list(caps)
        )]
        members[name] = m
        cp.join_cluster(new_cluster(name, **caps), m)
    cp.settle()
    cp.store.apply(PropagationPolicy(
        meta=ObjectMeta(name="pol", namespace="default"),
        spec=PropagationSpec(
            resource_selectors=[ResourceSelector(
                api_version="apps/v1", kind="Deployment"
            )],
            placement=dynamic_weight_placement(),
        ),
    ))
    return cp, members


class TestControllerDirtyPlumbing:
    def test_problem_cache_identity_iff_content(self):
        """Identity <=> content, the delta plumbing's contract: an
        unchanged binding answers the SAME object across waves (no dirty
        mark); a content move replaces it and marks the key dirty."""
        cp, _members = small_plane()
        cp.store.apply(new_deployment("w0", replicas=4, cpu="1",
                                      memory="1Gi"))
        cp.settle()
        key = "default/w0-deployment"
        rb = cp.store.get("ResourceBinding", key)
        sched = cp.scheduler
        # sync the cache to the settled state first (the committed
        # placement updated prev, which IS a content move), then prove
        # stability: rebuilt-but-equal answers the same object, no mark
        p1 = sched._problem_for(key, rb, False)
        sched._dirty_problem_keys.clear()
        p2 = sched._problem_for(key, rb, False)
        assert p2 is p1
        assert key not in sched._dirty_problem_keys
        rb.spec.replicas += 3
        p3 = sched._problem_for(key, rb, False)
        assert p3 is not p1 and p3.replicas == p1.replicas + 3
        assert key in sched._dirty_problem_keys

    def test_dry_solve_delta_leaves_no_trace(self):
        """The descheduler's scoring seam on the delta path: a dry solve
        carrying dirty keys still restores the quota working remaining
        and re-arms provenance (PR 14's contract, extended)."""
        from karmada_tpu.utils.explainstore import ExplainStore

        cp, _members = small_plane()
        cp.store.apply(FederatedResourceQuota(
            meta=ObjectMeta(name="q", namespace="default"),
            spec=FederatedResourceQuotaSpec(overall={"cpu": 100000}),
        ))
        cp.store.apply(new_deployment("w0", replicas=4, cpu="1",
                                      memory="1Gi"))
        cp.settle()
        key = "default/w0-deployment"
        rb = cp.store.get("ResourceBinding", key)
        rb.spec.replicas += 2  # positive delta demand: a leak WOULD debit
        problem = cp.scheduler._problem_for(key, rb, True)
        engine = cp.scheduler._inproc_engine()
        store = ExplainStore(cap=4)
        engine.set_explain(store)
        cp.scheduler._ensure_engine_quota(engine)
        before = engine.quota.remaining.copy()
        res = cp.scheduler.dry_solve([problem], dirty_keys={key})
        assert res[0].success
        assert np.array_equal(engine.quota.remaining, before)
        assert store.debug_doc(proc="t")["waves"] == []
        assert engine.explain is store


# --------------------------------------------------------------------------
# chaos-seeded churn
# --------------------------------------------------------------------------


class TestChaosChurn:
    def teardown_method(self):
        faultinject.disarm()

    def test_seeded_cluster_kill_mid_churn(self):
        """A PR 7 seeded fault (cluster.health=down) lands in the middle
        of a churn sequence: the snapshot swap invalidates the resident
        base, fresh placements must avoid the tainted member, totals
        hold for churned bindings, and the settled plane's placements
        match a fresh engine's full re-solve of every binding bit for
        bit."""
        cp, _members = small_plane()
        n_bindings = 6
        for i in range(6):
            cp.store.apply(new_deployment(
                f"w{i}", replicas=6 + i, cpu="1", memory="1Gi"
            ))
        cp.settle()

        def placements():
            out = {}
            for i in range(n_bindings):
                rb = cp.store.get(
                    "ResourceBinding", f"default/w{i}-deployment"
                )
                out[rb.meta.namespace + "/" + rb.meta.name] = {
                    tc.name: tc.replicas for tc in rb.spec.clusters
                }
            return out

        # churn round 1 (healthy plane)
        for i in (0, 2, 4):
            d = new_deployment(f"w{i}", replicas=10 + i, cpu="1",
                               memory="1Gi")
            cp.store.apply(d)
        cp.settle()

        # the seeded kill fires mid-sequence
        faultinject.arm("cluster.health=down,match=c1", seed=11)
        cp.settle()
        mid = placements()
        # churn round 2 lands while c1 is down: two existing bindings
        # scale up, and one brand-new binding arrives with no prev
        for i in (1, 3):
            cp.store.apply(new_deployment(
                f"w{i}", replicas=12 + i, cpu="1", memory="1Gi"
            ))
        cp.store.apply(new_deployment("w6", replicas=9, cpu="1",
                                      memory="1Gi"))
        n_bindings = 7
        cp.settle()
        after = placements()
        # NotReady stamps the NoSchedule taint. The engine's Steady
        # semantics credit prev, so bindings that already hold replicas
        # on c1 keep it as a weighted member; the hard contract is that
        # totals hold for every churned binding and that a FRESH
        # placement (no prev credit anywhere) never lands on the
        # tainted member.
        for i in (1, 3):
            key = f"default/w{i}-deployment"
            assert sum(after[key].values()) == 12 + i, after[key]
        w6 = after["default/w6-deployment"]
        assert "c1" not in w6, w6
        assert sum(w6.values()) == 9, w6

        # recovery: disarm, re-judge health, settle
        faultinject.disarm()
        cp.settle()

        # the settled plane vs a full re-solve by a fresh engine over the
        # plane's snapshot (no armed batch: it walks): Steady semantics
        # credit prev, so a full solve of the same problems answers the
        # committed placements exactly
        sched = cp.scheduler
        engine = sched._inproc_engine()
        final = placements()
        for i in range(n_bindings):
            key = f"default/w{i}-deployment"
            rb = cp.store.get("ResourceBinding", key)
            problem = sched._problem_for(key, rb, False)
            fresh = TensorScheduler(
                engine.snapshot,
                extra_estimators=engine.extra_estimators,
                disabled_plugins=engine.disabled_plugins,
                custom_filters=engine.custom_filters,
            )
            res = fresh.schedule([problem])
            assert res[0].success, (key, res[0].error)
            assert dict(res[0].clusters) == final[key], key
