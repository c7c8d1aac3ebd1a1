"""Multichip sharding of the scheduling grid (ISSUE 9).

Placement identity between a single-device engine and a mesh-sharded one
(the conftest 8-virtual-CPU-device mesh stands in for a TPU slice), the
donated persistent residents, the env-resolved mesh construction, and
the mesh-divisible padding semantics. Fast shapes only — the heavier
multi-stage lifecycle (churn/growth/compaction at 4k rows) lives in
``__graft_entry__.dryrun_multichip`` and ``bench.py --multichip``.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import karmada_tpu.scheduler.fleet as fleet_mod
from karmada_tpu.api.policy import Placement, ReplicaSchedulingStrategy
from karmada_tpu.parallel import mesh as mesh_mod
from karmada_tpu.parallel.mesh import (
    divisible,
    mesh_from_shape,
    mesh_shape,
    pad_to_mesh,
    resolve_mesh,
    scheduling_mesh,
)
from karmada_tpu.scheduler import (
    BindingProblem,
    ClusterSnapshot,
    TensorScheduler,
)
from karmada_tpu.utils.builders import (
    dynamic_weight_placement,
    synthetic_fleet,
)
from karmada_tpu.utils.quantity import parse_resource_list

C = 48


@pytest.fixture(scope="module")
def snap():
    return ClusterSnapshot(synthetic_fleet(C, seed=7, taint_fraction=0.08))


def build_problems(snap, n, *, seed=3, with_dup=True, prefix="b"):
    """A mixed batch: Divided rows with prev placements, plus (opt-in)
    Duplicated and zero-replica rows so the feasibility-bitset path runs
    under the mesh too."""
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


def decoded(results):
    return [
        (dict(r.clusters), r.success, tuple(sorted(r.feasible)))
        for r in results
    ]


class TestMeshConstruction:
    def test_resolve_mesh_env_off_by_default(self, monkeypatch):
        monkeypatch.delenv(mesh_mod.MESH_ENV, raising=False)
        assert resolve_mesh(None) is None
        for off in ("", "0", "1"):
            monkeypatch.setenv(mesh_mod.MESH_ENV, off)
            assert resolve_mesh(None) is None

    def test_resolve_mesh_env_builds_and_false_opts_out(self, monkeypatch):
        monkeypatch.setenv(mesh_mod.MESH_ENV, "2")
        m = resolve_mesh(None)
        assert mesh_shape(m) == (("b", 2), ("c", 1))
        # the explicit opt-out beats the env (the trace-manifest pattern)
        assert resolve_mesh(False) is None
        # an explicit Mesh passes through untouched
        assert resolve_mesh(m) is m

    def test_resolve_mesh_cluster_axis_env(self, monkeypatch):
        monkeypatch.setenv(mesh_mod.MESH_ENV, "4")
        monkeypatch.setenv(mesh_mod.CLUSTER_AXIS_ENV, "2")
        assert mesh_shape(resolve_mesh(None)) == (("b", 2), ("c", 2))

    def test_resolve_mesh_bad_values_fail_loudly(self, monkeypatch):
        monkeypatch.setenv(mesh_mod.MESH_ENV, "banana")
        with pytest.raises(ValueError):
            resolve_mesh(None)
        # more devices than the backend hosts: loud, never silent 1-chip
        monkeypatch.setenv(mesh_mod.MESH_ENV, "4096")
        with pytest.raises(ValueError):
            resolve_mesh(None)

    def test_mesh_shape_round_trips(self):
        m = scheduling_mesh(4, cluster_axis=2)
        shape = mesh_shape(m)
        assert shape == (("b", 2), ("c", 2))
        m2 = mesh_from_shape(shape)
        assert mesh_shape(m2) == shape
        assert mesh_shape(None) is None and mesh_from_shape(None) is None

    def test_pad_and_divisible(self):
        m = scheduling_mesh(4)
        assert pad_to_mesh(10, m) == 12 and pad_to_mesh(12, m) == 12
        assert divisible(12, m) and not divisible(10, m)
        assert pad_to_mesh(10, None) == 10 and divisible(10, None)

    def test_materialize_mesh_statics(self):
        st = mesh_mod.materialize_mesh_statics(
            {"mesh": (("b", 2), ("c", 1)), "e_cap": 4}
        )
        assert mesh_shape(st["mesh"]) == (("b", 2), ("c", 1))
        assert st["e_cap"] == 4
        passthrough = {"mesh": None, "e_cap": 4}
        assert mesh_mod.materialize_mesh_statics(passthrough) == passthrough

    def test_family_shardings_cover_families(self):
        m = scheduling_mesh(2)
        for family, spec in mesh_mod.FAMILY_SPECS.items():
            ins = mesh_mod.family_shardings(m, family)
            assert len(ins) == len(spec["in"]), family
            outs = mesh_mod.family_shardings(m, family, "out")
            assert len(outs) == len(spec["out"]), family


class TestShardedPlacementIdentity:
    """Sharded-vs-single identity across the bucket grid, including B not
    divisible by the device count and batches
    small enough that padding dominates whole chunks."""

    # (rows, note) — 512 aligns with the 256-chunk; 300/31 leave padding
    # rows in the tail chunk (31 pads a whole sub-chunk at eff_chunk 256)
    BATCHES = ((512, "aligned"), (300, "padded-tail"), (31, "tiny"))

    def test_mesh2_identity_across_batch_shapes(self, snap):
        mesh = scheduling_mesh(2)
        for n, note in self.BATCHES:
            problems = build_problems(snap, n, prefix=f"s{n}_")
            single = TensorScheduler(snap, trace_manifest="")
            shard = TensorScheduler(snap, mesh=mesh, trace_manifest="")
            for p in range(2):  # steady pass re-uses the delta base
                ref = decoded(single.schedule(problems))
                got = decoded(shard.schedule(problems))
                assert ref == got, (note, n, "pass", p)
            # the fleet path must actually have engaged under the mesh
            # for batches past the threshold — identity over the host
            # fallback would prove nothing about the sharded kernels
            if n >= TensorScheduler.fleet_threshold:
                assert shard._fleet is not None
                assert shard._fleet._mesh is mesh

    def test_mesh4_churn_identity(self, snap, monkeypatch):
        clusters = synthetic_fleet(C, seed=7, taint_fraction=0.08)
        base = ClusterSnapshot(clusters)
        problems = build_problems(base, 512)
        single = TensorScheduler(base, trace_manifest="")
        shard = TensorScheduler(
            base, mesh=scheduling_mesh(4), trace_manifest=""
        )
        assert decoded(single.schedule(problems)) == decoded(
            shard.schedule(problems)
        )
        rng = np.random.default_rng(17)
        for r in range(2):  # availability drift: the churn fold paths
            for cl in clusters:
                rs = cl.status.resource_summary
                for dim, q in list(rs.allocated.items()):
                    alloc = rs.allocatable.get(dim, 0)
                    step = int(rng.integers(-3, 4)) * max(1, alloc // 100)
                    rs.allocated[dim] = int(min(max(0, q + step), alloc))
            drifted = ClusterSnapshot(clusters)
            assert single.update_snapshot(drifted)
            assert shard.update_snapshot(drifted)
            assert decoded(single.schedule(problems)) == decoded(
                shard.schedule(problems)
            ), f"churn-{r}"

    def test_non_pow2_mesh_falls_back_single_device(self, snap):
        # 3 devices cannot divide the pow2 chunk buckets: the table must
        # disable the mesh (loudly logged) and still place identically
        mesh3 = scheduling_mesh(3)
        problems = build_problems(snap, 300)
        single = TensorScheduler(snap, trace_manifest="")
        shard = TensorScheduler(snap, mesh=mesh3, trace_manifest="")
        ref = decoded(single.schedule(problems))
        got = decoded(shard.schedule(problems))
        assert ref == got
        assert shard._fleet is not None and shard._fleet._mesh is None


class TestMeshedQuotaAdmission:
    def test_quota_admission_identity_under_mesh(self, snap):
        """The quota family shards B-wise too (FAMILY_SPECS "quota"):
        admission decisions and the surviving placements must match the
        single-device engine exactly, with the meshed dispatch minting
        its own ledger key."""
        from karmada_tpu.scheduler.quota import QuotaSnapshot

        problems = build_problems(snap, 512, with_dup=False)
        for i, p in enumerate(problems):
            p.namespace = f"ns{i % 3}"
            p.prev = {}  # fresh demand so admission actually gates
        dims = ["cpu", "memory", "pods"]
        # ns0 tight (some denials), ns1 roomy, ns2 unquota'd
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

        single = TensorScheduler(snap, trace_manifest="")
        shard = TensorScheduler(
            snap, mesh=scheduling_mesh(2), trace_manifest=""
        )
        single.set_quota(quota())
        shard.set_quota(quota())
        ref = [(dict(r.clusters), r.success, r.error)
               for r in single.schedule(problems)]
        got = [(dict(r.clusters), r.success, r.error)
               for r in shard.schedule(problems)]
        assert ref == got
        assert any(not s for _, s, _ in ref), "quota never denied anything"
        q_keys = lambda eng: {  # noqa: E731
            k for k in eng._engine_traces if k[0] == "Q"
        }
        assert q_keys(single).isdisjoint(q_keys(shard))


class TestDonatedResidents:
    """The persistent packed state is donated into the next solve: the
    pre-pass buffers are CONSUMED (aliased in place), not copied."""

    @pytest.mark.parametrize("meshed", (False, True), ids=("single", "mesh2"))
    def test_dense_residents_donated(self, snap, meshed):
        problems = build_problems(snap, 512, with_dup=False)
        eng = TensorScheduler(
            snap,
            mesh=scheduling_mesh(2) if meshed else False,
            trace_manifest="",
        )
        eng.schedule(problems)
        old_dense = eng._fleet._res_dense
        old_meta = eng._fleet._res_meta
        eng.schedule(problems)
        assert old_dense.is_deleted() and old_meta.is_deleted()
        if meshed:
            # and the new residents keep the row-sharded layout (the
            # alias only holds when in/out shardings agree)
            spec = eng._fleet._res_dense.sharding.spec
            assert tuple(spec)[:1] == ("b",)

    def test_steady_upload_bounded(self, snap):
        # a steady storm must not re-upload the packed grid: after the
        # first pass the only host->device traffic is the (cached) row
        # index buffer — asserted well below the full state upload
        problems = build_problems(snap, 512, with_dup=False)
        eng = TensorScheduler(snap, trace_manifest="")
        eng.schedule(problems)
        first = eng._fleet.last_breakdown["upload_mb"]
        eng.schedule(problems)
        steady = eng._fleet.last_breakdown["upload_mb"]
        assert first > 0.1  # the initial packed-state upload
        assert steady == 0.0  # all-rows index cached on device


class TestDenseResidentBound:
    """The dense resident is the one layout: a table whose cap x C would
    pass DENSE_RESIDENT_MAX_BYTES is refused where it grows, before
    anything is allocated for it — an input check, not a fallback."""

    @pytest.mark.parametrize("meshed", (False, True), ids=("single", "mesh2"))
    def test_table_past_the_bound_raises_and_allocates_nothing(
        self, snap, monkeypatch, meshed
    ):
        # 512 rows x 48 clusters sits exactly on the bound and is
        # admitted; the 513th row asks for a cap of 1024
        monkeypatch.setattr(fleet_mod, "DENSE_RESIDENT_MAX_BYTES", 512 * C)
        eng = TensorScheduler(
            snap,
            chunk_size=256,
            mesh=scheduling_mesh(2) if meshed else False,
            trace_manifest="",
        )
        too_many = build_problems(snap, 600, with_dup=False, prefix="big_")
        with pytest.raises(fleet_mod.FleetTableTooLarge) as exc:
            eng.schedule(too_many)
        msg = str(exc.value)
        for fact in ("1024 rows", f"{C} clusters", str(1024 * C),
                     str(512 * C)):
            assert fact in msg, (fact, msg)
        table = eng._fleet
        assert table.cap == 512 and table.n_rows == 512
        assert table._st["cp_idx"].shape == (512,)
        assert table._dev_state is None and table._dev_tables is None
        assert table._res_dense is None and table._res_meta is None
        assert not any(table.device_bytes().values())
        # no side path took the batch, and the engine still serves a
        # table that fits, with the host path's placements
        fits = too_many[:400]
        got = decoded(eng.schedule(fits))
        assert eng._fleet is table and table._res_dense.shape == (512, C)
        if meshed:
            assert tuple(table._res_dense.sharding.spec)[:1] == ("b",)
        host = TensorScheduler(snap, trace_manifest="")
        want = decoded(host._schedule_host(
            fits, [host._compiled(p.placement) for p in fits]
        ))
        assert got == want

    def test_budget_env_var_is_gone(self):
        """KARMADA_TPU_DENSE_BUDGET selected between two layouts; with one
        layout it is not a knob: unregistered, and a process that sets it
        reads the same bound."""
        from karmada_tpu.utils.flags import ENV_FLAGS

        assert "KARMADA_TPU_DENSE_BUDGET" not in ENV_FLAGS
        env = dict(os.environ, KARMADA_TPU_DENSE_BUDGET="1",
                   JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c",
             "import karmada_tpu.scheduler.fleet as f; "
             "print(f.DENSE_RESIDENT_MAX_BYTES)"],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert int(out.stdout.split()[-1]) == 6 << 30
        assert fleet_mod.DENSE_RESIDENT_MAX_BYTES == 6 << 30


class TestMeshTraceIdentity:
    def test_trace_keys_distinguish_mesh_shapes(self, snap):
        """The same workload on mesh=1 vs mesh=2 engines mints DISTINCT
        ledger keys — the restart-across-mesh-change hazard: equal keys
        would let a single-device manifest fake-warm a meshed boot."""
        problems = build_problems(snap, 512, with_dup=False)
        single = TensorScheduler(snap, trace_manifest="")
        shard = TensorScheduler(
            snap, mesh=scheduling_mesh(2), trace_manifest=""
        )
        single.schedule(problems)
        shard.schedule(problems)
        solve_keys = lambda eng: {  # noqa: E731
            k for k in eng._fleet._seen_traces if k[0] in ("A", "L")
        }
        assert solve_keys(single).isdisjoint(solve_keys(shard))

    def test_bits_key_carries_mesh_shape_and_skips_manifest(
        self, snap, tmp_path
    ):
        """The feasibility-bitset ("B") trace key carries the canonical
        mesh shape — not a bool — and its meshed dispatches stay
        manifest-UNRECORDED (the kernel has no mesh static: a replay
        could only compile the single-device form, so recording would
        fake-warm a later boot's ledger). Regression for the review
        finding: a bool element let a mesh=2 manifest seed a mesh=8
        boot's "B" key as already-warmed."""
        from karmada_tpu.scheduler import prewarm

        # Duplicated rows drive the bits path; decoding (feasible access)
        # triggers the lazy dispatch
        problems = build_problems(snap, 256, with_dup=True)
        path = tmp_path / "mesh_bits.json"
        eng = TensorScheduler(
            snap, mesh=scheduling_mesh(2), trace_manifest=str(path)
        )
        decoded(eng.schedule(problems))
        b_keys = {k for k in eng._fleet._seen_traces if k[0] == "B"}
        assert b_keys, "bits path did not dispatch"
        assert all(k[-1] == (("b", 2), ("c", 1)) for k in b_keys)
        assert not any(
            r["kernel"] == "fleet_bits"
            for r in prewarm.TraceManifest(str(path)).records
        )
        # positive control: the single-device engine records it
        path1 = tmp_path / "single_bits.json"
        eng1 = TensorScheduler(
            snap, mesh=False, trace_manifest=str(path1)
        )
        decoded(eng1.schedule(problems))
        assert any(
            r["kernel"] == "fleet_bits"
            for r in prewarm.TraceManifest(str(path1)).records
        )
        assert {
            k for k in eng1._fleet._seen_traces if k[0] == "B"
        } .isdisjoint(b_keys)

    def test_trace_dump_and_debug_endpoint_report_mesh(self, snap):
        """`trace dump` and /debug/traces carry the process's scheduling-
        mesh shape — how an operator tells a single-chip from an 8-chip
        plane without poking jax."""
        import json as _json
        import urllib.request

        from karmada_tpu.cli import cmd_trace_dump
        from karmada_tpu.parallel.mesh import record_active_mesh
        from karmada_tpu.utils.metrics import MetricsServer

        record_active_mesh(scheduling_mesh(2))
        doc = cmd_trace_dump()
        assert doc["mesh"] == [["b", 2], ["c", 1]]
        srv = MetricsServer()
        srv.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/traces", timeout=10
            ) as resp:
                remote = _json.loads(resp.read().decode())
            assert remote["mesh"] == [["b", 2], ["c", 1]]
        finally:
            srv.stop()

    def test_engine_mesh_info(self, snap):
        assert TensorScheduler(snap, trace_manifest="").mesh_info is None
        eng = TensorScheduler(
            snap, mesh=scheduling_mesh(4, cluster_axis=2),
            trace_manifest="",
        )
        assert eng.mesh_info == (("b", 2), ("c", 2))
        # a >1 cluster axis opts the engine into cluster sharding
        assert eng.shard_clusters is True
