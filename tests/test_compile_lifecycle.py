"""Compile-lifecycle subsystem: persistent trace manifest + AOT prewarm.

The cold-start contract (ISSUE 1): the fleet engine persists every fresh
solve-family trace signature (kernel + input shapes + statics) to a
TraceManifest; ``prewarm.warmup`` replays the manifest through AOT
compilation in a process that has never scheduled anything; an engine
restored from a REPLAYED manifest reports ``new_trace=False`` on its
first pass over a covered fleet shape — including across a real process
restart (subprocess test below).

Everything runs at toy shapes on the conftest CPU platform, so tier-1
exercises the whole subsystem without TPU access.
"""

import json
import os
import subprocess
import sys

import numpy as np

from karmada_tpu.scheduler import (
    BindingProblem,
    ClusterSnapshot,
    TensorScheduler,
)
from karmada_tpu.scheduler import prewarm
from karmada_tpu.utils.builders import (
    dynamic_weight_placement,
    synthetic_fleet,
)
from karmada_tpu.utils.quantity import parse_resource_list

C, B = 50, 300


def toy_problems(n=B, seed=11):
    rng = np.random.default_rng(seed)
    pl = dynamic_weight_placement()
    profiles = [
        parse_resource_list(
            {"cpu": f"{250 * (p + 1)}m", "memory": f"{512 * (p + 1)}Mi"}
        )
        for p in range(3)
    ]
    return [
        BindingProblem(
            key=f"t{i}",
            placement=pl,
            replicas=int(rng.integers(1, 40)),
            requests=profiles[i % 3],
            gvk="apps/v1/Deployment",
        )
        for i in range(n)
    ]


def seed_manifest(path, *, passes=3):
    """Schedule a toy fleet with manifest recording on; returns the
    settled engine (its trace set is what the manifest must replay)."""
    snap = ClusterSnapshot(synthetic_fleet(C, seed=7))
    problems = toy_problems()
    eng = TensorScheduler(snap, trace_manifest=str(path))
    assert eng.trace_manifest is not None
    for _ in range(passes):
        eng.schedule(problems)
    assert eng._fleet is not None, "fleet path did not engage"
    return eng


class TestTraceManifest:
    def test_records_written_and_deduped(self, tmp_path):
        path = tmp_path / "manifest.json"
        seed_manifest(path)
        data = json.loads(path.read_text())
        kernels = [r["kernel"] for r in data["records"]]
        assert kernels, "no trace records persisted"
        assert set(kernels) <= set(prewarm._KERNELS)
        # re-loading dedups to the same record set, and every observed
        # record round-trips its ledger key back to a tuple
        m = prewarm.TraceManifest(str(path))
        assert len(m.records) == len(data["records"])
        for key in m.keys():
            assert isinstance(key, tuple) and isinstance(key[0], str)

    def test_same_workload_records_once(self, tmp_path):
        path = tmp_path / "manifest.json"
        eng = seed_manifest(path)
        n = len(eng.trace_manifest.records)
        # more passes over the settled shape add nothing
        eng.schedule(toy_problems())
        assert len(eng.trace_manifest.records) == n

    def test_corrupt_manifest_tolerated(self, tmp_path):
        path = tmp_path / "manifest.json"
        path.write_text("{not json")
        m = prewarm.TraceManifest(str(path))
        assert m.records == []
        # and replay of an empty manifest is a clean no-op
        stats = prewarm.replay(m)
        assert stats["specs"] == 0 and stats["failed"] == 0

    def test_record_of_a_removed_kernel_dropped_on_load(self, tmp_path):
        """A manifest written by a build that still had the
        entry-resident layout holds ``fleet_solve`` records. The kernel is
        gone: the loader drops them as it drops any unknown kernel, and
        replay compiles the rest without reporting a failure."""
        path = tmp_path / "manifest.json"
        seed_manifest(path)
        data = json.loads(path.read_text())
        n_live = len(data["records"])
        data["records"].insert(0, {
            "kernel": "fleet_solve",
            "key": ["L", 4096, C, [16, 14], 512, 1, 64, 64, 4096, True,
                    None, False, True, None, False, True],
            "in_shapes": [[[4096, 64], "int32"]],
            "statics": {"chunk": 512, "n_chunks": 1, "k_out": 64,
                        "k_res": 64, "e_cap": 4096},
        })
        path.write_text(json.dumps(data))
        m = prewarm.TraceManifest(str(path))
        assert len(m.records) == n_live
        assert all(r["kernel"] != "fleet_solve" for r in m.records)
        stats = prewarm.replay(m, expand=False)
        assert stats["failed"] == 0 and stats["compiled"] == n_live
        assert all(k[0] != "L" for k in m.warmed_keys())

    def test_ir_retrace_round_trip(self, tmp_path):
        """A recorded manifest entry re-traced by the graftlint IR tier
        yields a byte-identical shape/static signature across a
        save/load cycle — the IR004 fidelity contract: replay dedup and
        ledger seeding key on this canon, so any serialization loss
        would make prewarm cover less than the serving path."""
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.graftlint import ir as graft_ir

        path = tmp_path / "manifest.json"
        seed_manifest(path)
        m1 = prewarm.TraceManifest(str(path))
        assert m1.records
        canons = []
        for i, rec in enumerate(m1.records):
            spec = graft_ir.spec_from_record(rec, f"manifest[{i}]")
            original, rebuilt = graft_ir.record_canon(rec, spec)
            assert original == rebuilt, (original, rebuilt)
            canons.append(rebuilt)
        # the full cycle: re-save, re-load, re-derive — still identical
        m1._save()
        m2 = prewarm.TraceManifest(str(path))
        assert [
            graft_ir.record_canon(r, graft_ir.spec_from_record(r, "x"))[1]
            for r in m2.records
        ] == canons

    def test_expand_records_next_bucket(self):
        from karmada_tpu.scheduler.fleet import M_ROUND, _cap_round

        entries = {
            "kernel": "fleet_entries",
            "key": ["E", 1024],
            "in_shapes": [[[64, 4], "int64"]],
            "statics": {"e_cap": 1024, "chunk": 256},
        }
        grown = prewarm.expand_records([entries])
        assert len(grown) == 1
        # expanded specs are honest: no ledger key (never dispatched),
        # and the e_cap landed on the NEXT quantized bucket
        assert grown[0]["key"] is None
        assert grown[0]["statics"]["e_cap"] == _cap_round(1025) > 1024

        # fleet_pass grows the changed-meta cap, but only within the
        # padded row count (an over-bound m_cap is a trace nothing ever
        # dispatches): with n_pad == m_cap, no meta expansion happens
        def pass_rec(n_pad):
            return {
                "kernel": "fleet_pass",
                "key": ["A", 7],
                "in_shapes": [[[4], "int32"]] * 5
                + [[[n_pad, 8], "int64"]],
                "statics": {"m_cap": M_ROUND, "d_cap": 0},
            }

        grown = prewarm.expand_records([pass_rec(4 * M_ROUND)])
        # grow to the next quantum AND shrink to the 4096 floor (the
        # settle-train bucket); the toy key is too short for derivation,
        # so the shrink spec stays compile-only (key=None)
        assert [g["statics"]["m_cap"] for g in grown] == [2 * M_ROUND, 4096]
        assert all(g["key"] is None for g in grown)
        shrunk_only = prewarm.expand_records([pass_rec(M_ROUND)])
        assert [g["statics"]["m_cap"] for g in shrunk_only] == [4096]

        # floor caps expand to the engine's REAL next bucket, not
        # floor+quantum: m_round's first step is 4096 -> M_ROUND, and
        # d_round's is D_FLOOR -> D_ROUND (phantom buckets like 36864
        # would be compiles nothing ever dispatches)
        from karmada_tpu.scheduler.fleet import D_FLOOR, D_ROUND

        floor = {
            "kernel": "fleet_pass",
            "key": ["A", 9],
            "in_shapes": [[[4], "int32"]] * 5
            + [[[4 * M_ROUND, 8], "int64"]],
            "statics": {"m_cap": 4096, "d_cap": D_FLOOR},
        }
        caps = {
            k: g["statics"][k]
            for g in prewarm.expand_records([floor])
            for k in ("m_cap", "d_cap")
            if g["statics"][k] != floor["statics"][k]
        }
        assert caps == {"m_cap": M_ROUND, "d_cap": D_ROUND}


class TestRestoreContract:
    def test_round_trip_restored_engine_first_pass_warm(self, tmp_path):
        path = tmp_path / "manifest.json"
        seed_manifest(path)
        # replay in-process (the warmup boot stage), then a FRESH engine
        # restored from the same manifest must report new_trace=False on
        # its very first pass — zero compiles on the serving path
        stats = prewarm.warmup(str(path))
        assert stats["compiled"] >= stats["records"] > 0
        assert stats["failed"] == 0
        snap = ClusterSnapshot(synthetic_fleet(C, seed=7))
        eng = TensorScheduler(snap, trace_manifest=str(path))
        eng.schedule(toy_problems())
        assert eng.last_pass_new_trace is False

    def test_partial_warm_seeds_only_compiled_keys(self, tmp_path):
        # a record whose compile FAILS during replay (stale manifest vs
        # new build) must not seed the ledger: its trace would still
        # compile at first dispatch, so claiming new_trace=False for it
        # would put a cold compile inside the "warm" window
        path = tmp_path / "manifest.json"
        seed_manifest(path)
        m = prewarm.TraceManifest(str(path))
        good_keys = m.keys()
        bogus = {
            "kernel": "fleet_entries",
            "key": ["E", "bogus", 999],
            "in_shapes": [[[3, 3], "int64"]],
            "statics": {"e_cap": -1, "chunk": 0},
        }
        m.records.append(bogus)
        m._seen.add(prewarm._canon(bogus))
        stats = prewarm.replay(m, expand=False)
        assert stats["failed"] >= 1 and stats["compiled"] >= 1
        warmed = m.warmed_keys()
        assert ("E", "bogus", 999) not in warmed
        assert warmed == good_keys

    def test_explicit_opt_out_beats_env(self, tmp_path, monkeypatch):
        # trace_manifest="" is the documented opt-out; an inherited
        # KARMADA_TPU_TRACE_MANIFEST must not resurrect recording at the
        # fleet layer (the engine resolved the opt-out once)
        env_manifest = tmp_path / "env.json"
        monkeypatch.setenv("KARMADA_TPU_TRACE_MANIFEST", str(env_manifest))
        snap = ClusterSnapshot(synthetic_fleet(C, seed=7))
        eng = TensorScheduler(snap, trace_manifest="")
        assert eng.trace_manifest is None
        eng.schedule(toy_problems())
        assert eng._fleet is not None and eng._fleet._manifest is None
        assert not env_manifest.exists()

    def test_seeding_gated_on_replay(self, tmp_path):
        # an engine handed a manifest that was NOT replayed in this
        # process must not claim a warm first pass: seeding without the
        # compile would report new_trace=False while the compile still
        # runs at first dispatch
        path = tmp_path / "unreplayed.json"
        seed_manifest(path)
        snap = ClusterSnapshot(synthetic_fleet(C, seed=7))
        eng = TensorScheduler(snap, trace_manifest=str(path))
        eng.schedule(toy_problems())
        assert eng.last_pass_new_trace is True

    def test_restore_across_mesh_change(self, tmp_path):
        """A manifest recorded at mesh=1 must NOT seed ``new_trace=False``
        on a multi-device boot (the partitioned executables are distinct
        compiles — their ledger keys carry the mesh shape), while a
        meshed engine's own records DO warm the next meshed boot and the
        single-device records keep warming single-device engines."""
        from karmada_tpu.parallel.mesh import scheduling_mesh

        path = tmp_path / "manifest.json"
        seed_manifest(path)  # single-device records
        prewarm.warmup(str(path))
        snap = ClusterSnapshot(synthetic_fleet(C, seed=7))
        meshed = TensorScheduler(
            snap, mesh=scheduling_mesh(2), trace_manifest=str(path)
        )
        meshed.schedule(toy_problems())
        assert meshed.last_pass_new_trace is True, (
            "a mesh=1 manifest fake-warmed a mesh=2 boot"
        )
        # the meshed pass recorded its partitioned traces (mesh shape in
        # the statics); a fresh warmup replays them over this process's
        # devices and a meshed restart is then genuinely warm
        for _ in range(2):
            meshed.schedule(toy_problems())
        stats = prewarm.warmup(str(path))
        assert stats["failed"] == 0 and stats["compiled"] > 0
        recorded_meshes = {
            json.dumps(r["statics"].get("mesh"))
            for r in prewarm.TraceManifest(str(path)).records
        }
        assert '[["b", 2], ["c", 1]]' in recorded_meshes
        meshed2 = TensorScheduler(
            snap, mesh=scheduling_mesh(2), trace_manifest=str(path)
        )
        meshed2.schedule(toy_problems())
        assert meshed2.last_pass_new_trace is False
        # and the original single-device records still warm 1-chip boots
        single = TensorScheduler(snap, trace_manifest=str(path))
        single.schedule(toy_problems())
        assert single.last_pass_new_trace is False

    def test_restored_engine_settle_train_stays_warm(
        self, tmp_path, monkeypatch
    ):
        """The BENCH_r05 mid-settle compile, at toy scale: a manifest that
        only observed CHURN passes at a table shape misses the
        shrink-bucket solve family (a settle train's cell-delta demand
        collapses to the floor, and the sustained-shrink retune of
        ``fleet_pass``'s ``d_cap`` mints a fresh trace mid-settle). The
        shrink expansion must cover it: an engine restored from the
        churn-only manifest lives the recorded life over again AND a full
        settle train beyond it with no fresh solve trace. The fleet
        reaches its 300 rows only in the storm, so the recorded cold
        start (256 rows) ran the floor bucket at another shape. Full
        passes only (the delta path freezes cap tuning, so shrink
        dynamics live on the full-pass side: every pass moves the
        snapshot generation, so no answer is replayed); the delta quanta
        are cut to toy size so 300 rows x 50 clusters move them."""
        import karmada_tpu.scheduler.fleet as fleet_mod

        monkeypatch.setattr(fleet_mod, "D_FLOOR", 64)
        monkeypatch.setattr(fleet_mod, "D_ROUND", 256)

        def full_pass(eng, problems):
            # the same snapshot under a new generation: the pass
            # dispatches every row
            assert eng.update_snapshot(eng.snapshot)
            return eng.schedule(problems)

        def churned(problems, seed):
            rng = np.random.default_rng(seed)
            out = list(problems)
            for i in rng.choice(len(out), len(out) // 2, replace=False):
                p = out[i]
                out[i] = BindingProblem(
                    key=p.key, placement=p.placement,
                    replicas=int(rng.integers(1, 40)),
                    requests=p.requests, gvk=p.gvk,
                )
            return out

        def settled(problems, seed):
            # exactly 3 rows, replicas GUARANTEED changed and bounded by
            # the churn range (a new max would legitimately re-key the
            # solve) — the settle dispatch shapes stay deterministic
            rng = np.random.default_rng(seed)
            out = list(problems)
            for i in rng.choice(len(out), 3, replace=False):
                p = out[i]
                out[i] = BindingProblem(
                    key=p.key, placement=p.placement,
                    replicas=(p.replicas % 39) + 1,
                    requests=p.requests, gvk=p.gvk,
                )
            return out

        def recorded_life():
            """Cold start at 256 rows, then the churn storm over the
            grown fleet (caps grow and stay up), then one light pass:
            the small-scatter upload shapes are part of any real churn
            history; what the manifest must NOT have observed is the
            settle train's shrink retune."""
            problems = toy_problems()
            yield problems[:256]
            for s in range(1, 4):
                problems = churned(problems, s)
                yield problems
            yield settled(problems, 5)

        def settle_train(problems):
            for s in range(10, 20):
                problems = settled(problems, s)
                yield problems

        # the manifest-persisted solve families (fleet.py ledger-key
        # prefixes): the multi-second compiles the warmup contract
        # covers. Tiny ledger-only utility kernels (the "S" row scatter)
        # stay out of the manifest by design — their first-dispatch
        # compiles are sub-millisecond and allowed.
        solve_fams = ("A", "E", "B")

        def fresh_solve_keys(eng, problems):
            # a table not built yet starts from what the replay warmed
            before = (
                set(eng._fleet._seen_traces) if eng._fleet is not None
                else eng.trace_manifest.warmed_keys()
            )
            full_pass(eng, problems)
            return [
                k for k in eng._fleet._seen_traces - before
                if k[0] in solve_fams
            ]

        path = tmp_path / "churn.json"
        snap = ClusterSnapshot(synthetic_fleet(C, seed=7))
        eng = TensorScheduler(snap, trace_manifest=str(path))
        for problems in recorded_life():
            full_pass(eng, problems)
        churn_records = path.read_bytes()
        settle_start = problems
        # the repro: keep settling THIS engine (light churn, demand near
        # zero) — the cap shrink retunes mid-train and mints a fresh
        # SOLVE trace the churn records never covered
        saw_fresh = []
        for problems in settle_train(settle_start):
            saw_fresh += fresh_solve_keys(eng, problems)
        assert [k[0] for k in saw_fresh] == ["A"], (
            "the settle train did not mint exactly one fresh fleet_pass "
            "trace — shrink dynamics moved; re-point this regression at "
            f"the new retune path: {saw_fresh}"
        )
        # restore from the CHURN-ONLY record set: shrink expansion must
        # prepay (and honestly seed) the settle train's buckets
        path2 = tmp_path / "restored.json"
        path2.write_bytes(churn_records)
        stats = prewarm.warmup(str(path2))
        assert stats["failed"] == 0 and stats["compiled"] > 0
        eng2 = TensorScheduler(snap, trace_manifest=str(path2))
        for i, problems in enumerate(recorded_life()):
            assert not fresh_solve_keys(eng2, problems), (
                f"recorded pass {i} compiled a solve trace on the "
                "restored engine"
            )
            if i == 0:
                assert eng2.last_pass_new_trace is False
        for i, problems in enumerate(settle_train(settle_start)):
            assert not fresh_solve_keys(eng2, problems), (
                f"settle pass {i + 1} compiled a solve trace on the "
                "restored engine"
            )

    def test_restart_smoke_subprocess(self, tmp_path):
        """The real restart: process 1 schedules and exits; process 2
        prewarms from the manifest + persistent cache and must run its
        first pass with new_trace=False. CPU toy shapes — the tier-1
        smoke for the whole cold-start path."""
        manifest = tmp_path / "manifest.json"
        cache = tmp_path / "cache"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
        env["KARMADA_TPU_TRACE_MANIFEST"] = str(manifest)
        body = (
            "import json, sys\n"
            f"sys.path.insert(0, "
            f"{os.path.dirname(os.path.abspath(__file__))!r})\n"
            "from test_compile_lifecycle import "
            "seed_manifest, toy_problems, C\n"
            "from karmada_tpu.scheduler import "
            "ClusterSnapshot, TensorScheduler\n"
            "from karmada_tpu.scheduler.prewarm import warmup\n"
            "from karmada_tpu.utils.builders import synthetic_fleet\n"
            "phase = sys.argv[1]\n"
            "manifest = sys.argv[2]\n"
            "if phase == 'seed':\n"
            "    eng = seed_manifest(manifest)\n"
            "    out = {'records': len(eng.trace_manifest.records)}\n"
            "else:\n"
            "    stats = warmup(manifest)\n"
            "    snap = ClusterSnapshot(synthetic_fleet(C, seed=7))\n"
            "    eng = TensorScheduler(snap, trace_manifest=manifest)\n"
            "    eng.schedule(toy_problems())\n"
            "    out = {'prewarm': stats,\n"
            "           'new_trace': eng.last_pass_new_trace}\n"
            "print(json.dumps(out))\n"
        )

        def run(phase):
            proc = subprocess.run(
                [sys.executable, "-c", body, phase, str(manifest)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, timeout=300,
                cwd=os.path.dirname(os.path.dirname(__file__)),
            )
            assert proc.returncode == 0, proc.stderr[-2000:]
            return json.loads(proc.stdout.strip().splitlines()[-1])

        seeded = run("seed")
        assert seeded["records"] > 0
        restored = run("restore")
        assert restored["prewarm"]["compiled"] > 0
        assert restored["prewarm"]["failed"] == 0
        assert restored["new_trace"] is False


class TestWarmupCLI:
    def test_warmup_verb(self, tmp_path, capsys):
        from karmada_tpu import cli

        path = tmp_path / "manifest.json"
        seed_manifest(path)
        rc = cli.main(["warmup", "--manifest", str(path)])
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 0
        assert out["compiled"] >= out["records"] > 0
        assert out["failed"] == 0
        assert out["manifest"] == str(path)

    def test_warmup_missing_manifest_is_noop(self, tmp_path, capsys):
        from karmada_tpu import cli

        rc = cli.main(
            ["warmup", "--manifest", str(tmp_path / "absent.json")]
        )
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 0
        assert out["specs"] == 0 and out["compiled"] == 0
