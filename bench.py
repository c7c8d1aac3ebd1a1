"""North-star benchmark: 100k bindings x 5k clusters through the ENGINE.

Reproduces BASELINE.json config 5 ("descheduler rebalance storm: 100k
bindings x 5k clusters, dynamic-weight division with taint/toleration
filters") through the REAL scheduling engine — TensorScheduler.schedule()
over BindingProblem objects against a ClusterSnapshot built from Cluster API
objects. The device-resident fleet table (scheduler/fleet.py) makes the
steady-storm pass one fused dispatch + one compact fetch; this is the
engine number, not a kernel-only number.

Measurement protocol (BASELINE.md):
- warm passes compile + tune the entry buffer, timed passes measure the
  steady rebalance storm: every binding re-divides its replicas against
  live availability with previous placements credited (Steady semantics).
- placements are verified identical against TWO independent
  implementations: the pure-Python oracle (karmada_tpu.refimpl, the
  semantics port of the Go divider) on rows sampled across every chunk, and
  the vectorized-numpy host divider (refimpl.divider_np) on EVERY row.
- baselines: vs_python_oracle extrapolates the pure-Python per-binding cost
  (the interpreter-relative multiple round 1 reported); vs_numpy_host times
  the vectorized-numpy divider on the full set (the conservative,
  compiled-host-comparable multiple — the in-tree Go divider the target
  names is a per-binding loop, so honest vectorized numpy is the closest
  calibration this image allows; no Go toolchain exists here).
  ``vs_baseline`` reports the CONSERVATIVE number.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
value = p50 wall seconds for the full 100k x 5k engine pass.

A mixed-strategy verification phase (all four strategies x Steady/Fresh/
scale-up/scale-down cohorts) runs the same engine against the oracle so the
identical-placement claim spans every assignment mode, not just the
headline workload.

Every record names the device it ran on (``platform``, ``device_kind``,
``device_count``). A run that finds no accelerator is an error unless
``--cpu`` asks for the CPU by name, and a failed tier or a placement
mismatch makes the run exit non-zero after the record is printed.
"""

from __future__ import annotations

import argparse
import gc
import hashlib
import json
import os
import sys
import time

import numpy as np


# Tier hygiene: each sub-tier dels its engine/results then gc.collect()s
# so its device residents free before the next tier allocates (three live
# engines exceed HBM at C=5000).


def build_parser():
    p = argparse.ArgumentParser()
    # None = "caller didn't say": resolved per tier in main() (the
    # headline tiers run 100k x 5k, --observability 20k x 512) — an
    # EXPLICIT --bindings 100000 must mean 100000 everywhere
    p.add_argument("--bindings", type=int, default=None)
    p.add_argument("--clusters", type=int, default=None)
    p.add_argument("--chunk", type=int, default=4096)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument(
        "--sample", type=int, default=1024,
        help="pure-Python-oracle sample size (spread across all chunks)",
    )
    p.add_argument(
        "--mix-sample", type=int, default=1024,
        help="mixed-strategy verification rows (all 4 strategies x cohorts)",
    )
    p.add_argument(
        "--cpu", action="store_true",
        help="run on CPU jax (debug; with --multichip/--shard on forced "
        "virtual host devices). Without it a CPU platform is an error: "
        "nothing here measures the CPU by accident",
    )
    p.add_argument(
        "--kernel-only", action="store_true",
        help="round-1 protocol: fused solve kernel with on-device input "
        "generation (no engine, no API objects) — the multichip/sharding "
        "diagnostic, not the headline metric",
    )
    p.add_argument(
        "--shard", default="",
        help="BxC mesh for the kernel step, e.g. 4x2 (requires B*C visible "
        "devices; with C>1 the cluster axis shards and the dispense sorts "
        "ride c-axis collectives). Runs make_sharded_step on host-built "
        "inputs, verifies placement identity against the unsharded step, "
        "and reports both timings.",
    )
    p.add_argument(
        "--multichip", action="store_true",
        help="the REAL multichip tier (supersedes the MULTICHIP_r0* toy "
        "dryruns): run the ENGINE storm at every --mesh-sizes size on "
        "forced host devices — steady p50 scaling curve, placement "
        "bit-identity vs the single-device engine, per-pass host<->device "
        "transfer bytes, and a live donated-buffer-reuse assertion. "
        "Defaults to 20k x 512. Runs on the default backend's devices "
        "and fails when it shows fewer than the largest mesh; --cpu runs "
        "the same tier on forced virtual host devices (identity, donation "
        "and transfer bounds only — no speed).",
    )
    p.add_argument(
        "--mesh-sizes", default="1,2,4,8",
        help="comma-separated device counts for --multichip "
        "(each must be a power of two; 1 = the single-device reference). "
        "Sizes above the visible device count are an error",
    )
    p.add_argument(
        "--no-verify", action="store_true",
        help="skip the oracle/numpy verification phases (timing only)",
    )
    p.add_argument(
        "--trace-dir",
        default="",
        help="capture a jax.profiler (xprof) trace of the timed passes into "
        "this directory — the SURVEY section-5 tracing analogue of the "
        "reference's slow-op trace + pprof endpoints",
    )
    p.add_argument("--dims", type=int, default=4)
    p.add_argument(
        "--hetero", type=int, default=0,
        help="config-5 variant: N UNIQUE placements (distinct label "
        "selectors / tolerations / static weights) spread across the "
        "bindings — stresses placement compilation, mask interning, and "
        "the fleet table's MAX_SLOTS rebuild behavior (SURVEY section 7 "
        "label-selector cost warning). 0 = the homogeneous headline "
        "workload",
    )
    p.add_argument(
        "--cold-start", action="store_true",
        help="measure the plane-restart cold wave: spawn three fresh "
        "engine processes over the headline workload — seed (populate "
        "the persistent compile cache + trace manifest), cold (both "
        "disabled: the pre-cache baseline), restore (manifest prewarm + "
        "cached restart) — and report first-wave latency for each. The "
        "parent never touches jax (one process per chip: each child owns "
        "it in turn)",
    )
    p.add_argument(
        "--cold-child", default="", choices=("", "seed", "cold", "restore"),
        help=argparse.SUPPRESS,
    )
    p.add_argument(
        "--check", default="", metavar="RECORD",
        help="perf-regression guard (ISSUE 12): compare RECORD.json "
        "against the newest committed BENCH_*.json with the same metric "
        "using tools/benchguard.py's per-metric directional noise "
        "bands; prints the verdict table and exits non-zero on any "
        "regression or missing guarded metric",
    )
    p.add_argument(
        "--observability", action="store_true",
        help="run the wave-trace observability tier: a whole-plane storm "
        "wave (default 20k bindings x 512 clusters; --bindings/--clusters "
        "override) through detector->scheduler->binding->works with wave "
        "tracing on, recording the per-phase attribution, the kernel "
        "compile/device/host split, and the coverage of the externally "
        "measured wall clock — the BENCH_OBS_r*.json record",
    )
    p.add_argument(
        "--chaos", action="store_true",
        help="run the chaos-failover tier (default 20k bindings x 512 "
        "clusters; --bindings/--clusters override): a whole-plane storm "
        "with ordered ClusterAffinities placements and live gRPC "
        "estimator servers, then a seeded fault-injection wave killing "
        "--chaos-kill clusters and SIGSTOP-partitioning one estimator "
        "server mid-wave; records time-to-stable-placement, displaced-"
        "binding count, batched-solve count, breaker transitions, and "
        "verifies the recovered placements against the numpy ordered-"
        "failover oracle replaying the same event log — the "
        "BENCH_CHAOS_r*.json record",
    )
    p.add_argument("--chaos-kill", type=int, default=8,
                   help="clusters killed by the chaos wave (K)")
    p.add_argument("--chaos-seed", type=int, default=1,
                   help="fault-injection seed (the replay key)")
    p.add_argument(
        "--quota", action="store_true",
        help="run the quota-enforcement tier (default 20k bindings x 512 "
        "clusters; --bindings/--clusters override): workloads across "
        "--quota-namespaces quota'd namespaces, FRQ limits tightened to "
        "used + headroom, then a CronFederatedHPA surge rescales half "
        "the fleet simultaneously through the scale-up dispense path "
        "against the quotas. Verifies every pass's admission decisions "
        "AND placements against the sequential numpy oracle "
        "(refimpl.quota_np), measures enforcement overhead against "
        "quota-disabled storms, and proves a quota raise clears "
        "QuotaExceeded without a full re-pack — the BENCH_QUOTA_r*.json "
        "record",
    )
    p.add_argument("--quota-namespaces", type=int, default=32,
                   help="quota'd namespaces the workloads spread across")
    p.add_argument(
        "--quota-headroom", type=float, default=0.4,
        help="fraction of the surge's delta demand each namespace's "
        "tightened quota leaves room for (the rest denies)",
    )
    p.add_argument(
        "--preemption", action="store_true",
        help="run the scarcity-plane tier (default 20k bindings x 512 "
        "clusters; --bindings/--clusters override): fill the fleet with "
        "priority-0 workloads, saturate member capacity exactly, then "
        "land a high-priority surge that cannot fit — the batched "
        "preemption kernel selects victims plane-wide and the demanders "
        "re-solve against the freed capacity in the same pass. Verifies "
        "victim selection AND final placements against the sequential "
        "numpy oracle (refimpl.preempt_np), measures armed-vs-disarmed "
        "steady-storm overhead, and runs a drift-rebalance round through "
        "the continuous descheduler under an exact disruption budget — "
        "the BENCH_PREEMPT_r*.json record",
    )
    p.add_argument("--preempt-surge", type=int, default=1000,
                   help="high-priority bindings in the scarcity surge")
    p.add_argument(
        "--preempt-budget", type=int, default=64,
        help="disruption budget for the drift-rebalance round "
        "(KARMADA_TPU_DESCHEDULE_MAX_DISRUPTION)",
    )
    p.add_argument(
        "--scale", action="store_true",
        help="force the scale-1M tier (1M bindings x 5k clusters: steady, "
        "availability-drift churn, and the row-churn delta tiers at "
        "0.1%%/1%%/10%% churn with the full-solve bit-identity oracle) "
        "even when --bindings/--no-verify would "
        "otherwise skip it; the default 100k run includes it already",
    )
    p.add_argument(
        "--estimator-only", action="store_true",
        help="run just the estimator-512 wire tier (4 live gRPC server "
        "processes): full-refresh storm p50 over the batched protocol, "
        "no-movement refresh p50 over GetGenerations pings, the unary-"
        "fallback parity run, and per-pass RPC counts — the "
        "BENCH_ESTIMATOR_r*.json record",
    )
    p.add_argument(
        "--config",
        type=int,
        default=5,
        choices=(1, 2, 3, 4, 5),
        help="BASELINE.json workload config (default 5: 100k x 5k "
        "dynamic-weight rebalance storm); 1-4 run the smaller scenario "
        "suites through the full engine",
    )
    return p



def settle_engine(engine, run_pass, *, floor: int, cap: int, label: str) -> int:
    """THE warm-loop contract, shared by every tier: keep running passes
    until one dispatches no unseen XLA trace AND no cap-shrink desire is
    accumulating (a pending sustained shrink compiles its one allowed
    trace within SHRINK_SUSTAIN passes — it must land here, not in a
    timed window). Returns the number of passes run."""
    for i in range(cap):
        t0 = time.perf_counter()
        run_pass(i)
        fresh = engine.last_pass_new_trace
        print(
            f"# {label} {i}: {time.perf_counter() - t0:.1f}s "
            f"new_trace={fresh}",
            file=sys.stderr,
        )
        if (
            i + 1 >= floor and not fresh
            and not engine.cap_shrink_pending
        ):
            return i + 1
    return cap


# --------------------------------------------------------------------------
# shared verification helpers
# --------------------------------------------------------------------------


def _oracle_inputs(snap, problems, engine):
    """Host-pack problems (the general path, independent of the fleet
    table) into the arrays the oracle and numpy divider consume."""
    compiled = [engine._compiled(p.placement) for p in problems]
    feasible, strategy, replicas, static_w, requests, prev, fresh = (
        engine._pack_chunk(problems, compiled, 0)
    )
    return feasible, strategy, replicas, static_w, requests, prev, fresh


def _general_avail_np(cap_np, requests):
    """numpy mirror of the general estimator: min over requested dims of
    floor(available/request); MAX_INT32 when nothing is requested."""
    from karmada_tpu.refimpl import MAX_INT32

    b, r = requests.shape
    c = cap_np.shape[0]
    out = np.full((b, c), MAX_INT32, np.int64)
    cap = np.maximum(cap_np, 0)
    for d in range(r):
        req = requests[:, d]
        ratio = cap[None, :, d] // np.maximum(req[:, None], 1)
        out = np.where((req > 0)[:, None], np.minimum(out, ratio), out)
    return np.minimum(out, MAX_INT32).astype(np.int64)


def _verify_rows(snap, problems, results, engine, sample_idx):
    """Compare engine results against the pure-Python oracle on the given
    rows. The availability input comes from the engine's profile table
    (which includes the resource-model estimator path — raw floor division
    would falsely flag every config-3-style fleet); the oracle independently
    re-executes the estimator MERGE and the full DIVISION semantics.
    Returns (ok, bad)."""
    from karmada_tpu import refimpl as R

    sub = [problems[i] for i in sample_idx]
    feasible, strategy, replicas, static_w, requests, prev, fresh = (
        _oracle_inputs(snap, sub, engine)
    )
    uniq, inv = np.unique(requests, axis=0, return_inverse=True)
    table = np.asarray(engine._profile_table(uniq))  # [P, C]; -1 = no answer
    ok = bad = 0
    for k, i in enumerate(sample_idx):
        res = results[i]
        cand_idx = np.flatnonzero(feasible[k])
        if len(cand_idx) == 0:
            good = not res.success
            ok, bad = ok + good, bad + (not good)
            continue
        est = [int(table[inv[k], j]) for j in cand_idx]
        avail = R.merge_estimates(int(replicas[k]), [est], len(cand_idx))
        prob = R.DivisionProblem(
            replicas=int(replicas[k]),
            strategy=int(strategy[k]),
            candidates=cand_idx.tolist(),
            available=avail,
            static_weights=[int(static_w[k, j]) for j in cand_idx],
            prev={int(j): int(prev[k, j]) for j in np.flatnonzero(prev[k])}
            or None,
            fresh=bool(fresh[k]),
        )
        try:
            want = R.assign_replicas(prob)
            want_named = {
                snap.names[j]: n for j, n in want.items() if n > 0
            }
            good = res.success and dict(res.clusters) == want_named
        except R.UnschedulableError:
            good = (not res.success) and "not enough" in res.error
        ok, bad = ok + good, bad + (not good)
    return ok, bad


# --------------------------------------------------------------------------
# configs 1-4: engine scenarios
# --------------------------------------------------------------------------


def run_engine_config(config: int) -> dict:
    """Configs 1-4: the engine-level BASELINE scenarios (full control-plane
    packing path, CPU-or-TPU agnostic), oracle-verified row by row."""
    import time as _time

    from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot, TensorScheduler
    from karmada_tpu.api.policy import SpreadConstraint, ClusterAffinity, LabelSelector
    from karmada_tpu.utils.builders import (
        aggregated_placement,
        duplicated_placement,
        dynamic_weight_placement,
        static_weight_placement,
        synthetic_fleet,
        new_cluster,
    )
    from karmada_tpu.utils.quantity import parse_resource_list

    req = parse_resource_list({"cpu": "250m", "memory": "512Mi"})
    verify_spread = False
    if config == 1:
        # samples/nginx: Duplicated across 3 members
        clusters = [new_cluster(f"member{i}") for i in (1, 2, 3)]
        placement = duplicated_placement()
        problems = [
            BindingProblem(key="nginx", placement=placement, replicas=2,
                           requests=req, gvk="apps/v1/Deployment")
        ]
        metric = "config1_nginx_duplicated"
    elif config == 2:
        clusters = [new_cluster(f"member{i}") for i in (1, 2, 3)]
        placement = static_weight_placement(
            {"member1": 2, "member2": 1, "member3": 1}
        )
        problems = [
            BindingProblem(key="web", placement=placement, replicas=10,
                           requests=req, gvk="apps/v1/Deployment")
        ]
        metric = "config2_static_weight_10"
    elif config == 3:
        from karmada_tpu.api.cluster import ResourceModel, ResourceModelRange, AllocatableModeling

        clusters = synthetic_fleet(20, seed=3)
        for cl in clusters:  # per-cluster ResourceModels (grade buckets)
            cl.spec.resource_models = [
                ResourceModel(grade=g, ranges=[
                    ResourceModelRange(name="cpu", min=1000 * 2**g, max=1000 * 2**(g + 1)),
                    ResourceModelRange(name="memory", min=(2 << 30) * 2**g,
                                       max=(2 << 30) * 2**(g + 1)),
                ])
                for g in range(3)
            ]
            cl.status.resource_summary.allocatable_modelings = [
                AllocatableModeling(grade=g, count=10 * (g + 1)) for g in range(3)
            ]
        placement = aggregated_placement()
        problems = [
            BindingProblem(key=f"b{i}", placement=placement,
                           replicas=(i % 20) + 1, requests=req,
                           gvk="apps/v1/Deployment")
            for i in range(100)
        ]
        metric = "config3_aggregated_models_100x20"
    else:  # config 4
        clusters = synthetic_fleet(500, seed=4)
        placement = dynamic_weight_placement(
            cluster_affinity=ClusterAffinity(
                label_selector=LabelSelector(match_labels={"env": "prod"})
            ),
            spread_constraints=[
                SpreadConstraint(spread_by_field="region", min_groups=2, max_groups=4),
                SpreadConstraint(spread_by_field="cluster", min_groups=2, max_groups=10),
            ],
        )
        problems = [
            BindingProblem(key=f"b{i}", placement=placement,
                           replicas=(i % 40) + 1, requests=req,
                           gvk="apps/v1/Deployment")
            for i in range(10_000)
        ]
        metric = "config4_spread_region_10kx500"
        verify_spread = True

    snap = ClusterSnapshot(clusters)
    sched = TensorScheduler(snap, chunk_size=4096)
    # warm with the full set so every padded chunk shape is traced; the
    # steady-state number is what the always-on scheduler process sees
    sched.schedule(problems)
    t0 = _time.perf_counter()
    results = sched.schedule(problems)
    wall = _time.perf_counter() - t0
    ok = sum(1 for r in results if r.success)

    # oracle verification: every row for small configs, a spread sample for
    # config 4 (whose selection narrowing is covered by its own golden
    # tests — the oracle verifies the division on the selected candidates)
    t0 = _time.perf_counter()
    if verify_spread:
        # EXACT placement identity for the spread config: the pure-Python
        # spread-selection oracle (refimpl.spread — independent of the
        # engine's scheduler/spread+groups path) narrows the candidates,
        # then the division oracle re-derives the assignment; every row
        # must match the engine bit for bit (VERDICT r3 item 8)
        from karmada_tpu import refimpl as R
        from karmada_tpu.refimpl.spread import select_spread_clusters

        host_eng = TensorScheduler(snap)
        feasible, strategy, reps_arr, static_w, requests, prev, fr = (
            _oracle_inputs(snap, problems, host_eng)
        )
        uniq, inv = np.unique(requests, axis=0, return_inverse=True)
        table = np.asarray(host_eng._profile_table(uniq))
        region_of = {
            j: snap.clusters[j].spec.region for j in range(len(snap.names))
        }
        constraints = {
            sc.spread_by_field: (sc.min_groups, sc.max_groups)
            for sc in placement.spread_constraints
        }
        n_ok = n_bad = 0
        t_oracle0 = _time.perf_counter()
        for i in range(len(problems)):
            res = results[i]
            reps_i = int(reps_arr[i])
            cand = np.flatnonzero(feasible[i])
            est_all = [int(v) for v in table[inv[i]]]
            merged = R.merge_estimates(reps_i, [est_all], len(est_all))
            score = {int(j): 100 if prev[i, j] > 0 else 0 for j in cand}
            credited = {
                int(j): merged[j] + int(prev[i, j]) for j in cand
            }
            sel = select_spread_clusters(
                [int(j) for j in cand], region_of, score, credited,
                constraints, reps_i, duplicated=False,
            ) if len(cand) else None
            if sel is None:
                good = not res.success
            else:
                prob = R.DivisionProblem(
                    replicas=reps_i,
                    strategy=int(strategy[i]),
                    candidates=sel,
                    available=R.merge_estimates(
                        reps_i, [[est_all[j] for j in sel]], len(sel)
                    ),
                    static_weights=[int(static_w[i, j]) for j in sel],
                    prev={
                        int(j): int(prev[i, j])
                        for j in np.flatnonzero(prev[i])
                    } or None,
                    fresh=bool(fr[i]),
                )
                try:
                    want = R.assign_replicas(prob)
                    want_named = {
                        snap.names[j]: n for j, n in want.items() if n > 0
                    }
                    good = res.success and dict(res.clusters) == want_named
                except R.UnschedulableError:
                    good = (not res.success) and "not enough" in res.error
            n_ok, n_bad = n_ok + good, n_bad + (not good)
        t_oracle = _time.perf_counter() - t_oracle0
        vs_baseline = round(t_oracle / max(wall, 1e-9), 1)
    else:
        n_ok, n_bad = _verify_rows(
            snap, problems, results, TensorScheduler(snap), list(range(len(problems)))
        )
        t_oracle = _time.perf_counter() - t0
        per_binding = t_oracle / max(1, n_ok + n_bad)
        vs_baseline = round(per_binding * len(problems) / max(wall, 1e-9), 1)
    print(
        f"# config {config}: {ok}/{len(problems)} scheduled in {wall:.3f}s; "
        f"oracle check {n_ok} ok / {n_bad} bad",
        file=sys.stderr,
    )
    return {
        "metric": metric,
        "value": round(wall, 4),
        "unit": "s",
        "vs_baseline": vs_baseline,
        "verified_rows": n_ok,
        "verified_mismatches": n_bad,
    }


# --------------------------------------------------------------------------
# config 5: the engine north star
# --------------------------------------------------------------------------


def build_headline_workload(b_total: int, c: int):
    """The config-5 headline fleet + bindings (the control plane's API
    objects), shared by the north-star tier and the cold-start children:
    same seeds and placement mix in every process, so the trace manifest a
    seed process writes covers exactly the shapes a restored process
    dispatches."""
    import types

    from karmada_tpu.api.cluster import Toleration
    from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot
    from karmada_tpu.utils.builders import (
        dynamic_weight_placement,
        synthetic_fleet,
    )
    from karmada_tpu.utils.quantity import parse_resource_list

    t0 = time.perf_counter()
    clusters = synthetic_fleet(c, seed=7, taint_fraction=0.08)
    snap = ClusterSnapshot(clusters)
    names = snap.names
    print(f"# fleet build: {time.perf_counter() - t0:.2f}s", file=sys.stderr)

    # ~30% of bindings tolerate the dedicated taint (two placement objects
    # -> two compiled masks; taint/toleration filter in the feasibility)
    tol = Toleration(key="fleet.io/dedicated", operator="Exists")
    pl_plain = dynamic_weight_placement()
    pl_tol = dynamic_weight_placement(cluster_tolerations=[tol])
    profiles = [
        parse_resource_list(
            {"cpu": f"{250 * (p + 1)}m", "memory": f"{512 * (p + 1)}Mi"}
        )
        for p in range(8)
    ]

    t0 = time.perf_counter()
    rng = np.random.default_rng(42)
    replicas = rng.integers(1, 100, b_total)
    prof_idx = rng.integers(0, 8, b_total)
    tol_mask = rng.random(b_total) < 0.30
    has_prev = rng.random(b_total) < 0.7
    prev_sites = rng.integers(0, c, (b_total, 8))
    prev_counts = rng.integers(1, 30, (b_total, 8))
    n_prev = rng.integers(1, 9, b_total)
    fresh = rng.random(b_total) < 0.05
    problems = [
        BindingProblem(
            key=f"b{i}",
            placement=pl_tol if tol_mask[i] else pl_plain,
            replicas=int(replicas[i]),
            requests=profiles[prof_idx[i]],
            gvk="apps/v1/Deployment",
            prev=(
                {
                    names[prev_sites[i, k]]: int(prev_counts[i, k])
                    for k in range(n_prev[i])
                }
                if has_prev[i]
                else {}
            ),
            fresh=bool(fresh[i]),
        )
        for i in range(b_total)
    ]
    print(f"# problem build: {time.perf_counter() - t0:.2f}s", file=sys.stderr)
    return types.SimpleNamespace(
        clusters=clusters, snap=snap, names=names, tol=tol,
        pl_plain=pl_plain, pl_tol=pl_tol, profiles=profiles,
        replicas=replicas, prof_idx=prof_idx, problems=problems,
    )


# --------------------------------------------------------------------------
# estimator-512 wire tier: batched protocol + generation-gated refresh
# --------------------------------------------------------------------------


def run_estimator_tier(args, tier_status=None) -> dict:
    """Availability from LIVE gRPC accurate estimators: 512 clusters
    multiplexed across 4 real server processes (python -m
    karmada_tpu.estimator --spec-file). Three timed shapes:

    - FULL refresh (invalidate(drop=True) per pass): every cluster re-pays
      the wire, but the batched protocol makes it ONE MaxAvailableReplicas
      Batch RPC per server process instead of clusters x profiles unary
      calls.
    - NO-MOVEMENT refresh (invalidate() per pass): one GetGenerations ping
      per server proves nothing moved, the memoized profile columns stay
      valid, and the fan-out never runs — the steady-state staleness check
      a cluster-status heartbeat triggers.
    - UNARY FALLBACK (KARMADA_TPU_ESTIMATOR_BATCH=0, full refresh): the
      mixed-version path — per-profile calls pipelined over each server
      channel via grpc futures.

    Identity: each cluster's estimator holds one node whose allocatable
    equals the snapshot's free capacity, so min-merge(general, accurate)
    == general and placements must match the snapshot-fed engine bit for
    bit on BOTH protocols. Per-pass RPC counts are recorded to prove the
    O(servers) steady shape."""
    import os

    from karmada_tpu.estimator.accurate import BATCH_ENV
    from karmada_tpu.estimator.fleet import spawn_estimator_fleet
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

    if tier_status is None:
        tier_status = {}
    c_e, b_e, n_servers = 512, 10_000, 4
    e_clusters = synthetic_fleet(c_e, seed=77)
    e_snap = ClusterSnapshot(e_clusters)
    e_names = e_snap.names
    dims = list(e_snap.dims)
    free = np.maximum(np.asarray(e_snap.available_cap), 0)
    pl_plain = dynamic_weight_placement()
    profiles = [
        parse_resource_list(
            {"cpu": f"{250 * (p + 1)}m", "memory": f"{512 * (p + 1)}Mi"}
        )
        for p in range(8)
    ]
    rng_e = np.random.default_rng(17)
    e_problems = [
        BindingProblem(
            key=f"e{i}", placement=pl_plain,
            replicas=int(rng_e.integers(1, 80)),
            requests=profiles[int(rng_e.integers(0, 8))],
            gvk="apps/v1/Deployment",
        )
        for i in range(b_e)
    ]
    with spawn_estimator_fleet(
        e_names, free, dims, n_servers=n_servers, index=e_snap.index,
    ) as fleet:
        registry = fleet.registry
        # the deadline must clear a full UNARY fan-out on the bench rig
        # (the fallback tier re-pays 512 x 8 per-profile RPCs per pass);
        # the batch path never comes near it
        batch = registry.make_batch_estimator(e_names, timeout_seconds=60.0)
        eng_est = TensorScheduler(
            e_snap, chunk_size=args.chunk, extra_estimators=[batch]
        )
        t0 = time.perf_counter()
        eng_est.schedule(e_problems)
        print(
            f"# estimator-512 warm pass: {time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
        )
        for _ in range(2):
            eng_est.schedule(e_problems)

        def timed_passes(tag: str, *, drop: bool, reps: int = 3):
            times, rpcs, res = [], [], None
            for rep in range(reps):
                registry.invalidate(drop=drop)
                c0 = dict(registry.rpc_counts)
                f0 = registry.fanout_seconds_total
                t0 = time.perf_counter()
                res = eng_est.schedule(e_problems)
                times.append(time.perf_counter() - t0)
                rpcs.append(
                    {k: registry.rpc_counts[k] - c0[k] for k in c0}
                )
                print(
                    f"# estimator-512 {tag} pass {rep}: {times[-1]:.3f}s "
                    f"(wire {registry.fanout_seconds_total - f0:.3f}s, "
                    f"rpcs {rpcs[-1]})",
                    file=sys.stderr,
                )
            return float(np.median(times)), rpcs[-1], res

        full_p50, rpc_full, e_res = timed_passes("full-refresh", drop=True)
        refresh_p50, rpc_steady, _ = timed_passes("no-movement", drop=False)

        # unary-fallback parity: the same tier forced onto the per-profile
        # protocol (old-server shape), pipelined over each channel, plus a
        # width-1 reference = the reference's blocking-sequential wire
        # shape measured on THIS rig (r05's 8.28 s came from a larger one)
        from karmada_tpu.estimator.accurate import WIDTH_ENV

        saved_env = {
            k: os.environ.get(k) for k in (BATCH_ENV, WIDTH_ENV)
        }
        os.environ[BATCH_ENV] = "0"
        try:
            fb_p50, rpc_fb, fb_res = timed_passes("fallback", drop=True)
            os.environ[WIDTH_ENV] = "1"
            fb_seq, _rpc_seq, _ = timed_passes(
                "fallback-sequential", drop=True, reps=1
            )
        finally:
            for key, val in saved_env.items():
                if val is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = val

        n_est = sum(1 for r in e_res if r.success)
        # identity vs the snapshot-fed engine on the same problems
        eng_plain = TensorScheduler(e_snap, chunk_size=args.chunk)
        p_res = eng_plain.schedule(e_problems)

        def identical(res):
            return sum(
                1 for a, b_ in zip(res, p_res)
                if a.success == b_.success
                and dict(a.clusters) == dict(b_.clusters)
            )

        ident = identical(e_res)
        fb_ident = identical(fb_res)
        print(
            f"# estimator-512 tier: full-refresh p50 {full_p50:.3f}s, "
            f"no-movement refresh p50 {refresh_p50:.3f}s, fallback p50 "
            f"{fb_p50:.3f}s, {n_est}/{b_e} scheduled, identity vs "
            f"snapshot-fed {ident}/{b_e} (fallback {fb_ident}/{b_e})",
            file=sys.stderr,
        )
        if ident != b_e or fb_ident != b_e:
            # divergence is a TIER FAILURE, not a footnote: flag it in the
            # parsed status so the record (and the generated docs' FAILED-
            # tiers row) can never bury it
            print(
                f"# WARNING: estimator-512 divergence: batch "
                f"{b_e - ident}, fallback {b_e - fb_ident}",
                file=sys.stderr,
            )
            tier_status["estimator-512"] = (
                f"DIVERGED: batch {b_e - ident}/{b_e}, "
                f"fallback {b_e - fb_ident}/{b_e} rows"
            )
        del eng_est, eng_plain, e_res, p_res, fb_res, e_problems
        gc.collect()
        return {
            "metric": f"estimator512_wire_{b_e // 1000}kx{c_e}",
            "value": round(full_p50, 4),
            "unit": "s",
            "estimator512_p50": round(full_p50, 4),
            "estimator512_refresh_p50": round(refresh_p50, 4),
            "estimator512_fallback_p50": round(fb_p50, 4),
            "estimator512_fallback_seq_s": round(fb_seq, 4),
            "estimator512_identical": ident == b_e,
            "estimator512_fallback_identical": fb_ident == b_e,
            "estimator512_rpc_full": rpc_full,
            "estimator512_rpc_steady": rpc_steady,
            "estimator512_rpc_fallback": rpc_fb,
            "estimator512_n_servers": n_servers,
        }


def run_engine_north_star(args) -> dict:
    import jax

    from karmada_tpu.refimpl.divider_np import assign_batch_np
    from karmada_tpu.scheduler import (
        BindingProblem,
        ClusterSnapshot,
        TensorScheduler,
    )
    from karmada_tpu.utils.builders import (
        aggregated_placement,
        duplicated_placement,
        dynamic_weight_placement,
        static_weight_placement,
        synthetic_fleet,
    )

    b_total, c = args.bindings, args.clusters

    # ---- fleet + bindings (the control plane's API objects) ---------------
    w = build_headline_workload(b_total, c)
    clusters, snap, names = w.clusters, w.snap, w.names
    tol, pl_plain, pl_tol = w.tol, w.pl_plain, w.pl_tol
    profiles, replicas, prof_idx = w.profiles, w.replicas, w.prof_idx

    def make_hetero_placements(n: int, seed: int = 5) -> list:
        # n unique placements: distinct matchExpressions over the fleet's
        # tier/env label vocabulary, toleration variants, and (a slice)
        # distinct static weight lists — every one is a separate
        # compile_placement + fleet cp-slot
        from karmada_tpu.api.policy import (
            ClusterAffinity as CA, LabelSelector as LS,
            LabelSelectorRequirement as LSR,
        )

        out: list = []
        rng_h = np.random.default_rng(seed)
        tiers = [f"t{k}" for k in range(16)]
        envs = ["prod", "staging", "dev"]
        for u in range(n):
            n_t = int(rng_h.integers(2, 9))
            tier_vals = sorted(
                str(t) for t in rng_h.choice(tiers, n_t, replace=False)
            )
            env_vals = sorted(
                str(e)
                for e in rng_h.choice(envs, int(rng_h.integers(1, 3)), replace=False)
            )
            aff = CA(
                label_selector=LS(
                    match_expressions=[
                        LSR(key="tier", operator="In", values=tier_vals),
                        LSR(key="env", operator="In", values=env_vals),
                    ]
                )
            )
            tols = [tol] if u % 3 == 0 else []
            mode = u % 10
            if mode < 8:
                pl = dynamic_weight_placement(
                    cluster_affinity=aff, cluster_tolerations=tols
                )
            elif mode == 8:
                pl = duplicated_placement()
                pl.cluster_affinity = aff
                pl.cluster_tolerations = tols
            else:
                picks = rng_h.choice(c, 24, replace=False)
                pl = static_weight_placement(
                    {
                        names[int(j)]: int(w)
                        for j, w in zip(picks, rng_h.integers(1, 6, 24))
                    }
                )
                pl.cluster_affinity = aff
                pl.cluster_tolerations = tols
            out.append(pl)
        from karmada_tpu.scheduler.fleet import MAX_SLOTS

        print(
            f"# heterogeneous tier: {len(out)} unique placements "
            f"(MAX_SLOTS check: {'EXCEEDS' if len(out) > MAX_SLOTS else 'fits'} "
            f"the {MAX_SLOTS}-slot fleet table)",
            file=sys.stderr,
        )
        return out

    problems = w.problems
    if args.hetero:
        # --hetero N swaps every binding's placement for one of N unique
        # ones; everything else (replicas, profiles, prev, fresh) stays
        # the headline workload
        hetero_pls = make_hetero_placements(args.hetero)
        problems = [
            BindingProblem(
                key=p.key, placement=hetero_pls[i % len(hetero_pls)],
                replicas=p.replicas, requests=p.requests, gvk=p.gvk,
                prev=p.prev, fresh=p.fresh,
            )
            for i, p in enumerate(problems)
        ]

    # ---- engine: warm (compile + entry-buffer tune), then timed -----------
    engine = TensorScheduler(snap, chunk_size=args.chunk)
    t0 = time.perf_counter()
    engine.schedule(problems)
    print(f"# warm/compile pass: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    # adaptive settle: buffer-cap votes land a few passes after demand
    # changes and every cap change is a fresh XLA trace, so loop until a
    # pass dispatches no unseen trace signature (engine.last_pass_new_trace)
    # with a 4-pass floor covering the 2-3-vote shrink windows — the timed
    # window below must only ever run already-compiled traces
    settle_engine(
        engine, lambda i: engine.schedule(problems),
        floor=4, cap=12, label="settle pass",
    )

    import contextlib

    trace_ctx = (
        jax.profiler.trace(args.trace_dir)
        if args.trace_dir
        else contextlib.nullcontext()
    )
    times = []
    results = None
    def show(tag, wall, eng=None):
        breakdown = dict(getattr(eng or engine, "last_breakdown", {}))
        parts = " ".join(
            f"{k}={v:.1f}" if k == "fetch_mb"
            else f"{k}={int(v)}" if k in ("changed_rows", "delta_rows")
            else f"{k}={v * 1e3:.0f}ms"
            for k, v in breakdown.items()
        )
        print(f"# {tag}: {wall:.3f}s  [{parts}]", file=sys.stderr)

    breakdown = {}
    with trace_ctx:
        for rep in range(args.repeats):
            t0 = time.perf_counter()
            results = engine.schedule(problems)
            t1 = time.perf_counter()
            times.append(t1 - t0)
            breakdown = dict(getattr(engine, "last_breakdown", {}))
            show(f"pass {rep}", t1 - t0)
    p50 = float(np.median(times))

    # ---- churn tier: live availability drift between passes ---------------
    # The steady storm re-divides everything on device but ships ~no bytes
    # home (placements unchanged -> delta fetch). A real descheduler storm
    # sees capacities move, so time passes where EVERY cluster's allocations
    # drifted: the snapshot swaps in place (update_snapshot), masks and
    # estimator tables rebuild, and every row's result re-ships.
    n_churn_timed = max(4, args.repeats)
    drift_snaps = []
    rng_c = np.random.default_rng(99)
    for _ in range(8 + n_churn_timed):
        for cl in clusters:
            rs = cl.status.resource_summary
            for dim, q in list(rs.allocated.items()):
                alloc = rs.allocatable.get(dim, 0)
                rs.allocated[dim] = int(
                    min(max(0, q + int(rng_c.integers(-3, 4)) * max(1, alloc // 200)), alloc)
                )
        drift_snaps.append(ClusterSnapshot(clusters))
    # adaptive churn warm: caps re-tier under the drift load and each
    # distinct cap is one XLA trace — warm until a drift pass dispatches
    # no unseen trace (min 2 passes: onset re-tiers the caps, the next
    # compiles whichever of the delta/speculative traces engages)
    def churn_warm_pass(i):
        assert engine.update_snapshot(drift_snaps[i])
        engine.schedule(problems)

    n_warm = settle_engine(
        engine, churn_warm_pass, floor=2, cap=8, label="churn warm pass",
    )
    churn_times = []
    for rep, snap_r in enumerate(drift_snaps[n_warm:n_warm + n_churn_timed]):
        t0 = time.perf_counter()
        swapped = engine.update_snapshot(snap_r)
        assert swapped
        engine.schedule(problems)
        t1 = time.perf_counter()
        churn_times.append(t1 - t0)
        show(f"churn pass {rep}", t1 - t0)
    churn_p50 = float(np.median(churn_times))
    churn_max = float(np.max(churn_times))
    print(
        f"# churn (full availability drift): p50 {churn_p50:.3f}s "
        f"max {churn_max:.3f}s over {len(churn_times)} passes",
        file=sys.stderr,
    )

    tier_status: dict = {}

    def _subtier(name, fn, default):
        """A sub-tier's failure does not lose the tiers already measured:
        it is reported, the record still prints with the tier's metric an
        explicit null and its ``tiers`` status the error (never a
        fast-looking 0.0) — and main() then exits non-zero on any status
        that is not "ok"."""
        try:
            out = fn()
            # a tier may have flagged its own soft failure (e.g. placement
            # divergence) — never clobber it with "ok"
            tier_status.setdefault(name, "ok")
            return out
        except Exception as e:  # noqa: BLE001 — recorded; main() exits 1
            print(f"# WARNING: {name} sub-tier FAILED: {e!r}", file=sys.stderr)
            tier_status[name] = f"error: {e!r}"
            return default

    # ---- heterogeneous-placement sub-tier (default run only) --------------
    # 3.5k UNIQUE placements across the same bindings: stresses selector
    # compilation, mask interning, and the fleet cp-table at scale (SURVEY
    # section 7 label-selector warning). A dedicated full run is available
    # via --hetero N.
    def _hetero_tier() -> float:
        h_pls = make_hetero_placements(3500)
        h_problems = [
            BindingProblem(
                key=p.key, placement=h_pls[i % len(h_pls)],
                replicas=p.replicas, requests=p.requests, gvk=p.gvk,
                prev=p.prev, fresh=p.fresh,
            )
            for i, p in enumerate(problems)
        ]
        h_engine = TensorScheduler(snap, chunk_size=args.chunk)
        t0 = time.perf_counter()
        h_engine.schedule(h_problems)
        print(f"# hetero warm pass: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
        # adaptive stabilize: cap shrink fires after up to 3 votes and
        # every cap change is a fresh trace — it must land here, not in a
        # timed pass
        settle_engine(
            h_engine, lambda i: h_engine.schedule(h_problems),
            floor=3, cap=8, label="hetero settle",
        )
        h_times = []
        for rep in range(3):
            t0 = time.perf_counter()
            h_res = h_engine.schedule(h_problems)
            h_times.append(time.perf_counter() - t0)
        hetero_p50 = float(np.median(h_times))
        n_h = sum(1 for r_ in h_res if r_.success)
        # spot-verify placements against the pure-Python oracle
        h_idx = list(range(0, b_total, max(1, b_total // 256)))[:256]
        h_ok, h_bad = _verify_rows(snap, h_problems, h_res, h_engine, h_idx)
        print(
            f"# hetero tier (3500 unique placements): p50 "
            f"{hetero_p50:.3f}s, {n_h}/{b_total} scheduled, oracle "
            f"{h_ok}/{len(h_idx)} identical",
            file=sys.stderr,
        )
        if h_bad:
            print(f"# WARNING: hetero mismatches: {h_bad}", file=sys.stderr)
            tier_status["hetero-3500"] = f"error: {h_bad} mismatches"
        del h_engine, h_res, h_problems
        gc.collect()
        return hetero_p50

    hetero_p50 = None
    ran_hetero = False
    if not args.hetero and not args.no_verify:
        ran_hetero = True
        hetero_p50 = _subtier("hetero-3500", _hetero_tier, None)

    # ---- >MAX_SLOTS-unique sub-tier (the old 8192-slot cliff) -------------
    # 9000 unique placements over 50k bindings: the slot cap now scales
    # with the HBM budget and retires unreferenced slots, so this tier
    # must keep ONE fleet table across passes (no rebuild-per-call) and
    # post a steady p50.
    def _hetero9k_tier() -> tuple:
        from karmada_tpu.scheduler.fleet import MAX_SLOTS as _MS

        k_pls = make_hetero_placements(9000)
        b_k = min(b_total, 50_000)
        k_problems = [
            BindingProblem(
                key=f"k{i}", placement=k_pls[i % len(k_pls)],
                replicas=int(replicas[i]), requests=profiles[prof_idx[i]],
                gvk="apps/v1/Deployment",
            )
            for i in range(b_k)
        ]
        k_engine = TensorScheduler(snap, chunk_size=args.chunk)
        t0 = time.perf_counter()
        k_engine.schedule(k_problems)
        print(f"# hetero-9000 warm pass: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        table_obj = k_engine._fleet
        settle_engine(
            k_engine, lambda i: k_engine.schedule(k_problems),
            floor=4, cap=8, label="hetero-9000 settle",
        )
        k_times = []
        for rep in range(2):
            t0 = time.perf_counter()
            k_res = k_engine.schedule(k_problems)
            k_times.append(time.perf_counter() - t0)
        hetero9k_p50 = float(np.median(k_times))
        survived = k_engine._fleet is table_obj
        k_idx = list(range(0, b_k, max(1, b_k // 128)))[:128]
        k_ok, k_bad = _verify_rows(snap, k_problems, k_res, k_engine, k_idx)
        print(
            f"# hetero-9000 tier (> {_MS} uniques, {b_k // 1000}k bindings): "
            f"p50 {hetero9k_p50:.3f}s, table survived={survived}, oracle "
            f"{k_ok}/{len(k_idx)} identical",
            file=sys.stderr,
        )
        if k_bad or not survived:
            print(
                f"# WARNING: hetero-9000 mismatches={k_bad} "
                f"survived={survived}",
                file=sys.stderr,
            )
            tier_status["hetero-9000"] = (
                f"error: mismatches={k_bad} survived={survived}"
            )

        # ---- slot-eviction churn: rotate ~10% NEW unique placements per
        # pass (VERDICT r4 next #7). Each rotation retires ~900 now-
        # unreferenced cp slots and appends ~900 never-seen selectors while
        # the other 90% of rows keep their placements — the case that
        # stresses eviction + append + delta-base survival together. Keys
        # stay stable so fleet rows persist; only the rotated rows' slots
        # and masks change. Runs in its OWN failure domain (the nested
        # _subtier) so a transient churn failure cannot discard the steady
        # measurement above.
        def rotate(pass_no: int) -> list:
            fresh_pls = make_hetero_placements(900, seed=10_000 + pass_no)
            lane = pass_no % 10
            return [
                BindingProblem(
                    key=p.key, placement=fresh_pls[i % len(fresh_pls)],
                    replicas=p.replicas, requests=p.requests, gvk=p.gvk,
                )
                if i % 10 == lane
                else p
                for i, p in enumerate(k_problems)
            ]

        def _rotation_churn() -> float:
            nonlocal k_problems, k_res
            def rotation_warm_pass(i):
                nonlocal k_problems
                k_problems = rotate(i)
                k_engine.schedule(k_problems)

            rot = settle_engine(
                k_engine, rotation_warm_pass, floor=2, cap=5,
                label="hetero-9000 rotation warm",
            )
            kc_times = []
            for i in range(3):
                k_problems = rotate(rot + i)
                t0 = time.perf_counter()
                k_res = k_engine.schedule(k_problems)
                kc_times.append(time.perf_counter() - t0)
                print(
                    f"# hetero-9000 rotation pass: {kc_times[-1]:.3f}s",
                    file=sys.stderr,
                )
            churn_p = float(np.median(kc_times))
            survived_churn = k_engine._fleet is table_obj
            tbl = k_engine._fleet
            print(
                f"# hetero-9000 churn diag: slots={len(tbl._cp_pl)} "
                f"max={tbl._max_slots()} gvk={len(tbl._gvk_list)} "
                f"profiles={len(tbl._profiles)} rows={tbl.n_rows}",
                file=sys.stderr,
            )
            kc_ok, kc_bad = _verify_rows(
                snap, k_problems, k_res, k_engine, k_idx
            )
            print(
                f"# hetero-9000 slot-eviction churn (10% unique rotation/"
                f"pass): p50 {churn_p:.3f}s, table survived="
                f"{survived_churn}, oracle {kc_ok}/{len(k_idx)} identical",
                file=sys.stderr,
            )
            if kc_bad or not survived_churn:
                print(
                    f"# WARNING: hetero-9000 churn mismatches={kc_bad} "
                    f"survived={survived_churn}",
                    file=sys.stderr,
                )
                tier_status["hetero-9000-churn"] = (
                    f"error: mismatches={kc_bad} survived={survived_churn}"
                )
            return churn_p

        hetero9k_churn_local = _subtier(
            "hetero-9000-churn", _rotation_churn, None
        )
        del k_engine, k_res, k_problems
        gc.collect()
        return hetero9k_p50, hetero9k_churn_local

    hetero9k_p50 = hetero9k_churn = None
    ran_hetero9k = False
    if not args.hetero and not args.no_verify:
        ran_hetero9k = True
        h9 = _subtier("hetero-9000", _hetero9k_tier, None)
        if h9 is not None:
            hetero9k_p50, hetero9k_churn = h9

    # ---- live-estimator sub-tier (VERDICT r4 next #5) ---------------------
    # The batched-wire + generation-gated-refresh tier, shared with
    # ``--estimator-only`` (run_estimator_tier): full-refresh storm p50
    # over one batch RPC per server, no-movement refresh p50 over
    # GetGenerations pings, and the unary-fallback parity run.
    def _estimator_tier() -> dict:
        return run_estimator_tier(args, tier_status)

    est512 = None
    ran_est512 = False
    if not args.hetero and not args.no_verify and b_total == 100_000:
        ran_est512 = True
        est512 = _subtier("estimator-512", _estimator_tier, None)

    # ---- 1M x 5k scale tier (first-class, VERDICT r3 item 9) --------------
    # Ten times the headline bindings through the same engine: steady +
    # full-drift churn p50s with sampled oracle verification. Its dense
    # resident (1,048,576 x 5,000 = 5.2 GB) is the largest the 6 GiB
    # bound admits at this cluster count.
    def _scale1m_tier() -> tuple:
        b_m = 1_000_000
        rng_m = np.random.default_rng(1234)
        reps_m = rng_m.integers(1, 100, b_m)
        prof_m = rng_m.integers(0, 8, b_m)
        tol_m = rng_m.random(b_m) < 0.30
        t0 = time.perf_counter()
        m_problems = [
            BindingProblem(
                key=f"m{i}",
                placement=pl_tol if tol_m[i] else pl_plain,
                replicas=int(reps_m[i]),
                requests=profiles[prof_m[i]],
                gvk="apps/v1/Deployment",
            )
            for i in range(b_m)
        ]
        print(f"# 1M problem build: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        m_engine = TensorScheduler(snap, chunk_size=args.chunk)
        t0 = time.perf_counter()
        m_engine.schedule(m_problems)
        print(f"# 1M warm pass: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        # adaptive settle (same contract as the headline tier: no timed
        # pass may dispatch an unseen trace)
        settle_engine(
            m_engine, lambda i: m_engine.schedule(m_problems),
            floor=4, cap=12, label="1M settle pass",
        )
        m_times = []
        for rep in range(3):
            t0 = time.perf_counter()
            m_res = m_engine.schedule(m_problems)
            m_times.append(time.perf_counter() - t0)
            show(f"1M steady pass {rep}", m_times[-1], m_engine)
        m1_steady = float(np.median(m_times))
        # row churn: mutate a fixed fraction of rows per pass against a
        # STABLE snapshot — the regime the incremental (dirty-row) solve
        # path serves. Cost must track churn size, not plane size; the
        # per-pass breakdown must prove the sub dispatch packed exactly
        # the dirty set, and placements must stay bit-identical to the
        # full-solve oracle (verified below, once the resident memory is
        # free for a second 1M engine).
        def _digest_rows(res, n):
            out = np.empty(n, np.uint64)
            for i in range(n):
                r = res[i]
                blob = (
                    repr(sorted(r.clusters.items()))
                    if r.success else "!" + str(r.error)
                )
                out[i] = int.from_bytes(
                    hashlib.blake2b(blob.encode(), digest_size=8).digest(),
                    "little",
                )
            return out

        rng_c = np.random.default_rng(20_777)
        m_churn_tiers: dict = {}
        m_churn_states: list = []  # (label, problems, digests) for oracle

        def m_row_churn(frac):
            dirty_n = int(b_m * frac)

            def mutate():
                for i in rng_c.choice(b_m, dirty_n, replace=False):
                    p = m_problems[i]
                    m_problems[i] = BindingProblem(
                        key=p.key, placement=p.placement,
                        replicas=(p.replicas % 99) + 1,
                        requests=p.requests, gvk=p.gvk,
                    )

            def warm_pass(_i):
                mutate()
                m_engine.schedule(m_problems)

            settle_engine(
                m_engine, warm_pass, floor=2, cap=8,
                label=f"1M row-churn {frac:.1%} settle",
            )
            times = []
            res = None
            for rep in range(3):
                mutate()
                t0 = time.perf_counter()
                res = m_engine.schedule(m_problems)
                times.append(time.perf_counter() - t0)
                bd = m_engine._fleet.last_breakdown
                dirty = int(bd.get("dirty_rows", -1))
                packed = int(bd.get("rows_packed", -1))
                show(
                    f"1M row-churn {frac:.1%} pass {rep}", times[-1], m_engine
                )
                assert dirty == dirty_n and packed == dirty_n, (
                    f"delta pass dispatched {dirty} dirty / {packed} packed "
                    f"rows for a {dirty_n}-row churn set"
                )
            m_churn_states.append(
                (f"{frac:.1%}", list(m_problems), _digest_rows(res, b_m))
            )
            return float(np.median(times))

        for frac, t_key in (
            (0.001, "churn0p1pct"),
            (0.01, "churn1pct"),
            (0.10, "churn10pct"),
        ):
            m_churn_tiers[t_key] = m_row_churn(frac)
        print(
            "# 1M row-churn p50: " + ", ".join(
                f"{k} {v:.3f}s" for k, v in m_churn_tiers.items()
            ),
            file=sys.stderr,
        )
        # churn: adaptive full-availability-drift warm (the onset pass
        # re-tiers the caps, the next compiles the delta-wire trace those
        # caps select; loop until compile-stable) + 4 timed passes
        m_drifts = []
        for _ in range(12):
            for cl in clusters:
                rs = cl.status.resource_summary
                for dim, q in list(rs.allocated.items()):
                    alloc = rs.allocatable.get(dim, 0)
                    rs.allocated[dim] = int(min(max(
                        0, q + int(rng_m.integers(-3, 4)) * max(1, alloc // 200)
                    ), alloc))
            m_drifts.append(ClusterSnapshot(clusters))
        def m_churn_warm_pass(i):
            assert m_engine.update_snapshot(m_drifts[i])
            m_engine.schedule(m_problems)

        m_warm = settle_engine(
            m_engine, m_churn_warm_pass, floor=2, cap=8,
            label="1M churn warm pass",
        )
        m_churn_times = []
        for rep, snap_m in enumerate(m_drifts[m_warm:m_warm + 4]):
            t0 = time.perf_counter()
            swapped = m_engine.update_snapshot(snap_m)
            assert swapped
            m_res = m_engine.schedule(m_problems)
            m_churn_times.append(time.perf_counter() - t0)
            show(f"1M churn pass {rep}", m_churn_times[-1], m_engine)
        m1_churn = float(np.median(m_churn_times))
        m1_churn_max = float(np.max(m_churn_times))
        m_idx = list(range(0, b_m, max(1, b_m // 128)))[:128]
        m_ok, m_bad = _verify_rows(
            ClusterSnapshot(clusters), m_problems, m_res, m_engine, m_idx
        )
        print(
            f"# 1M x 5k tier: steady p50 {m1_steady:.3f}s, churn p50 "
            f"{m1_churn:.3f}s max {m1_churn_max:.3f}s, oracle "
            f"{m_ok}/{len(m_idx)} identical",
            file=sys.stderr,
        )
        if m_bad:
            print(f"# WARNING: 1M mismatches: {m_bad}", file=sys.stderr)
            tier_status["scale-1M"] = f"error: {m_bad} mismatches"
        del m_engine, m_res
        gc.collect()
        # bit-identity oracle for the row-churn tiers: a fresh engine (no
        # armed batch: it walks) full-solves each tier's final problem
        # state; every row's placement must hash identical to what the
        # delta passes returned.
        for label, o_probs, digests in m_churn_states:
            o_engine = TensorScheduler(snap, chunk_size=args.chunk)
            t0 = time.perf_counter()
            o_res = o_engine.schedule(o_probs)
            o_dig = _digest_rows(o_res, b_m)
            bad = int(np.count_nonzero(o_dig != digests))
            print(
                f"# 1M row-churn {label} oracle: full solve "
                f"{time.perf_counter() - t0:.1f}s, {bad} rows diverge",
                file=sys.stderr,
            )
            assert bad == 0, (
                f"row-churn {label}: {bad} placements diverge from the "
                "full-solve oracle"
            )
            del o_engine, o_res
        del m_problems, m_churn_states
        gc.collect()
        return {
            "steady": m1_steady,
            "churn": m1_churn,
            "churn_max": m1_churn_max,
            **m_churn_tiers,
        }

    m1 = None
    ran_1m = False
    if args.scale or (
        not args.hetero and not args.no_verify and b_total == 100_000
    ):
        ran_1m = True
        m1 = _subtier("scale-1M", _scale1m_tier, None)

    # ---- whole-plane storm tier (VERDICT r4 next #6) ----------------------
    # The FULL spine at 100k bindings: detector -> scheduler -> binding ->
    # works through the store, driven by a rebalancer storm (every binding
    # re-reconciles each wave). The engine rides the device; the recorded
    # number is HOST-path throughput — store applies, admission, watch
    # fan-out, Work rendering. Round 2 recorded ~2.3k bindings/s at
    # 2000x50; the target is >=2x that at 50x the binding count.
    def _whole_plane_tier() -> float:
        from karmada_tpu import cli as _cli
        from karmada_tpu.api import (
            PropagationPolicy,
            PropagationSpec,
            ResourceSelector,
        )
        from karmada_tpu.api.core import ObjectMeta
        from karmada_tpu.controllers.extras import (
            ObjectReferenceSelector,
            WorkloadRebalancer,
            WorkloadRebalancerSpec,
        )
        from karmada_tpu.utils.builders import new_cluster, new_deployment

        n_wp, c_wp = 100_000, 250
        clock = [10_000.0]
        cp = _cli.cmd_init(clock=lambda: clock[0])
        for i in range(c_wp):
            cp.join_cluster(
                new_cluster(f"wp{i}", cpu="2000", memory="4000Gi")
            )
        cp.settle()
        t0 = time.perf_counter()
        cp.store.apply(PropagationPolicy(
            meta=ObjectMeta(name="wp-policy", namespace="default"),
            spec=PropagationSpec(
                resource_selectors=[
                    ResourceSelector(api_version="apps/v1", kind="Deployment")
                ],
                placement=dynamic_weight_placement(),
            ),
        ))
        for i in range(n_wp):
            cp.store.apply(
                new_deployment(f"wpa{i}", replicas=(i % 8) + 1)
            )
        print(f"# whole-plane build: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        t0 = time.perf_counter()
        cp.settle()
        cold = time.perf_counter() - t0
        n_works = len(cp.store.list("Work"))
        print(
            f"# whole-plane cold wave: {cold:.1f}s = {n_wp / cold:.0f} "
            f"bindings/s ({n_works} works rendered)",
            file=sys.stderr,
        )
        rb0 = cp.store.get("ResourceBinding", "default/wpa0-deployment")
        assert rb0 is not None and rb0.spec.clusters, "spine never divided"

        def storm_wave(tag: str) -> float:
            clock[0] += 60
            cp.store.apply(WorkloadRebalancer(
                meta=ObjectMeta(name=f"wp-storm-{tag}"),
                spec=WorkloadRebalancerSpec(workloads=[
                    ObjectReferenceSelector(kind="Deployment", name=f"wpa{i}")
                    for i in range(n_wp)
                ]),
            ))
            t0 = time.perf_counter()
            cp.settle()
            return time.perf_counter() - t0

        # adaptive warm: the first storms after the cold build still pay
        # heap/queue settlement (measured 48 s -> 33.8 s -> 11.3 s wave
        # sequence); warm until the wave cost FLATTENS (<30% improvement)
        # so the timed window records steady-state throughput
        prev_w = None
        for wi in range(4):
            w = storm_wave(f"warm{wi}")
            print(
                f"# whole-plane warm{wi} wave: {w:.1f}s = "
                f"{n_wp / w:.0f} bindings/s",
                file=sys.stderr,
            )
            if prev_w is not None and w > prev_w * 0.7:
                break
            prev_w = w
        waves = []
        for k in range(3):
            waves.append(storm_wave(f"t{k}"))
            print(
                f"# whole-plane wave {k}: {waves[-1]:.1f}s = "
                f"{n_wp / waves[-1]:.0f} bindings/s",
                file=sys.stderr,
            )
        rate = n_wp / float(np.median(waves))
        # convergence: every binding observed at its latest generation with
        # a full assignment (sampled)
        for i in range(0, n_wp, max(1, n_wp // 64)):
            rb = cp.store.get("ResourceBinding", f"default/wpa{i}-deployment")
            assert rb.status.scheduler_observed_generation == rb.meta.generation
            assert sum(tc.replicas for tc in rb.spec.clusters) == (i % 8) + 1
        print(
            f"# whole-plane storm: {rate:.0f} bindings/s "
            f"(round-2 referent 2300/s)",
            file=sys.stderr,
        )
        del cp
        gc.collect()
        return rate

    whole_plane = None
    ran_wp = False
    if not args.hetero and not args.no_verify and b_total == 100_000:
        ran_wp = True
        whole_plane = _subtier("whole-plane", _whole_plane_tier, None)

    # restore the measured-snapshot results for verification below (the
    # original ``snap`` holds copies of the pre-drift capacities)
    swapped = engine.update_snapshot(snap)
    assert swapped
    results = engine.schedule(problems)
    n_sched = sum(1 for r in results if r.success)
    print(
        f"# scheduled {n_sched}/{b_total} bindings via the engine",
        file=sys.stderr,
    )

    metric = f"p50_engine_schedule_{b_total // 1000}kx{c}_dynamic_weight"
    if args.hetero:
        metric = (
            f"p50_engine_hetero{args.hetero}_"
            f"{b_total // 1000}kx{c}"
        )
    def _r(v):
        return round(v, 4) if v is not None else None

    out = {
        "metric": metric,
        "value": round(p50, 4),
        "unit": "s",
        "churn_p50": round(churn_p50, 4),
        "churn_max": round(churn_max, 4),
    }
    if ran_hetero:
        out["hetero3500_p50"] = _r(hetero_p50)
    if ran_hetero9k:
        out["hetero9000_p50"] = _r(hetero9k_p50)
        out["hetero9k_churn_p50"] = _r(hetero9k_churn)
    if ran_est512:
        for key, val in (est512 or {}).items():
            if key.startswith("estimator512_"):
                out[key] = val
    if ran_wp:
        out["whole_plane_bindings_s"] = (
            round(whole_plane, 1) if whole_plane is not None else None
        )
    if ran_1m:
        m1d = m1 or {}
        out["scale1m_steady_p50"] = _r(m1d.get("steady"))
        out["scale1m_churn_p50"] = _r(m1d.get("churn"))
        out["scale1m_churn_max"] = _r(m1d.get("churn_max"))
        out["scale1m_churn0p1pct_p50"] = _r(m1d.get("churn0p1pct"))
        out["scale1m_churn1pct_p50"] = _r(m1d.get("churn1pct"))
        out["scale1m_churn10pct_p50"] = _r(m1d.get("churn10pct"))
    if tier_status:
        out["tiers"] = tier_status
    if args.no_verify:
        out["vs_baseline"] = 0.0
        return out

    # ---- full-set verification vs the vectorized-numpy host divider ------
    # (which is itself oracle-verified by tests/test_divider_np.py); also
    # times the conservative host baseline on identical pre-packed inputs
    host_eng = TensorScheduler(snap)
    chunk = 8192
    t_np = 0.0
    np_ok = np_bad = 0
    cap_np = snap.available_cap
    for start in range(0, b_total, chunk):
        sub = problems[start : start + chunk]
        feasible, strategy, reps, static_w, requests, prev, fr = (
            _oracle_inputs(snap, sub, host_eng)
        )
        uniq, inv = np.unique(requests, axis=0, return_inverse=True)
        t0 = time.perf_counter()
        per_prof = _general_avail_np(cap_np, uniq)
        avail = per_prof[inv]
        avail = np.minimum(
            np.where(avail == 2**31 - 1, reps[:, None], avail), 2**31 - 1
        ).astype(np.int32)
        got, unsched = assign_batch_np(
            strategy, reps, feasible, static_w, avail, prev, fr
        )
        t_np += time.perf_counter() - t0
        for k in range(len(sub)):
            res = results[start + k]
            if unsched[k] or not feasible[k].any():
                good = not res.success
            else:
                want = {
                    names[j]: int(got[k, j]) for j in np.flatnonzero(got[k])
                }
                good = res.success and dict(res.clusters) == want
            np_ok, np_bad = np_ok + good, np_bad + (not good)
    print(
        f"# numpy-host check: {np_ok}/{np_ok + np_bad} identical; "
        f"numpy divider wall {t_np:.2f}s for {b_total}",
        file=sys.stderr,
    )

    # ---- sampled verification vs the pure-Python oracle -------------------
    sample_idx = list(
        range(0, b_total, max(1, b_total // max(1, args.sample)))
    )[: args.sample]
    t0 = time.perf_counter()
    ok, bad = _verify_rows(snap, problems, results, host_eng, sample_idx)
    t_oracle = time.perf_counter() - t0
    per_binding = t_oracle / max(1, len(sample_idx))
    oracle_full = per_binding * b_total
    print(
        f"# oracle check: {ok}/{len(sample_idx)} identical across all "
        f"chunks; {per_binding * 1e3:.2f} ms/binding -> {oracle_full:.0f}s "
        f"extrapolated",
        file=sys.stderr,
    )

    # ---- mixed-strategy verification (all strategies x cohorts) -----------
    mix_n = args.mix_sample
    rng = np.random.default_rng(7)
    pl_static = static_weight_placement(
        {names[j]: int(w) for j, w in zip(range(0, c, max(1, c // 32)),
                                          rng.integers(1, 6, 32))}
    )
    mix_pls = [pl_plain, duplicated_placement(), pl_static,
               aggregated_placement()]
    mix = []
    for i in range(mix_n):
        reps_i = int(rng.integers(0, 100))
        # cohort and strategy indices are decorrelated so all 16
        # strategy x cohort combinations are exercised
        cohort = (i // 4) % 4  # steady-up / steady-down / fresh / no-prev
        if cohort == 0:  # scale-up: prev sum < replicas
            prev = {names[int(j)]: 1 for j in rng.choice(c, min(3, max(1, reps_i)), replace=False)} if reps_i > 3 else {}
            fr = False
        elif cohort == 1:  # scale-down: prev sum > replicas
            prev = {names[int(j)]: int(reps_i) + 2 for j in rng.choice(c, 2, replace=False)}
            fr = False
        elif cohort == 2:
            prev = {names[int(j)]: 2 for j in rng.choice(c, 2, replace=False)}
            fr = True
        else:
            prev, fr = {}, False
        mix.append(
            BindingProblem(
                key=f"m{i}", placement=mix_pls[i % 4], replicas=reps_i,
                requests=profiles[int(rng.integers(0, 8))],
                gvk="apps/v1/Deployment", prev=prev, fresh=fr,
            )
        )
    mix_results = engine.schedule(mix)
    mok, mbad = _verify_rows(snap, mix, mix_results, host_eng, list(range(mix_n)))
    print(
        f"# mixed-strategy oracle check: {mok}/{mix_n} identical "
        f"(duplicated/static/dynamic/aggregated x steady/fresh/scale)",
        file=sys.stderr,
    )

    mismatches = np_bad + bad + mbad
    if mismatches:
        print(f"# WARNING: {mismatches} placement mismatches", file=sys.stderr)
    out.update(
        {
            "vs_baseline": round(t_np / p50, 1),
            "vs_numpy_host": round(t_np / p50, 1),
            "vs_python_oracle": round(oracle_full / p50, 1),
            "verified_rows": np_ok + ok + mok,
            "verified_mismatches": mismatches,
        }
    )
    # native calibration (baselines/calibrate.py): a single-thread C++ -O2
    # re-execution of the reference's per-binding division loop (incl. the
    # per-binding calAvailableReplicas recompute) on THIS exact workload —
    # the defensible stand-in for "the in-tree Go divider" (no Go in image)
    import os

    cal_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "baselines", "CALIBRATION.json",
    )
    if os.path.exists(cal_path):
        with open(cal_path) as f:
            cal = json.load(f)
        if (
            cal.get("bindings") == b_total
            and cal.get("clusters") == c
            and cal.get("verified_rows", 0) >= b_total
            and cal.get("verified_mismatches", 1) == 0
        ):
            out["vs_cpp_native"] = round(cal["cpp_seconds"] / p50, 1)
            out["cpp_native_seconds"] = cal["cpp_seconds"]
            print(
                f"# native C++ divider baseline (calibrated): "
                f"{cal['cpp_seconds']:.2f}s -> {out['vs_cpp_native']}x",
                file=sys.stderr,
            )
    return out


# --------------------------------------------------------------------------
# --cold-start: plane-restart first-wave tier (persistent cache + manifest)
# --------------------------------------------------------------------------


def run_cold_child(args) -> dict:
    """One process of the cold-start tier: build the headline workload,
    time the FIRST engine wave (the wave a plane restart / HA failover
    serves); seed additionally settles (filling the manifest), restore
    settles and times the steady wave all ratios are quoted against.

    The parent's env decides the mode's cache/manifest state:

    - ``seed``    — fresh cache dir + manifest: its first wave IS the
      no-cache baseline, and it leaves both populated for ``restore``.
    - ``cold``    — cache and manifest disabled: the pre-cache control
      (what every restart paid before this subsystem existed).
    - ``restore`` — manifest prewarm (scheduler.prewarm.warmup, off the
      timed window) + the seed's persistent cache: the first wave must
      dispatch only already-compiled traces (``new_trace=False``).
    """
    from karmada_tpu.scheduler import TensorScheduler

    mode = args.cold_child
    out: dict = {"mode": mode}
    if mode == "restore":
        from karmada_tpu.scheduler.prewarm import warmup

        stats = warmup()
        out["prewarm"] = stats
        print(
            f"# prewarm: {stats['compiled']}/{stats['specs']} traces in "
            f"{stats['seconds']:.1f}s",
            file=sys.stderr,
        )
    w = build_headline_workload(args.bindings, args.clusters)
    engine = TensorScheduler(w.snap, chunk_size=args.chunk)
    t0 = time.perf_counter()
    engine.schedule(w.problems)
    first = time.perf_counter() - t0
    out["first_wave_s"] = round(first, 3)
    out["new_trace_first_pass"] = bool(engine.last_pass_new_trace)
    print(
        f"# {mode} first wave: {first:.1f}s "
        f"new_trace={engine.last_pass_new_trace}",
        file=sys.stderr,
    )
    # the cold child exists only for its first wave (the pre-cache
    # baseline): no manifest to record into and the parent quotes every
    # ratio against the RESTORE child's steady wave, so settling it
    # would burn minutes of compile for numbers nobody reads
    if mode == "cold":
        return out
    # settle (seed mode records the late cap-tune traces into the
    # manifest here — the restore child's prewarm replays ALL of them)
    settle_engine(
        engine, lambda i: engine.schedule(w.problems),
        floor=2, cap=12, label=f"{mode} settle",
    )
    if mode == "restore":
        from karmada_tpu.scheduler import BindingProblem

        # the steady wave (same problems, zero changed rows)
        times = []
        for _ in range(max(2, args.repeats)):
            t0 = time.perf_counter()
            engine.schedule(w.problems)
            times.append(time.perf_counter() - t0)
        out["steady_wave_s"] = round(float(np.median(times)), 3)
        # the warm WHOLE-PLANE wave the restart ratio is quoted against:
        # every binding changed (replicas bumped) in an already-warm
        # process, so the wave re-packs, re-uploads, and fetches ALL
        # rows — exactly the work a restart's first wave does minus the
        # restore overhead. The unchanged steady wave above fetches zero
        # rows; quoting the restart against it holds the first wave to a
        # bar no live all-change wave meets.
        bumped = [
            BindingProblem(
                key=p.key, placement=p.placement, replicas=p.replicas + 1,
                requests=p.requests, gvk=p.gvk, prev=p.prev, fresh=p.fresh,
            )
            for p in w.problems
        ]
        t0 = time.perf_counter()
        engine.schedule(bumped)
        out["warm_all_change_wave_s"] = round(time.perf_counter() - t0, 3)
        print(
            f"# warm all-change wave: {out['warm_all_change_wave_s']:.1f}s",
            file=sys.stderr,
        )
    return out


def run_cold_start(args) -> dict:
    """Parent of the cold-start tier: three fresh processes over the same
    headline workload, sharing one cache+manifest directory — the fixed
    ``coldstart`` subdirectory of the resolved compile cache
    (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache/<platform
    set>``), emptied here so the seed child starts cold. The parent itself
    never imports jax — one process owns the chip at a time, so each child
    must own it in turn."""
    import os
    import shutil
    import subprocess

    from karmada_tpu.utils.compilecache import resolve_cache_dir

    resolved = resolve_cache_dir()
    if not resolved:
        raise SystemExit(
            "bench.py --cold-start: the compile cache is disabled "
            "(JAX_COMPILATION_CACHE_DIR is empty); the tier measures it"
        )
    cache_root = os.path.join(resolved, "coldstart")
    shutil.rmtree(cache_root, ignore_errors=True)
    manifest = os.path.join(cache_root, "trace_manifest.json")

    def child(mode: str) -> dict:
        env = dict(os.environ)
        if mode == "cold":
            env["JAX_COMPILATION_CACHE_DIR"] = ""
            env["KARMADA_TPU_TRACE_MANIFEST"] = ""
        else:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_root
            env["KARMADA_TPU_TRACE_MANIFEST"] = manifest
            # restart-resilient plane config: persist EVERY trace, not
            # just slow ones — the utility kernels (row scatter, meta
            # gather) each compile under the default 1 s threshold, but a
            # restart re-pays all of them at once on the first wave
            env["KARMADA_TPU_CACHE_MIN_COMPILE_SECS"] = "0"
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--cold-child", mode,
            "--bindings", str(args.bindings),
            "--clusters", str(args.clusters),
            "--chunk", str(args.chunk),
            "--repeats", str(args.repeats),
        ]
        if args.cpu:
            cmd.append("--cpu")
        print(f"# cold-start: spawning {mode} child", file=sys.stderr)
        proc = subprocess.run(
            cmd, env=env, stdout=subprocess.PIPE, text=True
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"cold-start {mode} child exited rc={proc.returncode}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])

    assert "jax" not in sys.modules, "the cold-start parent must stay off jax"
    seed = child("seed")
    cold = child("cold")
    restore = child("restore")
    steady = restore["steady_wave_s"]
    warm = restore["warm_all_change_wave_s"]
    return {
        "metric": (
            f"cold_start_first_wave_{args.bindings // 1000}k"
            f"x{args.clusters}"
        ),
        "value": restore["first_wave_s"],
        "unit": "s",
        # the headline ratio: how much faster a restored restart's first
        # wave is than the pre-cache cold wave it replaces
        "vs_baseline": round(cold["first_wave_s"] / restore["first_wave_s"], 2),
        "seed_first_wave_s": seed["first_wave_s"],
        "cold_first_wave_s": cold["first_wave_s"],
        "restore_first_wave_s": restore["first_wave_s"],
        "steady_wave_s": steady,
        "warm_all_change_wave_s": warm,
        "cold_over_steady": round(cold["first_wave_s"] / steady, 2),
        "restore_over_steady": round(restore["first_wave_s"] / steady, 2),
        # the acceptance ratios: a restart's first wave re-packs,
        # re-uploads, and fetches EVERY row, so the fair warm bar is the
        # all-change wave (which does the same work warm), not the
        # unchanged steady wave (which fetches zero rows)
        "cold_over_warm": round(cold["first_wave_s"] / warm, 2),
        "restore_over_warm": round(restore["first_wave_s"] / warm, 2),
        "restore_new_trace_first_pass": restore["new_trace_first_pass"],
        "prewarm": restore.get("prewarm"),
        # the parent is jax-free: the device is what the children found
        **{k: restore[k] for k in DEVICE_FIELDS},
    }


# --------------------------------------------------------------------------
# --observability: wave-trace attribution over a whole-plane storm
# --------------------------------------------------------------------------


def run_chaos(args) -> dict:
    """ISSUE 7 acceptance tier: the failure half of the plane at storm
    scale. A 20k x 512 whole-plane fleet under an ordered-failover policy
    (ClusterAffinities [primary, fallback]) with availability served by
    LIVE gRPC estimator servers; a seeded chaos wave flips K member
    clusters NotReady (cluster.health fault point -> the real
    condition->taint->NoExecute-eviction machinery) and SIGSTOP-partitions
    one estimator server mid-wave. Records time-to-stable-placement, the
    displaced-binding count against the batched-solve count (failover must
    reschedule in O(chunks) solves, not O(bindings)), the estimator
    breaker's open->half-open->closed recovery, and verifies the final
    placements bit-for-bit against the numpy per-binding oracle
    (refimpl.failover_np.replay_failover) consuming the same fault-event
    log."""
    import signal

    from karmada_tpu import cli as _cli
    from karmada_tpu.api import (
        PropagationPolicy,
        PropagationSpec,
        ResourceSelector,
    )
    from karmada_tpu.api.core import ObjectMeta
    from karmada_tpu.api.policy import ClusterAffinityTerm, LabelSelector
    from karmada_tpu.controllers.extras import (
        ObjectReferenceSelector,
        WorkloadRebalancer,
        WorkloadRebalancerSpec,
    )
    from karmada_tpu.estimator.fleet import spawn_estimator_fleet
    from karmada_tpu.refimpl.failover_np import replay_failover
    from karmada_tpu.scheduler import ClusterSnapshot
    from karmada_tpu.scheduler.snapshot import compile_placement
    from karmada_tpu.utils import backoff, faultinject
    from karmada_tpu.utils.builders import (
        dynamic_weight_placement,
        new_cluster,
        new_deployment,
    )
    from karmada_tpu.utils.features import FAILOVER, feature_gate
    from karmada_tpu.utils.metrics import circuit_state, degraded_passes

    n, c, kill_k, seed = args.bindings, args.clusters, args.chaos_kill, args.chaos_seed
    n_servers = 4
    n_fallback = max(c // 8, kill_k + 2)

    def group_term(g):
        return ClusterAffinityTerm(
            affinity_name=f"grp-{g}",
            label_selector=LabelSelector(match_labels={"group": g}),
        )

    from karmada_tpu.estimator.accurate import NodeState
    from karmada_tpu.utils.member import MemberCluster
    from karmada_tpu.utils.quantity import parse_resource_list

    feature_gate.set(FAILOVER, True)
    clock = [10_000.0]
    cp = _cli.cmd_init(clock=lambda: clock[0])
    for i in range(c):
        group = "fallback" if i >= c - n_fallback else "primary"
        name = f"ch{i:04d}"
        caps = {
            "cpu": f"{2000 + 8 * (i % 37)}", "memory": "4000Gi",
            "pods": 10_000,
        }
        # members carry REAL node state (one node = the cluster's caps):
        # the status controller derives genuine resource summaries from
        # it, so availability is capacity math (not the no-summary
        # sentinel clamp) and the estimator servers mirror it exactly —
        # the oracle-identity precondition
        member = MemberCluster(name)
        member.nodes = [
            NodeState(
                name=f"{name}-n0", allocatable=parse_resource_list(caps)
            )
        ]
        cp.join_cluster(
            new_cluster(name, labels={"group": group}, **caps), member
        )
    cp.settle()

    # live estimator fleet over the SAME capacities the snapshot carries
    # (min-merge(general, accurate) == general, so placements stay
    # oracle-checkable); ISSUE 4's invariant keeps degraded passes
    # un-replayable while a server is partitioned
    snap0 = ClusterSnapshot(sorted(
        cp.store.list("Cluster"), key=lambda cl: cl.name
    ))
    free = np.maximum(np.asarray(snap0.available_cap), 0)
    dims = list(snap0.dims)
    t0 = time.perf_counter()
    fleet_ctx = spawn_estimator_fleet(
        snap0.names, free, dims, n_servers=n_servers, index=snap0.index,
        timeout_seconds=3.0,
    )
    fleet = fleet_ctx.__enter__()
    record: dict = {}
    try:
        cp.scheduler.estimator_registry = fleet.registry
        cp.scheduler.extra_estimators = [
            fleet.registry.make_batch_estimator(
                snap0.names, timeout_seconds=5.0
            )
        ]
        print(
            f"# chaos build: {c} clusters, {n_servers} estimator server "
            f"processes in {time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
        )

        t0 = time.perf_counter()
        cp.store.apply(PropagationPolicy(
            meta=ObjectMeta(name="chaos-policy", namespace="default"),
            spec=PropagationSpec(
                resource_selectors=[
                    ResourceSelector(api_version="apps/v1", kind="Deployment")
                ],
                placement=dynamic_weight_placement(
                    cluster_affinities=[
                        group_term("primary"), group_term("fallback"),
                    ]
                ),
            ),
        ))
        profiles = [
            {"cpu": f"{250 * (p + 1)}m", "memory": f"{512 * (p + 1)}Mi"}
            for p in range(8)
        ]
        for i in range(n):
            prof = profiles[i % 8]
            cp.store.apply(new_deployment(
                f"ch{i}", replicas=(i % 8) + 1, cpu=prof["cpu"],
                memory=prof["memory"],
            ))
        print(f"# chaos workload build: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        t0 = time.perf_counter()
        cp.settle()
        print(f"# chaos cold wave: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)

        def storm_wave(tag: str) -> float:
            clock[0] += 60
            cp.store.apply(WorkloadRebalancer(
                meta=ObjectMeta(name=f"chaos-storm-{tag}"),
                spec=WorkloadRebalancerSpec(workloads=[
                    ObjectReferenceSelector(kind="Deployment", name=f"ch{i}")
                    for i in range(n)
                ]),
            ))
            t0 = time.perf_counter()
            cp.settle()
            return time.perf_counter() - t0

        prev_w = None
        for wi in range(3):
            w = storm_wave(f"warm{wi}")
            print(f"# chaos warm{wi} wave: {w:.1f}s", file=sys.stderr)
            if prev_w is not None and w > prev_w * 0.7:
                break
            prev_w = w

        # ---- steady reference (fault injection DISARMED: the injection
        # points are live in every hot path below, armed-off)
        steady = [storm_wave(f"steady{k}") for k in range(3)]
        steady_p50 = float(np.median(steady))
        print(f"# chaos steady storm p50 (disarmed): {steady_p50:.2f}s",
              file=sys.stderr)

        # ---- record pre-kill placements + pick the kill set from the
        # clusters actually carrying placements (seeded, replayable)
        before: dict[str, dict[str, int]] = {}
        for i in range(n):
            rb = cp.store.get("ResourceBinding", f"default/ch{i}-deployment")
            before[rb.meta.namespaced_name] = {
                tc.name: tc.replicas for tc in rb.spec.clusters
            }
        placed_primary = sorted({
            name for placed in before.values() for name in placed
        })
        primary_names = {
            cl.name for cl in cp.store.list("Cluster")
            if cl.meta.labels.get("group") == "primary"
        }
        candidates = [p for p in placed_primary if p in primary_names]
        rng = np.random.default_rng(seed)
        kill = sorted(
            rng.choice(candidates, size=min(kill_k, len(candidates)),
                       replace=False).tolist()
        )
        spec = ";".join(f"cluster.health=down,match={k}" for k in kill)
        est_conn = fleet.conns[0]
        stopped_proc = fleet.procs[0]
        est_channel = f"estimator@{est_conn.target}"

        # ---- the chaos wave: arm the seeded kills and partition
        # estimator server 0, then settle. The next heartbeat (the tick
        # at the head of the settle) flips the K members NotReady INSIDE
        # the wave; taints -> NoExecute evictions -> the cluster event
        # re-gates the whole 20k grid, and the displaced rows reschedule
        # through the ranked ordered-failover path as batched solves —
        # all while one estimator server is black-holed (its clusters
        # answer -1, the pass is degraded-not-stalled, and its breaker
        # opens). Time-to-stable-placement is this settle's wall clock.
        d0 = degraded_passes.value(channel="estimator")
        inj = faultinject.arm(spec, seed=seed)
        stopped_proc.send_signal(signal.SIGSTOP)
        solves0 = cp.scheduler._engine.solve_batches
        clock[0] += 60
        t0 = time.perf_counter()
        cp.settle()
        time_to_stable = time.perf_counter() - t0
        solves_wave = cp.scheduler._engine.solve_batches - solves0
        degraded_wave = degraded_passes.value(channel="estimator") - d0
        print(
            f"# chaos wave: stable in {time_to_stable:.1f}s, "
            f"{solves_wave} batched solves, degraded estimator "
            f"passes={degraded_wave}",
            file=sys.stderr,
        )

        # ---- verify: every binding against the per-binding numpy oracle
        # replaying the same event log
        after: dict[str, dict[str, int]] = {}
        displaced = 0
        killed_set = set(kill)
        for i in range(n):
            rb = cp.store.get("ResourceBinding", f"default/ch{i}-deployment")
            key = rb.meta.namespaced_name
            after[key] = {tc.name: tc.replicas for tc in rb.spec.clusters}
            if killed_set & set(before[key]):
                displaced += 1
        engine = cp.scheduler._engine
        esnap = engine.snapshot
        pl = cp.store.get(
            "PropagationPolicy", "default/chaos-policy"
        ).spec.placement
        cpl = compile_placement(pl, esnap)
        term_masks = np.stack([m for _, m in cpl.terms])
        base = cpl.taint_ok & cpl.spread_field_ok
        # per-profile availability rows (general == merged: the estimator
        # mirrors the snapshot, and -1 never survives the min-merge)
        pods_dim = esnap.dim_index("pods")
        avail_rows = {}
        from karmada_tpu.utils.quantity import parse_resource_list

        for p, prof in enumerate(profiles):
            reqs = np.zeros((1, len(esnap.dims)), np.int64)
            for d, q in parse_resource_list(prof).items():
                di = esnap.dim_index(d)
                if di is not None:
                    reqs[0, di] = q
            if pods_dim is not None:
                reqs[0, pods_dim] = 1
            avail_rows[p] = engine._availability_np(
                reqs, np.asarray([8], np.int32)
            )[0]
        keys = list(before)
        want = replay_failover(
            inj.log,
            esnap.names,
            before,
            {k: term_masks for k in keys},
            {k: base for k in keys},
            {k: cpl.strategy for k in keys},
            {k: (i % 8) + 1 for i, k in enumerate(keys)},
            {k: cpl.static_weights for k in keys},
            {k: avail_rows[i % 8] for i, k in enumerate(keys)},
        )
        mismatches = [
            k for k in keys if want[k] != after[k]
        ]
        oracle_identical = not mismatches
        print(
            f"# chaos oracle: {len(keys) - len(mismatches)}/{len(keys)} "
            f"placements identical, {displaced} displaced by "
            f"{len(kill)} killed clusters",
            file=sys.stderr,
        )
        if mismatches:
            k = mismatches[0]
            print(
                f"# chaos oracle FIRST MISMATCH {k}: want {want[k]} "
                f"got {after[k]} (before {before[k]})",
                file=sys.stderr,
            )

        # ---- degraded storms with the server STILL partitioned: the
        # breaker crosses its threshold and opens — a breaker-open pass
        # answers -1 with zero wire cost and is observable on the
        # karmada_tpu_circuit_state gauge
        degraded_storm_s = [storm_wave(f"degraded{k}") for k in range(2)]
        breaker_open = est_conn.breaker.state == backoff.OPEN or (
            circuit_state.value(channel=est_channel) == backoff.OPEN
        )
        print(
            f"# chaos degraded storms (server partitioned): "
            f"{', '.join(f'{s:.1f}s' for s in degraded_storm_s)}; "
            f"estimator breaker open={breaker_open}",
            file=sys.stderr,
        )

        # ---- recovery: un-partition the estimator server; the breaker
        # must close half-open -> closed without operator action
        stopped_proc.send_signal(signal.SIGCONT)
        faultinject.disarm()
        import grpc as _grpc

        try:
            _grpc.channel_ready_future(est_conn._channel).result(timeout=30)
        except Exception:  # noqa: BLE001 — recovery probe below decides
            pass
        recovered = False
        storm = 0.0
        deadline = time.time() + 30.0
        while time.time() < deadline:
            clock[0] += 60
            fleet.registry.invalidate(drop=True)
            storm = storm_wave("recover")
            if est_conn.breaker.state == backoff.CLOSED:
                recovered = True
                break
            time.sleep(0.5)
        print(
            f"# chaos recovery: breaker "
            f"{'closed' if recovered else 'STILL OPEN'} after server "
            f"resume (last recover wave {storm:.1f}s)",
            file=sys.stderr,
        )

        record = {
            "metric": f"chaos_storm_{n // 1000}kx{c}",
            "value": round(time_to_stable, 4),
            "unit": "s",
            # the acceptance slot: oracle-identical fraction (1.0 passes)
            "vs_baseline": round(
                (len(keys) - len(mismatches)) / max(len(keys), 1), 6
            ),
            "time_to_stable_s": round(time_to_stable, 4),
            "steady_p50_disarmed_s": round(steady_p50, 4),
            "killed_clusters": kill,
            "est_server_partitioned": est_conn.target,
            "displaced_bindings": displaced,
            "degraded_storm_s": [round(s, 4) for s in degraded_storm_s],
            "solves_failover_wave": int(solves_wave),
            "oracle_identical": oracle_identical,
            "oracle_mismatches": len(mismatches),
            "breaker_open_observed": bool(breaker_open),
            "breaker_recovered_closed": bool(recovered),
            "degraded_estimator_passes": int(
                degraded_passes.value(channel="estimator") - d0
            ),
            "replay_events": len(inj.log),
            "chaos_seed": seed,
        }
    finally:
        feature_gate.set(FAILOVER, False)
        faultinject.disarm()
        try:
            fleet_ctx.__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
    del cp
    gc.collect()
    return record


def run_quota(args) -> dict:
    """ISSUE 8 acceptance tier: the quota plane at storm scale. Workloads
    spread across N quota'd namespaces schedule against FRQ limits
    tightened to leave only --quota-headroom of the surge's delta demand,
    then a CronFederatedHPA surge rescales half the fleet simultaneously
    through the scale-up dispense path. Every engine pass's admission
    decisions and placements are verified against the sequential numpy
    oracle (refimpl.quota_np.admit_wave_np + the per-binding divider),
    steady storms run with enforcement on AND off (the overhead bound),
    and one namespace's quota raise must clear its QuotaExceeded
    conditions without re-packing the rest of the fleet."""
    import calendar
    import os

    from karmada_tpu import cli as _cli
    from karmada_tpu.api import (
        PropagationPolicy,
        PropagationSpec,
        ResourceSelector,
    )
    from karmada_tpu.api.autoscaling import (
        CronFederatedHPA,
        CronFederatedHPARule,
        CronFederatedHPASpec,
        ScaleTargetRef,
    )
    from karmada_tpu.api.core import ObjectMeta
    from karmada_tpu.api.policy import (
        FederatedResourceQuota,
        FederatedResourceQuotaSpec,
        StaticClusterAssignment,
    )
    from karmada_tpu.api.work import SCHEDULED
    from karmada_tpu.controllers.extras import (
        ObjectReferenceSelector,
        WorkloadRebalancer,
        WorkloadRebalancerSpec,
    )
    from karmada_tpu.refimpl.divider_np import assign_batch_np
    from karmada_tpu.refimpl.quota_np import (
        admit_wave_np,
        asking_ns_ids,
        cluster_caps_seq,
    )
    from karmada_tpu.scheduler.quota import QUOTA_EXCEEDED_ERROR
    from karmada_tpu.scheduler.snapshot import compile_placement
    from karmada_tpu.utils.builders import (
        dynamic_weight_placement,
        new_cluster,
        new_deployment,
    )
    from karmada_tpu.utils.quantity import parse_resource_list

    n, c = args.bindings, args.clusters
    n_ns = max(2, args.quota_namespaces)
    headroom = args.quota_headroom
    cap_ns_count = min(4, n_ns)  # namespaces that ALSO carry static caps
    surge_delta = 3
    base = calendar.timegm((2026, 1, 1, 8, 59, 0, 0, 0, 0))
    clock = [float(base)]
    cp = _cli.cmd_init(clock=lambda: clock[0])
    t0 = time.perf_counter()
    for i in range(c):
        cp.join_cluster(new_cluster(
            f"q{i:04d}",
            cpu=f"{2000 + 8 * (i % 37)}", memory="4000Gi", pods=1_000_000,
        ))
    cp.settle()
    namespaces = [f"nsq{k:02d}" for k in range(n_ns)]
    pl = dynamic_weight_placement()
    for ns in namespaces:
        cp.store.apply(PropagationPolicy(
            meta=ObjectMeta(name="pol", namespace=ns),
            spec=PropagationSpec(
                resource_selectors=[
                    ResourceSelector(api_version="apps/v1", kind="Deployment")
                ],
                placement=pl,
            ),
        ))
        # generous initial limits: the cold wave admits everything, then
        # the bench tightens to used + headroom once usage is live
        cp.store.apply(FederatedResourceQuota(
            meta=ObjectMeta(name="quota", namespace=ns),
            spec=FederatedResourceQuotaSpec(
                overall={"cpu": 1 << 40, "memory": 1 << 50}
            ),
        ))
    print(f"# quota build: {c} clusters + {n_ns} FRQs in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    t0 = time.perf_counter()
    req = parse_resource_list({"cpu": "500m", "memory": "512Mi"})
    keys = []
    for i in range(n):
        ns = namespaces[i % n_ns]
        cp.store.apply(new_deployment(
            f"w{i}", namespace=ns, replicas=(i % 4) + 1,
            cpu="500m", memory="512Mi",
        ))
        keys.append(f"{ns}/w{i}-deployment")
    cp.settle()
    print(f"# quota cold wave (+build): {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    def storm_wave(tag: str) -> float:
        clock[0] += 1
        cp.store.apply(WorkloadRebalancer(
            meta=ObjectMeta(name=f"quota-storm-{tag}"),
            spec=WorkloadRebalancerSpec(workloads=[
                ObjectReferenceSelector(
                    kind="Deployment", name=f"w{i}",
                    namespace=namespaces[i % n_ns],
                )
                for i in range(n)
            ]),
        ))
        t0 = time.perf_counter()
        cp.settle()
        return time.perf_counter() - t0

    prev_w = None
    for wi in range(3):
        w = storm_wave(f"warm{wi}")
        print(f"# quota warm{wi} wave: {w:.1f}s", file=sys.stderr)
        if prev_w is not None and w > prev_w * 0.7:
            break
        prev_w = w

    # ---- steady storms, enforcement ON vs DISARMED, interleaved so rig
    # warm-up drift cannot masquerade as enforcement cost (ON: delta
    # demand 0 — the enforcement cost is the admission mask pass; OFF:
    # the kill switch leaves one `is None` check on the engine hook).
    # Beside the whole-settle wall (which the shared rig swings ~2x wave
    # to wave), the ENGINE-schedule seconds per storm are tracked — the
    # admission hook lives entirely inside engine.schedule, so that pair
    # is the deterministic face of the enforcement-overhead claim.
    engine0 = cp.scheduler._engine
    sched_s = [0.0]
    inner0 = engine0.schedule

    def timed_schedule(problems):
        t0 = time.perf_counter()
        res = inner0(problems)
        sched_s[0] += time.perf_counter() - t0
        return res

    engine0.schedule = timed_schedule
    steady_on: list = []
    steady_off: list = []
    sched_on: list = []
    sched_off: list = []
    try:
        for k in range(3):
            sched_s[0] = 0.0
            steady_on.append(storm_wave(f"on{k}"))
            sched_on.append(sched_s[0])
            os.environ["KARMADA_TPU_QUOTA_ENFORCEMENT"] = "0"
            try:
                sched_s[0] = 0.0
                steady_off.append(storm_wave(f"off{k}"))
                sched_off.append(sched_s[0])
            finally:
                os.environ.pop("KARMADA_TPU_QUOTA_ENFORCEMENT", None)
    finally:
        engine0.schedule = inner0
    on_p50 = float(np.median(steady_on))
    off_p50 = float(np.median(steady_off))
    sched_on_p50 = float(np.median(sched_on))
    sched_off_p50 = float(np.median(sched_off))
    print(
        f"# quota steady storm p50: enforcement on {on_p50:.2f}s / off "
        f"{off_p50:.2f}s wall ({on_p50 / max(off_p50, 1e-9):.3f}x); "
        f"engine schedule {sched_on_p50:.2f}s / {sched_off_p50:.2f}s "
        f"({sched_on_p50 / max(sched_off_p50, 1e-9):.3f}x)",
        file=sys.stderr,
    )

    # ---- tighten every namespace's quota to used + headroom x the
    # surge's delta demand, and give the first cap_ns_count namespaces a
    # static-assignment cap on cluster 0 (folds into availability)
    surged = [i for i in range(n) if i % 2 == 0]
    surged_per_ns: dict[str, int] = {}
    for i in surged:
        nsn = namespaces[i % n_ns]
        surged_per_ns[nsn] = surged_per_ns.get(nsn, 0) + 1
    cpu_req = req["cpu"]
    limits: dict[str, int] = {}
    for k, ns in enumerate(namespaces):
        frq = cp.store.get("FederatedResourceQuota", f"{ns}/quota")
        used = int(frq.status.overall_used.get("cpu", 0))
        surge_demand = surged_per_ns.get(ns, 0) * surge_delta * cpu_req
        limit = used + int(surge_demand * headroom)
        limits[ns] = limit
        frq.spec.overall = {"cpu": limit}
        if k < cap_ns_count:
            frq.spec.static_assignments = [StaticClusterAssignment(
                cluster_name="q0000", hard={"cpu": 2000}
            )]
        cp.store.apply(frq)
    cp.settle()

    # ---- capture every engine pass of the surge for the oracle replay:
    # (keys, namespaces, replicas, prev dicts, fresh, remaining tensor,
    # ns ids, engine results) in engine arrival order
    engine = cp.scheduler._engine
    esnap = engine.snapshot
    passes: list = []
    inner = engine.schedule

    def capture_schedule(problems):
        q = engine.quota
        snap_rem = (
            (q.remaining.copy(), dict(q.ns_index), q.generation)
            if q is not None
            else None
        )
        res = inner(problems)
        passes.append((list(problems), snap_rem, list(res)))
        return res

    engine.schedule = capture_schedule
    solves0 = engine.solve_batches
    try:
        for i in surged:
            nsn = namespaces[i % n_ns]
            cp.store.apply(CronFederatedHPA(
                meta=ObjectMeta(name=f"surge-w{i}", namespace=nsn),
                spec=CronFederatedHPASpec(
                    scale_target_ref=ScaleTargetRef(
                        kind="Deployment", name=f"w{i}"
                    ),
                    rules=[CronFederatedHPARule(
                        name="surge", schedule="0 9 * * *",
                        target_replicas=(i % 4) + 1 + surge_delta,
                    )],
                ),
            ))
        cp.settle()
        clock[0] = float(base) + 90  # cross 09:00: every rule fires
        t0 = time.perf_counter()
        cp.settle()
        surge_s = time.perf_counter() - t0
    finally:
        engine.schedule = inner
    surge_solves = engine.solve_batches - solves0
    print(
        f"# quota surge wave: {surge_s:.1f}s, {surge_solves} batched "
        f"solves over {len(passes)} engine passes",
        file=sys.stderr,
    )

    # ---- oracle replay: admission via the sequential numpy loop,
    # placements via the per-pass batched numpy divider over cap-folded
    # availability — decisions AND placements must match every pass
    cpl = compile_placement(pl, esnap)
    base_mask = cpl.terms[0][1] & cpl.taint_ok & cpl.spread_field_ok
    dims = list(esnap.dims)
    cpu_dim = dims.index("cpu")
    pods_dim = esnap.dim_index("pods")
    req_vec = np.zeros(len(dims), np.int64)
    for d, qty in req.items():
        j = esnap.dim_index(d)
        if j is not None:
            req_vec[j] = qty
    if pods_dim is not None:
        req_vec[pods_dim] = max(req_vec[pods_dim], 1)
    # base availability row shared per replicas-count (engine mirror —
    # the chaos-bench precedent: inputs shared, decision math oracle-own)
    avail_rows: dict[int, np.ndarray] = {}

    def avail_row(reps: int) -> np.ndarray:
        row = avail_rows.get(reps)
        if row is None:
            row = engine._availability_np(
                req_vec[None, :], np.asarray([reps], np.int32)
            )[0]
            avail_rows[reps] = row
        return row

    # oracle cap rows per namespace (cluster_caps_seq: the sequential
    # per-cluster loop, one row per capped namespace)
    cap_rows_by_ns: dict[str, np.ndarray] = {}
    for k in range(cap_ns_count):
        frq = cp.store.get(
            "FederatedResourceQuota", f"{namespaces[k]}/quota"
        )
        caps = np.full((1, c, len(dims)), 2**62, np.int64)
        for assignment in frq.spec.static_assignments:
            col = esnap.index.get(assignment.cluster_name)
            if col is not None:
                for res, hard in assignment.hard.items():
                    j = esnap.dim_index(res)
                    if j is not None:
                        caps[0, col, j] = int(hard)
        cap_rows_by_ns[namespaces[k]] = cluster_caps_seq(caps, 0, req_vec)

    adm_checked = adm_mismatch = 0
    pl_checked = pl_mismatch = 0
    strategy = np.int32(cpl.strategy)
    for problems, snap_rem, results in passes:
        if snap_rem is None:
            continue
        remaining, ns_index, _gen = snap_rem
        ns_ids = [ns_index.get(p.namespace, -1) for p in problems]
        demand = np.zeros((len(problems), len(dims)), np.int64)
        for row_i, p in enumerate(problems):
            if ns_ids[row_i] < 0:
                continue
            delta = p.replicas - sum(p.prev.values())
            if delta > 0:
                demand[row_i] = req_vec * delta
        want_admit, _used = admit_wave_np(
            asking_ns_ids(ns_ids, demand), demand, remaining
        )
        got_admit = [r.error != QUOTA_EXCEEDED_ERROR for r in results]
        adm_checked += len(problems)
        adm_mismatch += sum(
            1 for w, g in zip(want_admit, got_admit) if w != g
        )
        # placements of the admitted rows: one batched numpy divide
        adm_idx = [
            i for i, (w, r) in enumerate(zip(want_admit, results))
            if w and r.success and problems[i].replicas > 0
        ]
        if not adm_idx:
            continue
        b = len(adm_idx)
        reps = np.fromiter(
            (problems[i].replicas for i in adm_idx), np.int32, b
        )
        prev = np.zeros((b, c), np.int32)
        fresh = np.zeros(b, bool)
        avail = np.zeros((b, c), np.int64)
        for row_i, i in enumerate(adm_idx):
            p = problems[i]
            fresh[row_i] = p.fresh
            for name, r_prev in p.prev.items():
                col = esnap.index.get(name)
                if col is not None:
                    prev[row_i, col] = r_prev
            row = avail_row(p.replicas).astype(np.int64)
            cap = cap_rows_by_ns.get(p.namespace)
            if cap is not None:
                row = np.minimum(row, cap.astype(np.int64))
            avail[row_i] = row
        cand = np.broadcast_to(base_mask, (b, c))
        assignment, unsched = assign_batch_np(
            np.full(b, strategy, np.int32), reps, cand,
            np.zeros((b, c), np.int32),
            np.minimum(avail, 2**31 - 1).astype(np.int32),
            prev, fresh,
        )
        for row_i, i in enumerate(adm_idx):
            want = {
                esnap.names[j]: int(assignment[row_i, j])
                for j in np.flatnonzero(assignment[row_i] > 0)
            }
            pl_checked += 1
            if bool(unsched[row_i]):
                # adm_idx rows are engine-SUCCESSFUL: the oracle calling
                # one unschedulable is itself a divergence, not a skip
                pl_mismatch += 1
                if pl_mismatch == 1:
                    print(
                        f"# quota oracle FIRST placement mismatch "
                        f"{problems[i].key}: oracle unschedulable, engine "
                        f"placed {results[i].clusters}",
                        file=sys.stderr,
                    )
                continue
            if want != results[i].clusters:
                pl_mismatch += 1
                if pl_mismatch == 1:
                    print(
                        f"# quota oracle FIRST placement mismatch "
                        f"{problems[i].key}: want {want} got "
                        f"{results[i].clusters}",
                        file=sys.stderr,
                    )
    print(
        f"# quota oracle: admission {adm_checked - adm_mismatch}/"
        f"{adm_checked} identical, placements "
        f"{pl_checked - pl_mismatch}/{pl_checked} identical",
        file=sys.stderr,
    )

    # ---- post-surge state: denied bindings carry QuotaExceeded and
    # keep their pre-surge replicas
    denied_keys = []
    scaled = 0
    for i in surged:
        rb = cp.store.get("ResourceBinding", keys[i])
        cond = next(
            (cc for cc in rb.status.conditions if cc.type == SCHEDULED),
            None,
        )
        total = sum(tc.replicas for tc in rb.spec.clusters)
        if cond is not None and not cond.status:
            denied_keys.append(keys[i])
            assert cond.reason == "QuotaExceeded", cond
        elif total == (i % 4) + 1 + surge_delta:
            scaled += 1
    print(
        f"# quota surge outcome: {scaled} scaled, {len(denied_keys)} "
        f"denied with QuotaExceeded",
        file=sys.stderr,
    )

    # ---- quota raise clears denials WITHOUT a full re-pack: raise ONE
    # namespace's limit and count the extra batched solves
    raise_ns = None
    for ns in namespaces:
        if any(k.startswith(ns + "/") for k in denied_keys):
            raise_ns = ns
            break
    raise_clear = raise_solves = None
    if raise_ns is not None:
        ns_denied = [k for k in denied_keys if k.startswith(raise_ns + "/")]
        solves0 = engine.solve_batches
        frq = cp.store.get("FederatedResourceQuota", f"{raise_ns}/quota")
        frq.spec.overall = {"cpu": limits[raise_ns] + (1 << 40)}
        cp.store.apply(frq)
        clock[0] += 60
        cp.settle()
        raise_solves = cp.scheduler._engine.solve_batches - solves0
        cleared = sum(
            1
            for k in ns_denied
            if next(
                cc
                for cc in cp.store.get(
                    "ResourceBinding", k
                ).status.conditions
                if cc.type == SCHEDULED
            ).status
        )
        raise_clear = cleared == len(ns_denied)
        print(
            f"# quota raise on {raise_ns}: {cleared}/{len(ns_denied)} "
            f"denials cleared in {raise_solves} batched solve(s)",
            file=sys.stderr,
        )

    record = {
        "metric": f"quota_surge_{n // 1000}kx{c}",
        "value": round(surge_s, 4),
        "unit": "s",
        # acceptance slot: identical fraction over admission + placements
        "vs_baseline": round(
            (adm_checked - adm_mismatch + pl_checked - pl_mismatch)
            / max(adm_checked + pl_checked, 1),
            6,
        ),
        "surge_wave_s": round(surge_s, 4),
        "surge_solves": int(surge_solves),
        "surge_engine_passes": len(passes),
        "quota_namespaces": n_ns,
        "capped_namespaces": cap_ns_count,
        "surged_bindings": len(surged),
        "scaled_bindings": int(scaled),
        "denied_bindings": len(denied_keys),
        "admission_checked": int(adm_checked),
        "admission_identical": adm_mismatch == 0,
        "placements_checked": int(pl_checked),
        "placements_identical": pl_mismatch == 0,
        "steady_p50_enforced_s": round(on_p50, 4),
        "steady_p50_disabled_s": round(off_p50, 4),
        "enforcement_overhead_x": round(on_p50 / max(off_p50, 1e-9), 4),
        # the deterministic overhead face: engine.schedule seconds alone
        # (admission lives there; the settle wall swings ~2x on the rig)
        "steady_sched_enforced_s": round(sched_on_p50, 4),
        "steady_sched_disabled_s": round(sched_off_p50, 4),
        "sched_overhead_x": round(
            sched_on_p50 / max(sched_off_p50, 1e-9), 4
        ),
        "raise_namespace": raise_ns,
        "raise_cleared_all": raise_clear,
        "raise_solves": raise_solves,
    }
    del cp
    gc.collect()
    return record


def run_preemption(args) -> dict:
    """ISSUE 14 acceptance tier: the scarcity plane at storm scale.

    A fleet of C member clusters carries B priority-0 workloads, member
    capacity is then saturated EXACTLY (the spot market is fully
    subscribed), and a high-priority surge lands that cannot fit
    anywhere. The batched preemption kernel must select victims
    plane-wide in ONE dispatch, the demanders must place against the
    freed capacity in the same engine pass (solve_batches counts prove
    the shape), and both the victim set and the final placements must be
    bit-identical to the sequential numpy oracle. A drift-rebalance
    round through the continuous descheduler then re-places the worst-
    drifted residents under an EXACT disruption budget, and interleaved
    armed/disarmed steady storms bound the disarmed cost."""
    import os

    from karmada_tpu import cli as _cli
    from karmada_tpu.api import (
        PropagationPolicy,
        PropagationSpec,
        ResourceSelector,
    )
    from karmada_tpu.api.core import ObjectMeta
    from karmada_tpu.api.policy import LabelSelector
    from karmada_tpu.controllers.extras import (
        ObjectReferenceSelector,
        WorkloadRebalancer,
        WorkloadRebalancerSpec,
    )
    from karmada_tpu.estimator.accurate import NodeState
    from karmada_tpu.refimpl.preempt_np import (
        preempt_and_place_np,
        rebalance_np,
    )
    from karmada_tpu.scheduler.quota import per_replica_vector
    from karmada_tpu.scheduler.snapshot import compile_placement
    from karmada_tpu.utils.builders import (
        dynamic_weight_placement,
        new_cluster,
        new_deployment,
    )
    from karmada_tpu.utils.member import MemberCluster
    from karmada_tpu.utils.metrics import preemptions_total
    from karmada_tpu.utils.quantity import parse_resource_list

    n, c = args.bindings, args.clusters
    n_hi = max(1, args.preempt_surge)
    budget = max(1, args.preempt_budget)
    reps_low = 2
    cpu_req = 500  # milli per replica

    from karmada_tpu.api.policy import ClusterAffinity

    cp = _cli.cmd_init(enable_drift_rebalancer=True)
    cp.drift_rebalancer.active = False  # manual rounds only
    members: dict = {}
    # cluster groups spread the priority-0 residents across the fleet
    # (the per-binding estimates carry no intra-wave decrement, so an
    # ungrouped identical-profile fill would stack on the first columns
    # — groups model the tenancy structure a real spot fleet has)
    n_groups = max(1, min(64, c // 8))
    t0 = time.perf_counter()
    for i in range(c):
        name = f"p{i:04d}"
        caps = {"cpu": "200", "memory": "4000Gi", "pods": 1_000_000}
        m = MemberCluster(name)
        m.nodes = [NodeState(
            name=f"{name}-n0", allocatable=parse_resource_list(caps)
        )]
        members[name] = m
        cp.join_cluster(new_cluster(
            name, labels={"group": f"g{i % n_groups}"}, **caps
        ), m)
    cp.settle()
    pl = dynamic_weight_placement()

    def policy(name, match, priority=0, placement=pl):
        return PropagationPolicy(
            meta=ObjectMeta(name=name, namespace="default"),
            spec=PropagationSpec(
                resource_selectors=[ResourceSelector(
                    api_version="apps/v1", kind="Deployment",
                    label_selector=LabelSelector(match_labels=match),
                )],
                placement=placement,
                priority=priority,
            ),
        )

    for k in range(n_groups):
        cp.store.apply(policy(
            f"low-g{k}",
            {"tier": "low", "grp": f"g{k}"},
            placement=dynamic_weight_placement(
                cluster_affinity=ClusterAffinity(
                    label_selector=LabelSelector(
                        match_labels={"group": f"g{k}"}
                    )
                )
            ),
        ))
    cp.store.apply(policy("high", {"tier": "high"}, priority=100))
    for i in range(n):
        cp.store.apply(new_deployment(
            f"w{i}", replicas=reps_low, cpu="500m", memory="512Mi",
            labels={"tier": "low", "grp": f"g{i % n_groups}"},
        ))
    cp.settle()
    print(
        f"# preempt build: {c} clusters + {n} low bindings in "
        f"{time.perf_counter() - t0:.1f}s",
        file=sys.stderr,
    )

    def sync_member_usage(saturate: bool = False):
        """node.requested mirrors bound replicas (the kubelet's role in
        this harness); ``saturate`` then clamps each node's cpu
        allocatable down TO its requested — the fully-subscribed spot
        fleet the scarcity scenario needs."""
        usage = {name: {} for name in members}
        for rb in cp.store.list("ResourceBinding"):
            req = (
                rb.spec.replica_requirements.resource_request
                if rb.spec.replica_requirements
                else {}
            )
            for tc in rb.spec.clusters:
                acc = usage.get(tc.name)
                if acc is None:
                    continue
                for res, qty in req.items():
                    acc[res] = acc.get(res, 0) + qty * tc.replicas
                acc["pods"] = acc.get("pods", 0) + tc.replicas
        for name, m in members.items():
            m.nodes[0].requested = dict(usage[name])
            if saturate:
                m.nodes[0].allocatable = dict(
                    m.nodes[0].allocatable,
                    cpu=usage[name].get("cpu", 0),
                )
        cp.settle()

    # warm storms until flat (the settle_engine discipline, driven
    # through whole-plane rebalancer waves)
    def storm_wave(tag: str) -> float:
        cp.store.apply(WorkloadRebalancer(
            meta=ObjectMeta(name=f"preempt-storm-{tag}"),
            spec=WorkloadRebalancerSpec(workloads=[
                ObjectReferenceSelector(
                    kind="Deployment", name=f"w{i}", namespace="default"
                )
                for i in range(n)
            ]),
        ))
        t0 = time.perf_counter()
        cp.settle()
        return time.perf_counter() - t0

    prev_w = None
    for wi in range(3):
        w = storm_wave(f"warm{wi}")
        print(f"# preempt warm{wi} wave: {w:.1f}s", file=sys.stderr)
        if prev_w is not None and w > prev_w * 0.7:
            break
        prev_w = w

    # ---- armed-vs-disarmed steady storms, interleaved (the quota-tier
    # discipline: rig warm-up drift cannot masquerade as arming cost).
    # A handful of PLACED high-priority bindings keeps the armed path's
    # priority scan + victim-source arming genuinely engaged while no
    # binding is unschedulable — the disarmed-claim's exact shape.
    for i in range(50):
        cp.store.apply(new_deployment(
            f"warmhi{i}", replicas=1, cpu="500m", memory="512Mi",
            labels={"tier": "high"},
        ))
    cp.settle()
    engine0 = cp.scheduler._inproc_engine()
    sched_s = [0.0]
    inner0 = engine0.schedule

    def timed_schedule(problems):
        t0 = time.perf_counter()
        res = inner0(problems)
        sched_s[0] += time.perf_counter() - t0
        return res

    engine0.schedule = timed_schedule
    steady_armed: list = []
    steady_off: list = []
    sched_armed: list = []
    sched_off: list = []
    try:
        for k in range(3):
            sched_s[0] = 0.0
            steady_armed.append(storm_wave(f"armed{k}"))
            sched_armed.append(sched_s[0])
            os.environ["KARMADA_TPU_PREEMPTION"] = "0"
            try:
                sched_s[0] = 0.0
                steady_off.append(storm_wave(f"off{k}"))
                sched_off.append(sched_s[0])
            finally:
                os.environ.pop("KARMADA_TPU_PREEMPTION", None)
    finally:
        engine0.schedule = inner0
    armed_p50 = float(np.median(steady_armed))
    off_p50 = float(np.median(steady_off))
    sched_armed_p50 = float(np.median(sched_armed))
    sched_off_p50 = float(np.median(sched_off))
    overhead_x = sched_armed_p50 / max(sched_off_p50, 1e-9)
    print(
        f"# preempt steady storm p50: armed {armed_p50:.2f}s / disarmed "
        f"{off_p50:.2f}s wall ({armed_p50 / max(off_p50, 1e-9):.3f}x); "
        f"engine schedule {sched_armed_p50:.2f}s / {sched_off_p50:.2f}s "
        f"({overhead_x:.3f}x)",
        file=sys.stderr,
    )

    # ---- saturate the fleet exactly and snapshot pre-surge state
    sync_member_usage(saturate=True)
    engine = cp.scheduler._inproc_engine()
    esnap = engine.snapshot
    dims = list(esnap.dims)
    base_caps = np.asarray(esnap.available_cap).copy()
    cpu_dim = esnap.dim_index("cpu")
    assert int(np.maximum(base_caps[:, cpu_dim], 0).sum()) == 0, (
        "saturation failed: free cpu remains"
    )
    # the resident pool, in the victim-source's iteration order
    pre_surge = [
        (
            rb.meta.namespaced_name,
            {tc.name: tc.replicas for tc in rb.spec.clusters},
            (
                rb.spec.replica_requirements.resource_request
                if rb.spec.replica_requirements
                else {}
            ),
            getattr(rb.spec, "priority", 0),
        )
        for rb in cp.store.list("ResourceBinding")
        if rb.spec.clusters
    ]

    # ---- the scarcity surge, every engine pass captured
    passes: list = []
    inner = engine.schedule

    def capture_schedule(problems):
        res = inner(problems)
        passes.append((
            list(problems), list(res), engine.last_preemption,
        ))
        return res

    engine.schedule = capture_schedule
    solves0 = engine.solve_batches
    try:
        for i in range(n_hi):
            cp.store.apply(new_deployment(
                f"hi{i}", replicas=reps_low, cpu="500m", memory="512Mi",
                labels={"tier": "high"},
            ))
        t0 = time.perf_counter()
        cp.settle()
        surge_s = time.perf_counter() - t0
    finally:
        engine.schedule = inner
    surge_solves = engine.solve_batches - solves0
    outcome_passes = [
        (pp, rr, oo) for pp, rr, oo in passes if oo is not None and oo.victims
    ]
    print(
        f"# preempt surge wave: {surge_s:.1f}s, {surge_solves} batched "
        f"solves over {len(passes)} engine passes "
        f"({len(outcome_passes)} with preemption)",
        file=sys.stderr,
    )

    # ---- oracle replay: sequential victim selection + per-binding
    # boosted divides, sharing NO selection code with the kernel. Inputs
    # (row order, placements, requests) are shared — the chaos-bench
    # precedent — the decision math is the oracle's own.
    victim_keys_engine = sorted(
        rb.meta.namespaced_name
        for rb in cp.store.list("ResourceBinding")
        if any(
            t.reason == "PreemptedByHigherPriority"
            for t in rb.spec.graceful_eviction_tasks
        )
    )
    cpl = compile_placement(pl, esnap)
    base_mask = cpl.terms[0][1] & cpl.taint_ok & cpl.spread_field_ok
    vic_checked = vic_mismatch = 0
    pl_checked = pl_mismatch = 0
    oracle_victims: list = []
    if outcome_passes:
        problems0, results0, _out0 = outcome_passes[0]
        demanders = [
            p for p in problems0 if getattr(p, "priority", 0) > 0
        ]
        wave_keys = {p.key for p in problems0}
        keys, prios, demand_rows, freed_rows = [], [], [], []
        victim_ok, weights = [], []
        assigned_by_key: dict = {}
        requests_by_key: dict = {}
        for p in demanders:
            keys.append(p.key)
            prios.append(getattr(p, "priority", 0))
            vec = per_replica_vector(p.requests, dims)
            requests_by_key[p.key] = vec
            short = p.replicas - (0 if p.fresh else sum(p.prev.values()))
            demand_rows.append(vec * max(short, 0))
            freed_rows.append(np.zeros(len(dims), np.int64))
            victim_ok.append(False)
            weights.append(0)
        for key, placement, req, prio in pre_surge:
            if key in wave_keys:
                continue
            keys.append(key)
            prios.append(prio)
            vec = per_replica_vector(req, dims)
            requests_by_key[key] = vec
            assigned_by_key[key] = placement
            total = sum(placement.values())
            demand_rows.append(np.zeros(len(dims), np.int64))
            freed_rows.append(vec * total)
            victim_ok.append(total > 0)
            weights.append(total)
        oracle_victims, oracle_placed = preempt_and_place_np(
            keys, prios,
            np.stack(demand_rows), np.stack(freed_rows),
            victim_ok, weights,
            names=esnap.names,
            assigned=assigned_by_key,
            requests=requests_by_key,
            # UNCLAMPED base caps: an overcommitted dim must stay
            # negative until the freed capacity digs it out — the
            # engine's clamp-AFTER-add order (host_profile_table)
            base_caps=base_caps,
            demanders=[p.key for p in demanders],
            candidates={
                p.key: np.asarray(base_mask) for p in demanders
            },
            strategies={p.key: int(cpl.strategy) for p in demanders},
            replicas={p.key: p.replicas for p in demanders},
            prev={p.key: dict(p.prev) for p in demanders},
        )
        vic_checked = len(
            set(oracle_victims) | set(victim_keys_engine)
        )
        vic_mismatch = len(
            set(oracle_victims) ^ set(victim_keys_engine)
        )
        for p in demanders:
            want = oracle_placed.get(p.key, {})
            rb = cp.store.get("ResourceBinding", p.key)
            got = (
                {tc.name: tc.replicas for tc in rb.spec.clusters}
                if rb is not None
                else {}
            )
            pl_checked += 1
            if want != got:
                pl_mismatch += 1
                if pl_mismatch == 1:
                    print(
                        f"# preempt oracle FIRST placement mismatch "
                        f"{p.key}: want {want} got {got}",
                        file=sys.stderr,
                    )
    print(
        f"# preempt oracle: victims {vic_checked - vic_mismatch}/"
        f"{vic_checked} identical, placements "
        f"{pl_checked - pl_mismatch}/{pl_checked} identical",
        file=sys.stderr,
    )
    preempted_count = sum(preemptions_total.samples().values())

    # ---- drift-rebalance round: fresh spot capacity arrives, the
    # continuous descheduler re-places the worst drifted residents under
    # an exact budget, oracle-verified
    n_new = 8
    for i in range(n_new):
        name = f"new{i:02d}"
        caps = {"cpu": "400", "memory": "4000Gi", "pods": 1_000_000}
        m = MemberCluster(name)
        m.nodes = [NodeState(
            name=f"{name}-n0", allocatable=parse_resource_list(caps)
        )]
        members[name] = m
        cp.join_cluster(new_cluster(name, **caps), m)
    cp.settle()
    engine = cp.scheduler._inproc_engine()
    dsnap = engine.snapshot

    # the oracle's trigger set: per-binding fresh one-row divides over
    # the SAME candidate/availability inputs, sequential (placements
    # differ per group policy, so candidates compile per placement)
    o_keys, o_current, o_cands, o_strats, o_reps, o_avail = (
        [], {}, {}, {}, {}, {}
    )
    avail_rows: dict = {}
    cpl_cache: dict = {}
    for kind, rb, problem in cp.drift_rebalancer._candidates():
        key = rb.meta.namespaced_name
        o_keys.append(key)
        o_current[key] = {tc.name: tc.replicas for tc in rb.spec.clusters}
        dcpl = cpl_cache.get(id(rb.spec.placement))
        if dcpl is None:
            dcpl = compile_placement(rb.spec.placement, dsnap)
            cpl_cache[id(rb.spec.placement)] = dcpl
        o_cands[key] = np.asarray(
            dcpl.terms[0][1] & dcpl.taint_ok & dcpl.spread_field_ok
        )
        o_strats[key] = int(dcpl.strategy)
        o_reps[key] = rb.spec.replicas
        row = avail_rows.get(rb.spec.replicas)
        if row is None:
            vec = per_replica_vector(
                problem.requests, list(dsnap.dims)
            )[None, :]
            row = engine._availability_np(
                vec, np.asarray([rb.spec.replicas], np.int32)
            )[0]
            avail_rows[rb.spec.replicas] = row
        o_avail[key] = row
    t0 = time.perf_counter()
    os.environ["KARMADA_TPU_DESCHEDULE_MAX_DISRUPTION"] = str(budget)
    try:
        stats = cp.drift_rebalancer.rebalance_once()
        cp.settle()  # the triggered bindings re-place as Fresh waves
    finally:
        os.environ.pop("KARMADA_TPU_DESCHEDULE_MAX_DISRUPTION", None)
    drift_s = time.perf_counter() - t0
    _odrifts, oracle_triggered = rebalance_np(
        o_keys,
        names=dsnap.names,
        current=o_current,
        candidates=o_cands,
        strategies=o_strats,
        replicas=o_reps,
        avail=o_avail,
        budget=budget,
    )
    drift_identical = stats["triggered"] == oracle_triggered
    budget_exact = len(stats["triggered"]) == min(
        budget, stats["drifted"]
    )
    replaced = sum(
        1
        for key in stats["triggered"]
        for rb in [cp.store.get("ResourceBinding", key)]
        if rb is not None
        and rb.status.last_scheduled_time is not None
        and rb.spec.reschedule_triggered_at is not None
        and rb.status.last_scheduled_time
        >= rb.spec.reschedule_triggered_at
    )
    print(
        f"# preempt drift round: {stats['drifted']} drifted, "
        f"{len(stats['triggered'])}/{budget} triggered "
        f"(oracle identical={drift_identical}, budget exact="
        f"{budget_exact}, {replaced} re-placed) in {drift_s:.1f}s",
        file=sys.stderr,
    )

    record = {
        "metric": f"preempt_storm_{n // 1000}kx{c}",
        "value": round(surge_s, 4),
        "unit": "s",
        # acceptance slot: identical fraction over victims + placements
        "vs_baseline": round(
            (vic_checked - vic_mismatch + pl_checked - pl_mismatch)
            / max(vic_checked + pl_checked, 1),
            6,
        ),
        "surge_wave_s": round(surge_s, 4),
        "surge_solves": int(surge_solves),
        "surge_engine_passes": len(passes),
        "preemption_passes": len(outcome_passes),
        "surged_bindings": n_hi,
        "victims_evicted": len(victim_keys_engine),
        "victims_checked": int(vic_checked),
        "victims_identical": vic_mismatch == 0,
        "placements_checked": int(pl_checked),
        "placements_identical": pl_mismatch == 0,
        "preemptions_total": int(preempted_count),
        "steady_p50_armed_s": round(armed_p50, 4),
        "steady_p50_disarmed_s": round(off_p50, 4),
        "steady_sched_armed_s": round(sched_armed_p50, 4),
        "steady_sched_disarmed_s": round(sched_off_p50, 4),
        # the guarded disarmed-vs-armed claim: engine.schedule seconds
        # alone (arming lives there; the settle wall swings on the rig)
        "preempt_overhead_x": round(overhead_x, 4),
        "drift_round_s": round(drift_s, 4),
        "drift_scored": int(stats["scored"]),
        "drift_drifted": int(stats["drifted"]),
        "drift_budget": int(budget),
        "drift_triggered": len(stats["triggered"]),
        "drift_budget_exact": bool(budget_exact),
        "drift_oracle_identical": bool(drift_identical),
        "drift_replaced": int(replaced),
    }
    del cp
    gc.collect()
    return record


def run_observability(args) -> dict:
    """ISSUE 6 acceptance tier: one whole-plane storm wave (detector ->
    scheduler -> binding -> works) with the wave tracer on. The record
    proves the measurement layer itself: the wave's span tree must cover
    >=95% of the externally measured wall clock, with the kernel span
    split into compile/device/host components and the per-phase breakdown
    rendered into the docs tables (tools/docs_from_bench.py)."""
    from karmada_tpu import cli as _cli
    from karmada_tpu.api import (
        PropagationPolicy,
        PropagationSpec,
        ResourceSelector,
    )
    from karmada_tpu.api.core import ObjectMeta
    from karmada_tpu.controllers.extras import (
        ObjectReferenceSelector,
        WorkloadRebalancer,
        WorkloadRebalancerSpec,
    )
    from karmada_tpu.utils.builders import (
        dynamic_weight_placement,
        new_cluster,
        new_deployment,
    )
    from karmada_tpu.utils.metrics import kernel_compiles
    from karmada_tpu.utils.tracing import tracer

    n, c = args.bindings, args.clusters

    clock = [10_000.0]
    cp = _cli.cmd_init(clock=lambda: clock[0])
    for i in range(c):
        cp.join_cluster(new_cluster(f"obs{i}", cpu="2000", memory="4000Gi"))
    cp.settle()
    t0 = time.perf_counter()
    cp.store.apply(PropagationPolicy(
        meta=ObjectMeta(name="obs-policy", namespace="default"),
        spec=PropagationSpec(
            resource_selectors=[
                ResourceSelector(api_version="apps/v1", kind="Deployment")
            ],
            placement=dynamic_weight_placement(),
        ),
    ))
    for i in range(n):
        cp.store.apply(new_deployment(f"obs{i}", replicas=(i % 8) + 1))
    print(f"# observability build: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    t0 = time.perf_counter()
    cp.settle()
    cold_wall = time.perf_counter() - t0
    n_works = len(cp.store.list("Work"))
    print(
        f"# observability cold wave: {cold_wall:.1f}s "
        f"({n_works} works rendered)",
        file=sys.stderr,
    )
    cold_summary = tracer.wave_summary()

    def storm_wave(tag: str) -> tuple:
        """One rebalancer storm wave; returns (wall_s, summaries of the
        waves the settle produced, main summary = largest total)."""
        clock[0] += 60
        cp.store.apply(WorkloadRebalancer(
            meta=ObjectMeta(name=f"obs-storm-{tag}"),
            spec=WorkloadRebalancerSpec(workloads=[
                ObjectReferenceSelector(kind="Deployment", name=f"obs{i}")
                for i in range(n)
            ]),
        ))
        before = set(tracer.waves())
        t0 = time.perf_counter()
        cp.settle()
        wall = time.perf_counter() - t0
        new = [w for w in tracer.waves() if w not in before]
        sums = [tracer.wave_summary(w) for w in new] or [
            tracer.wave_summary()
        ]
        main = max(sums, key=lambda s: s["total_s"])
        return wall, sums, main

    # warm until the wave cost flattens (same discipline as the
    # whole-plane tier: the first storms still pay heap/queue settlement
    # and fleet-table compiles)
    prev_w = None
    for wi in range(4):
        w, _, _ = storm_wave(f"warm{wi}")
        print(f"# observability warm{wi} wave: {w:.1f}s", file=sys.stderr)
        if prev_w is not None and w > prev_w * 0.7:
            break
        prev_w = w

    # ISSUE 12 (b): the device-byte ledger across the measured steady
    # wave — resident bytes must not move between steady passes, and the
    # gauge's samples must sum to the engine's exact nbytes ledger
    eng = getattr(cp.scheduler, "_engine", None)
    bytes_before = eng.device_bytes() if eng is not None else {}

    wall, sums, main = storm_wave("measured")
    # the acceptance number: how much of the externally measured wall
    # clock the wave tree attributes to named spans (every settle the
    # storm ran counts — a wave the ring dropped would show here)
    attributed = sum(s["total_s"] for s in sums)
    coverage = attributed / wall if wall else 0.0
    compiles: dict[str, float] = {}
    for key, v in kernel_compiles.samples().items():
        kern = dict(key).get("kernel", "?")
        compiles[kern] = compiles.get(kern, 0) + v
    print(
        f"# observability measured wave: {wall:.2f}s, trace covers "
        f"{coverage * 100:.1f}% ({len(sums)} wave(s), "
        f"{main['spans']} spans in the main wave)",
        file=sys.stderr,
    )
    # device-byte ledger columns (ISSUE 12 b)
    from karmada_tpu.utils.history import render_history_table
    from karmada_tpu.utils.metrics import device_bytes as device_bytes_gauge

    bytes_after = eng.device_bytes() if eng is not None else {}
    dev_samples = device_bytes_gauge.samples()
    gauge_total = sum(
        v for k, v in dev_samples.items()
        if dict(k).get("kind") in bytes_after
    )
    platforms = sorted({
        dict(k).get("platform", "?") for k in dev_samples
        if dict(k).get("kind") in bytes_after
    })
    dev_constant = bool(bytes_after) and bytes_before == bytes_after
    # gated on a non-empty ledger: an engine that never built must
    # record "not verified", never a vacuous 0 == 0 pass
    dev_matches = bool(bytes_after) and (
        int(gauge_total) == sum(bytes_after.values())
    )
    print(
        f"# observability device bytes: {bytes_after} "
        f"(steady-constant={dev_constant}, gauge-sum-matches="
        f"{dev_matches}, platform={platforms})",
        file=sys.stderr,
    )
    # the history-backed per-wave table (ISSUE 12 a)
    hist = tracer.history
    hist_rows = hist.rows(window=10)
    print(render_history_table(hist_rows), file=sys.stderr)
    record = {
        "metric": f"observability_wave_{n // 1000}kx{c}",
        "value": round(wall, 4),
        "unit": "s",
        # the tier's acceptance ratio rides the vs_baseline slot: span-
        # attributed seconds over measured wall seconds (>= 0.95 passes)
        "vs_baseline": round(coverage, 4),
        "coverage_vs_wall": round(coverage, 4),
        "trace_total_s": round(attributed, 4),
        "bindings_s": round(n / wall, 1) if wall else None,
        "works": n_works,
        "cold_wave_s": round(cold_wall, 4),
        "cold_phases": cold_summary["phases"],
        "phases": main["phases"],
        "span_counts": main["span_counts"],
        "device_s": main["device_s"],
        "compile_s": main["compile_s"],
        "host_s": main["host_s"],
        "kernel_compiles": compiles,
        "waves_in_window": len(sums),
        # ISSUE 12: device-byte ledger + per-wave history columns
        "device_bytes": {k: int(v) for k, v in sorted(bytes_after.items())},
        "device_bytes_total": int(sum(bytes_after.values())),
        "device_bytes_steady_constant": dev_constant,
        "device_bytes_matches_gauge": dev_matches,
        "device_bytes_platform": ",".join(platforms),
        "history_waves": hist.sampled,
        "history_rows": hist_rows[-8:],
        "history_digests": hist.digests(window=64)["series"],
    }
    # ISSUE 13: the provenance (explain) tier — armed-vs-disarmed storm
    # overhead, capture sizes, a live denied binding's decision chain,
    # and the flight record's worst-binding explanations
    record.update(run_explain_tier(cp, clock, storm_wave))
    del cp
    gc.collect()
    # ISSUE 10: the 4-process stitched wave + flight-recorder proof
    record.update(run_stitched_observability(args))
    return record


def run_explain_tier(cp, clock, storm_wave) -> dict:
    """ISSUE 13 acceptance phase, riding the in-proc observability
    plane: (a) the same rebalancer storm armed vs disarmed — armed runs
    ONE extra explain dispatch per pass and must stay within the
    benchguard noise band; (b) capture sizes off the ExplainStore ring;
    (c) a live FederatedResourceQuota denial whose full decision chain
    `karmadactl-tpu explain` resolves; (d) a seeded SLO-breach flight
    record carrying the wave's worst-binding explanations, re-rendered
    identically offline by `trace analyze`."""
    import os
    import tempfile

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
    )
    from karmada_tpu.utils import explainstore as _expl
    from karmada_tpu.utils.builders import (
        dynamic_weight_placement,
        new_deployment,
    )

    eng = getattr(cp.scheduler, "_engine", None)
    if eng is None:
        return {}
    _expl.reset_store()
    estore = _expl.store()

    # disarmed / armed / disarmed interleave (shared rigs drift; the
    # overhead ratio reads against the disarmed MEAN). The first armed
    # wave warms the explain kernel traces off the timed window.
    dis1, _, _ = storm_wave("explain-off1")
    eng.set_explain(estore)
    warm, _, _ = storm_wave("explain-warm")
    armed_wall, _, _ = storm_wave("explain-armed")
    caps = estore.captures()
    cap_bind = sum(c.bindings for c in caps)
    cap_bytes = sum(c.nbytes() for c in caps)
    uniq_masks = sum(len(c.uniq_masks) for c in caps)
    eng.set_explain(None)
    dis2, _, _ = storm_wave("explain-off2")
    disarmed = (dis1 + dis2) / 2
    overhead = (armed_wall / disarmed) if disarmed else None
    print(
        f"# explain tier: armed {armed_wall:.2f}s (warm {warm:.2f}s) vs "
        f"disarmed {dis1:.2f}/{dis2:.2f}s -> {overhead:.3f}x; "
        f"{cap_bind} bindings captured in {len(caps)} capture(s), "
        f"{cap_bytes / 1e6:.2f} MB interned ({uniq_masks} unique mask "
        "rows)",
        file=sys.stderr,
    )

    # a LIVE quota denial under an armed flight recorder: the denial
    # wave both resolves through `karmadactl-tpu explain` AND breaches
    # the seeded SLO, so the flight record carries THIS wave's
    # worst-binding (the denied one) explanations — re-rendered
    # identically offline by `trace analyze`
    eng.set_explain(estore)
    flight_dir = tempfile.mkdtemp(prefix="karmada_tpu_flight_expl_")
    saved = {
        k: os.environ.get(k)
        for k in ("KARMADA_TPU_TRACE_SLO_SECONDS", "KARMADA_TPU_FLIGHT_DIR")
    }
    resolved = False
    binding_doc = None
    flight_identical = None
    try:
        os.environ["KARMADA_TPU_TRACE_SLO_SECONDS"] = "0.0001"
        os.environ["KARMADA_TPU_FLIGHT_DIR"] = flight_dir
        cp.store.apply(PropagationPolicy(
            meta=ObjectMeta(name="expl-policy", namespace="expl"),
            spec=PropagationSpec(
                resource_selectors=[
                    ResourceSelector(
                        api_version="apps/v1", kind="Deployment"
                    )
                ],
                placement=dynamic_weight_placement(),
            ),
        ))
        cp.store.apply(FederatedResourceQuota(
            meta=ObjectMeta(name="q", namespace="expl"),
            spec=FederatedResourceQuotaSpec(overall={"cpu": 0}),
        ))
        cp.store.apply(
            new_deployment("explain-denied", namespace="expl", replicas=4)
        )
        clock[0] += 60
        cp.settle()
        doc = _cli.cmd_explain_placement("expl/explain-denied-deployment")
        binding_doc = doc.get("binding")
        resolved = bool(
            binding_doc
            and binding_doc.get("reason") == "QuotaExceeded"
            and "QuotaExceeded" in (binding_doc.get("stages") or {})
            and binding_doc.get("candidates")
        )
        analysis = _cli.cmd_trace_analyze(
            os.path.join(flight_dir, "flight.jsonl")
        )
        expl_ctx = analysis.get("explain")
        flight_identical = bool(analysis.get("identical")) and any(
            w.get("reason") == "QuotaExceeded"
            for w in (expl_ctx or {}).get("worst", [])
        )
    except Exception as exc:  # noqa: BLE001 — the proof is recorded,
        # never crashes the whole bench record
        print(f"# explain tier: flight proof failed: {exc!r}",
              file=sys.stderr)
        flight_identical = False
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        eng.set_explain(None)
    print(
        f"# explain tier: live denied binding resolved={resolved} "
        f"(reason={binding_doc.get('reason') if binding_doc else None})",
        file=sys.stderr,
    )
    print(
        f"# explain tier: flight record explain re-render identical="
        f"{flight_identical}",
        file=sys.stderr,
    )
    return {
        "explain_armed_wave_s": round(armed_wall, 4),
        "explain_disarmed_wave_s": round(disarmed, 4),
        "explain_overhead_x": round(overhead, 4) if overhead else None,
        "explain_captures": len(caps),
        "explain_capture_bindings": int(cap_bind),
        "explain_capture_bytes": int(cap_bytes),
        "explain_unique_masks": int(uniq_masks),
        "explain_resolved": resolved,
        "explain_denied_stage": "QuotaExceeded" if resolved else "?",
        "explain_flight_identical": flight_identical,
    }


def run_stitched_observability(args) -> dict:
    """ISSUE 10 acceptance phase: one storm wave over a LIVE 4-process
    plane — this process is the scheduler plane, writing through a real
    store-bus process, solving through a solver-sidecar process that
    itself min-merges availability from an estimator-server process
    (``--estimator``) — with the trace context propagated over every
    channel. Records the stitched wave (per-process self time,
    per-channel client/server/network columns, cross-process coverage of
    the externally measured wall), then arms the flight recorder + a
    seeded solver fault (breaker trip mid-wave) and proves the recorded
    JSONL re-renders identically offline (``trace analyze``)."""
    import os
    import tempfile

    from karmada_tpu import cli as _cli
    from karmada_tpu.api import (
        PropagationPolicy,
        PropagationSpec,
        ResourceSelector,
    )
    from karmada_tpu.api.core import ObjectMeta
    from karmada_tpu.bus.agent import ReplicaStoreFacade
    from karmada_tpu.bus.service import StoreReplica
    from karmada_tpu.controllers.extras import (
        ObjectReferenceSelector,
        WorkloadRebalancer,
        WorkloadRebalancerSpec,
    )
    from karmada_tpu.localup import scrape_line, spawn_child
    from karmada_tpu.solver.client import RemoteSolver
    from karmada_tpu.utils import faultinject
    from karmada_tpu.utils import tracing as trc
    from karmada_tpu.utils.builders import (
        dynamic_weight_placement,
        new_cluster,
        new_deployment,
    )
    from karmada_tpu.utils.tracing import tracer

    # a smaller shape than the in-proc phase: every write is now a real
    # gRPC round-trip and the point is the MEASUREMENT layer, not plane
    # throughput (the 20kx512 coverage number above stands on its own)
    n = max(min(args.bindings // 10, 2000), 256)
    c = min(args.clusters, 64)
    py = sys.executable
    procs: list = []
    flight_dir = tempfile.mkdtemp(prefix="karmada_tpu_flight_")
    saved_env = {
        k: os.environ.get(k)
        for k in ("KARMADA_TPU_TRACE_SLO_SECONDS", "KARMADA_TPU_FLIGHT_DIR",
                  "KARMADA_TPU_FAULT_SPEC", "KARMADA_TPU_FAULT_SEED",
                  "KARMADA_TPU_BUS_BATCH", "KARMADA_TPU_BUS_TEMPLATE_DELTA")
    }
    replica = solver_client = None
    try:
        # ---- the other three processes -------------------------------
        t0 = time.perf_counter()
        bus_proc = spawn_child(
            [py, "-m", "karmada_tpu.bus", "--address", "127.0.0.1:0",
             "--metrics-port", "0"],
        )
        procs.append(bus_proc)
        endpoints = json.loads(scrape_line(bus_proc, r'(\{"bus".*\})'))
        bus_port, bus_metrics = endpoints["bus"], endpoints["metrics"]

        spec = {
            f"st{i:03d}": {"cpu": 2_000_000, "memory": 4000 << 30,
                           "pods": 1_000_000}
            for i in range(c)
        }
        names = sorted(spec)
        spec_f = tempfile.NamedTemporaryFile(
            "w", suffix=".json", delete=False
        )
        json.dump(spec, spec_f)
        spec_f.close()
        est_proc = spawn_child(
            [py, "-m", "karmada_tpu.estimator", "--spec-file", spec_f.name,
             "--metrics-port", "0"],
        )
        procs.append(est_proc)
        est_port = int(scrape_line(est_proc, r"port (\d+)", timeout=120))
        est_metrics = int(scrape_line(
            est_proc, r"metrics listening on port (\d+)", timeout=30
        ))

        solver_cmd = [
            py, "-m", "karmada_tpu.solver", "--address", "127.0.0.1:0",
            "--metrics-port", "0", "--warmup-manifest", "",
        ]
        for name in names:
            solver_cmd += ["--estimator", f"{name}=127.0.0.1:{est_port}"]
        solver_proc = spawn_child(solver_cmd)
        procs.append(solver_proc)
        solver_port = int(scrape_line(
            solver_proc, r"port (\d+)", timeout=120
        ))
        solver_metrics = int(scrape_line(
            solver_proc, r"metrics listening on port (\d+)", timeout=30
        ))
        trc.register_peer("bus", f"127.0.0.1:{bus_metrics}")
        trc.register_peer("estimator", f"127.0.0.1:{est_metrics}")
        trc.register_peer("solver", f"127.0.0.1:{solver_metrics}")
        print(
            f"# stitched plane up in {time.perf_counter() - t0:.1f}s "
            f"(bus:{bus_port} estimator:{est_port} solver:{solver_port})",
            file=sys.stderr,
        )

        # ---- this process: the scheduler plane over the bus ----------
        replica = StoreReplica(f"127.0.0.1:{bus_port}")
        replica.start()
        if not replica.wait_synced(30):
            raise RuntimeError("bus replica failed to sync")
        solver_client = RemoteSolver(
            f"127.0.0.1:{solver_port}", timeout_seconds=600.0
        )
        clock = [10_000.0]
        cp = _cli.cmd_init(
            clock=lambda: clock[0],
            store=ReplicaStoreFacade(replica),
            solver=solver_client,
        )
        for name in names:
            cp.join_cluster(new_cluster(name, cpu="2000", memory="4000Gi"))
        cp.settle()
        cp.store.apply(PropagationPolicy(
            meta=ObjectMeta(name="st-policy", namespace="default"),
            spec=PropagationSpec(
                resource_selectors=[ResourceSelector(
                    api_version="apps/v1", kind="Deployment"
                )],
                placement=dynamic_weight_placement(),
            ),
        ))
        for i in range(n):
            cp.store.apply(new_deployment(f"st{i}", replicas=(i % 8) + 1))

        def settle_through_echoes() -> float:
            """Settle until the write-echo stream quiesces: a settle's
            writes become locally visible only via the bus echo, which
            can land after run_until_settled returns. The measured wall
            ends at the LAST settle that did work — the trailing idle
            probes are this harness confirming quiescence, not plane
            time."""
            t0 = time.perf_counter()
            cp.settle()
            last_work = time.perf_counter()
            idle = 0
            while idle < 3:
                time.sleep(0.05)
                if cp.settle() == 0:
                    idle += 1
                else:
                    idle = 0
                    last_work = time.perf_counter()
            return last_work - t0

        boot = settle_through_echoes()
        print(f"# stitched boot wave: {boot:.1f}s "
              f"({len(cp.store.list('Work'))} works)", file=sys.stderr)

        def storm(tag: str) -> tuple:
            clock[0] += 60
            # drain the PREVIOUS burst's echo tail until its wave closes
            # so the measured window starts clean (bounded: a stubborn
            # straggler falls through to the inherited-wave fallback)
            drain_deadline = time.monotonic() + 5.0
            while (
                tracer.open_wave() is not None
                and time.monotonic() < drain_deadline
            ):
                cp.settle()
                time.sleep(0.05)
            before = set(tracer.waves())
            # the wave open RIGHT NOW (the previous storm's echo tail
            # can keep one open past its idle probes) absorbs this
            # storm's spans — a pure id-diff would attribute the whole
            # storm to "no new wave" and read as ~0% coverage
            inherited = tracer.open_wave()
            cp.store.apply(WorkloadRebalancer(
                meta=ObjectMeta(name=f"st-storm-{tag}"),
                spec=WorkloadRebalancerSpec(workloads=[
                    ObjectReferenceSelector(kind="Deployment", name=f"st{i}")
                    for i in range(n)
                ]),
            ))
            wall = settle_through_echoes()
            new = [w for w in tracer.waves() if w not in before]
            if inherited is not None and inherited not in new:
                new.append(inherited)
            return wall, new

        for wi in range(2):
            w, _ = storm(f"warm{wi}")
            print(f"# stitched warm{wi} wave: {w:.1f}s", file=sys.stderr)

        wall, new_waves = storm("measured")
        local = trc.trace_debug_doc()
        peer_docs = trc.fetch_peer_dumps(trc.peers())
        doc = trc.stitch_dumps(local, peer_docs)
        waves = [w for w in doc["waves"] if w["wave"] in new_waves]
        attributed = sum(w["total_s"] for w in waves)
        coverage = attributed / wall if wall else 0.0
        main = max(waves, key=lambda w: w["total_s"])
        print(
            f"# stitched measured wave: {wall:.2f}s, cross-process trace "
            f"covers {coverage * 100:.1f}% across {main['procs']} "
            f"(channels: { {k: v['rpcs'] for k, v in main['channels'].items()} })",
            file=sys.stderr,
        )
        phases = main.get("phases") or {}
        top_phase = max(phases.items(), key=lambda kv: kv[1]) if phases else ("", 0.0)

        # ---- ISSUE 11: batched vs unary parity + throughput ----------
        # the whole-plane storm re-runs with the columnar channel forced
        # off (KARMADA_TPU_BUS_BATCH=0 pins every connection unary,
        # KARMADA_TPU_BUS_TEMPLATE_DELTA=0 full-renders every Work) and
        # the final plane state must be IDENTICAL: same placements, and
        # template-delta rehydration byte-equivalent to full rendering
        def plane_state():
            import copy

            from karmada_tpu.controllers.propagation import work_manifests
            from karmada_tpu.utils.codec import to_jsonable

            def canon(doc):
                doc = copy.deepcopy(doc)
                meta = doc.get("meta") or {}
                for k in ("resource_version", "uid", "creation_timestamp"):
                    meta.pop(k, None)
                for bag in ("labels", "annotations"):
                    d = meta.get(bag) or {}
                    for k in list(d):
                        if "permanent-id" in k:
                            del d[k]
                return doc

            placements = {
                rb.meta.namespaced_name: sorted(
                    (tc.name, tc.replicas) for tc in rb.spec.clusters
                )
                for rb in cp.store.list("ResourceBinding")
            }
            manifests = {}
            for w in cp.store.list("Work"):
                docs = work_manifests(cp.store, w)
                manifests[w.meta.namespaced_name] = (
                    [canon(to_jsonable(m)) for m in docs]
                    if docs
                    else None
                )
            return placements, manifests

        batched_state = plane_state()
        delta_works = sum(
            1 for w in cp.store.list("Work")
            if w.spec.workload_template is not None
            and w.spec.workload_template.digest
        )
        n_templates = len(cp.store.list("WorkloadTemplate"))
        os.environ["KARMADA_TPU_BUS_BATCH"] = "0"
        os.environ["KARMADA_TPU_BUS_TEMPLATE_DELTA"] = "0"
        unary_wall, _ = storm("unary")
        unary_state = plane_state()
        for k in ("KARMADA_TPU_BUS_BATCH", "KARMADA_TPU_BUS_TEMPLATE_DELTA"):
            if saved_env.get(k) is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = saved_env[k]
        parity = (
            batched_state[0] == unary_state[0]
            and batched_state[1] == unary_state[1]
        )
        print(
            f"# bus parity: batched wave {wall:.2f}s vs unary wave "
            f"{unary_wall:.2f}s ({unary_wall / wall if wall else 0:.1f}x), "
            f"plane state identical={parity} ({delta_works} template-delta "
            f"works over {n_templates} templates); top stitched phase "
            f"{top_phase[0]} {top_phase[1]:.2f}s",
            file=sys.stderr,
        )

        # ---- flight recorder: seeded breaker trip mid-wave -----------
        os.environ["KARMADA_TPU_FLIGHT_DIR"] = flight_dir
        os.environ["KARMADA_TPU_TRACE_SLO_SECONDS"] = "0.5"
        # seed the storm FIRST, then arm: the injections must hit the
        # CONTROLLERS' channel traffic mid-wave, not this driver's own
        # seed write. The solver errors mark passes degraded (in-proc
        # fallback); the bus errors burn the write path's 3 retry
        # attempts back-to-back, so the bus breaker TRIPS mid-wave
        # (threshold 3) and the wave's channel.breaker transition span
        # arms the recorder on its own
        clock[0] += 60
        cp.store.apply(WorkloadRebalancer(
            meta=ObjectMeta(name="st-storm-fault"),
            spec=WorkloadRebalancerSpec(workloads=[
                ObjectReferenceSelector(kind="Deployment", name=f"st{i}")
                for i in range(n)
            ]),
        ))
        faultinject.arm(
            "solver.rpc=error,count=6;bus.rpc=error,count=9",
            seed=args.chaos_seed,
        )
        fault_wall = settle_through_echoes()
        faultinject.disarm()
        del os.environ["KARMADA_TPU_TRACE_SLO_SECONDS"]
        flight_path = os.path.join(flight_dir, "flight.jsonl")
        records = (
            trc.load_flight_records(flight_path)
            if os.path.exists(flight_path)
            else []
        )
        fault_rec = next(
            (r for r in records
             if "breaker-transition" in r["reasons"]
             or "degraded-pass" in r["reasons"]),
            records[-1] if records else None,
        )
        analysis = trc.analyze_record(fault_rec) if fault_rec else {}
        flight_history = bool(
            (fault_rec or {}).get("history", {}) or {}
        ) and bool(fault_rec["history"].get("row"))
        print(
            f"# stitched fault wave: {fault_wall:.2f}s, "
            f"{len(records)} flight record(s), reasons "
            f"{fault_rec['reasons'] if fault_rec else []}, analyze "
            f"identical={analysis.get('identical')}, history context "
            f"attached={flight_history}",
            file=sys.stderr,
        )
        if analysis.get("table"):
            print(analysis["table"], file=sys.stderr)

        os.unlink(spec_f.name)
        return {
            "stitched_bindings": n,
            "stitched_clusters": c,
            "stitched_wall_s": round(wall, 4),
            "stitched_coverage_vs_wall": round(coverage, 4),
            "stitched": main,
            "stitched_waves_in_window": len(waves),
            # ISSUE 11: the columnar bus channel record — whole-plane
            # storm throughput over the REAL 4-process bus, the unary
            # re-run of the same storm (writes per-object, template
            # rendering full), and the plane-state parity verdict
            "stitched_bindings_s": round(n / wall, 1) if wall else None,
            "bus_unary_wall_s": round(unary_wall, 4),
            "bus_unary_vs_batched": (
                round(unary_wall / wall, 2) if wall else None
            ),
            "bus_parity_identical": parity,
            "bus_top_self_phase": top_phase[0],
            "bus_top_self_phase_s": round(top_phase[1], 4),
            "bus_template_delta_works": delta_works,
            "bus_templates": n_templates,
            "flight_recorded": bool(fault_rec),
            "flight_reasons": fault_rec["reasons"] if fault_rec else [],
            "flight_records": len(records),
            "flight_analyze_identical": analysis.get("identical"),
            # ISSUE 12: the record carries the breaching wave's history
            # row + recent-window digests; `trace analyze` renders the
            # breach-vs-recent table from them offline
            "flight_history_attached": flight_history,
            "flight_fault_wall_s": round(fault_wall, 4),
            # the recorder's disarmed steady-state (SLO env unset) is one
            # env read per wave boundary and zero per-span work — the
            # BENCH_r05 steady-storm path carries no recorder cost
            "recorder_disarmed_cost": "one env read per wave boundary",
        }
    finally:
        faultinject.disarm()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        trc.clear_peers()
        if solver_client is not None:
            solver_client.close()
        if replica is not None:
            replica.close()
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — teardown best-effort
                proc.kill()
        gc.collect()


# --------------------------------------------------------------------------
# --kernel-only: round-1 fused-kernel protocol (diagnostic)
# --------------------------------------------------------------------------


def run_kernel_only(args) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from karmada_tpu.ops.divide import _divide_batch
    from karmada_tpu.ops.estimate import (
        gather_profile_rows,
        general_estimate,
        merge_estimates,
    )

    b_total, c, r = args.bindings, args.clusters, args.dims
    chunk = args.chunk
    n_chunks = (b_total + chunk - 1) // chunk

    key = jax.random.key(0)
    kcap, kfeas = jax.random.split(key)
    scales = jnp.asarray([512_000, 4 << 40, 5_500, 1 << 42], jnp.int64)[:r]
    available_cap = (
        jax.random.uniform(kcap, (c, r), minval=0.05, maxval=1.0)
        * scales[None, :].astype(jnp.float32)
    ).astype(jnp.int64)
    tainted = jax.random.uniform(kfeas, (c,)) < 0.08
    profiles = jnp.stack(
        [
            jnp.asarray([250, 1 << 29, 1, 1 << 30], jnp.int64)[:r] * (p + 1)
            for p in range(8)
        ]
    )
    i_bits = max(1, (c - 1).bit_length())
    fast = (12, 5, min(c, 128), True) if 12 + 5 + i_bits <= 31 else None

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    mesh = None
    if len(devs) > 1 and chunk % len(devs) == 0:
        mesh = Mesh(np.array(devs), ("b",))
        print(f"# mesh: {len(devs)} devices over the binding axis",
              file=sys.stderr)

    def shard_rows(*arrays):
        if mesh is None:
            return arrays
        out = []
        for a in arrays:
            spec = P("b", *([None] * (a.ndim - 1)))
            out.append(
                jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))
            )
        return tuple(out)

    def gen_chunk(i, tainted_arg):
        k = jax.random.fold_in(jax.random.key(42), i)
        k1, k2, k3, k4, k5, k6, k7 = jax.random.split(k, 7)
        replicas = jax.random.randint(k1, (chunk,), 1, 100, dtype=jnp.int32)
        prof_idx = jax.random.randint(k2, (chunk,), 0, 8)
        tolerates = jax.random.uniform(k3, (chunk, 1)) < 0.30
        candidates = ~tainted_arg[None, :] | tolerates
        has_prev = jax.random.uniform(k4, (chunk, 1)) < 0.7
        sites = jax.random.randint(k5, (chunk, 8), 0, c)
        cnts = jax.random.randint(k6, (chunk, 8), 1, 30, dtype=jnp.int32)
        prev0 = (
            jnp.zeros((chunk, c), jnp.int32)
            .at[jnp.arange(chunk)[:, None], sites]
            .set(cnts)
        )
        prev = jnp.where(has_prev & candidates, prev0, 0)
        fresh = jax.random.uniform(k7, (chunk,)) < 0.05
        strategy = jnp.full((chunk,), 2, jnp.int32)
        static_w = jnp.zeros((chunk, c), jnp.int32)
        return shard_rows(
            prof_idx, strategy, replicas, candidates, static_w, prev, fresh
        )

    per_profile = general_estimate(available_cap, profiles)

    def solve_chunk(i, table, tainted_arg):
        prof_idx, strategy, replicas, candidates, static_w, prev, fresh = (
            gen_chunk(i, tainted_arg)
        )
        general = gather_profile_rows(table, prof_idx)
        avail = merge_estimates(replicas, (general,))
        assignment, unsched = _divide_batch(
            strategy, replicas, candidates, static_w, avail, prev, fresh,
            False, False, fast,
        )
        placed = (assignment > 0).sum(axis=1).astype(jnp.int32)
        total = assignment.sum(axis=1).astype(jnp.int32)
        return placed, total, unsched

    @jax.jit
    def solve_all(table, tainted_arg):
        def body(carry, i):
            return carry, solve_chunk(i, table, tainted_arg)
        _, outs = lax.scan(body, 0, jnp.arange(n_chunks))
        return outs

    import contextlib

    times = []
    jax.block_until_ready((per_profile, tainted))
    jax.tree.map(np.asarray, solve_all(per_profile, tainted))
    trace_ctx = (
        jax.profiler.trace(args.trace_dir)
        if args.trace_dir
        else contextlib.nullcontext()
    )
    with trace_ctx:
        for rep in range(args.repeats):
            t0 = time.perf_counter()
            outs = solve_all(per_profile, tainted)
            outs = jax.tree.map(np.asarray, outs)
            t1 = time.perf_counter()
            times.append(t1 - t0)
            print(f"# pass {rep}: {t1 - t0:.3f}s", file=sys.stderr)
    p50 = float(np.median(times))
    unsched = outs[2].reshape(-1)[:b_total]
    print(
        f"# kernel-only: scheduled {int((~unsched).sum())}/{b_total}",
        file=sys.stderr,
    )
    return {
        "metric": f"p50_kernel_{b_total // 1000}kx{c}_dynamic_weight",
        "value": round(p50, 4),
        "unit": "s",
        "vs_baseline": 0.0,
    }


def run_multichip(args) -> dict:
    """The real multichip tier: the production ENGINE (fleet table +
    donated residents) sharded across a device mesh at every requested
    size, against the single-device engine as the identity reference.

    Measures per mesh size: steady storm p50 (decode included — the
    placements are the pass's product), per-pass host->device upload and
    device->host fetch bytes from the fleet breakdown, and a LIVE
    donation probe (the pre-pass resident buffer must be consumed by the
    next solve — the runtime face of graftlint IR005). Runs on the default
    backend's devices; with ``--cpu`` the forced host devices share one
    physical CPU, so the p50 curve proves identity/donation/transfer
    bounds, not speedup — the record carries that note."""
    import jax

    from karmada_tpu.parallel.mesh import scheduling_mesh
    from karmada_tpu.scheduler import TensorScheduler

    sizes = _mesh_sizes(args)
    b_total, c = args.bindings, args.clusters
    devs = jax.devices()
    w = build_headline_workload(b_total, c)
    problems = w.problems

    curve: dict = {}
    uploads: dict = {}
    fetches: dict = {}
    identical: dict = {}
    donated: dict = {}
    ref = None
    full_upload = None
    for m in sizes:
        key = str(m)
        mesh = scheduling_mesh(m) if m > 1 else False
        engine = TensorScheduler(
            w.snap, chunk_size=args.chunk, mesh=mesh, trace_manifest=""
        )
        first_bd: dict = {}

        def warm_pass(i, eng=engine, bd=first_bd):
            eng.schedule(problems)
            if i == 0:
                bd.update(eng._fleet.last_breakdown)

        settle_engine(
            engine, warm_pass, floor=2, cap=8, label=f"mesh={m} warm",
        )
        if full_upload is None:
            # the cold pass ships the whole packed grid: the bound the
            # steady-pass upload must stay well below
            full_upload = round(first_bd.get("upload_mb", 0.0), 6)
        # donation probe: the resident the table holds NOW must be
        # consumed (aliased, not copied) by the next pass's solve
        fleet = engine._fleet
        resident = fleet._res_dense
        engine.schedule(problems)
        donated[key] = bool(resident.is_deleted())
        times = []
        placements = None
        for rep in range(args.repeats):
            t0 = time.perf_counter()
            res = engine.schedule(problems)
            placements = [
                (dict(r.clusters), r.success) for r in res
            ]
            times.append(time.perf_counter() - t0)
            print(
                f"# mesh={m} pass {rep}: {times[-1]:.3f}s",
                file=sys.stderr,
            )
        bd = fleet.last_breakdown
        curve[key] = round(float(np.median(times)), 4)
        uploads[key] = round(bd.get("upload_mb", 0.0), 6)
        fetches[key] = round(bd.get("fetch_mb", 0.0), 6)
        if ref is None:
            ref = placements
            identical[key] = True
        else:
            identical[key] = placements == ref
        print(
            f"# mesh={m}: p50 {curve[key]}s identical={identical[key]} "
            f"donated={donated[key]} upload {uploads[key]:.4f}MB "
            f"fetch {fetches[key]:.4f}MB",
            file=sys.stderr,
        )
        del engine, fleet, resident, res
        gc.collect()

    return {
        "metric": f"multichip_scaling_{b_total // 1000}kx{c}",
        "value": curve[str(sizes[-1])],
        "unit": "s",
        # single-device p50 over the largest mesh's p50: >1 would be a
        # real speedup; ~1 on forced-host rigs (shared physical CPU)
        "vs_baseline": round(
            curve[str(sizes[0])] / max(curve[str(sizes[-1])], 1e-9), 2
        ),
        "mesh_sizes": sizes,
        "steady_p50_s": curve,
        "identical": identical,
        "donated": donated,
        "steady_upload_mb": uploads,
        "steady_fetch_mb": fetches,
        "full_grid_upload_mb": full_upload,
        "note": (
            "real accelerator devices: the p50 curve is a genuine "
            "scaling measurement"
            if devs[0].platform != "cpu"
            else "forced host devices share one physical CPU: the curve "
            "proves placement identity, donation, and transfer bounds; "
            "real scaling needs a TPU slice"
        ),
    }


def run_sharded_kernel(args) -> dict:
    """2D-sharded kernel step: shard the cluster axis over a
    ('b','c') mesh, verify placement identity against the unsharded step,
    and measure the sort-induced c-axis collective cost."""
    import jax
    import jax.numpy as jnp

    from karmada_tpu.parallel.solver import default_mesh, make_sharded_step, schedule_step

    b_mesh, _, c_mesh = args.shard.partition("x")
    b_mesh, c_mesh = int(b_mesh), int(c_mesh or 1)
    n_dev = b_mesh * c_mesh
    mesh = default_mesh(n_dev, cluster_axis=c_mesh)
    print(f"# mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} on "
          f"{mesh.devices.flat[0].platform}", file=sys.stderr)

    b, c, r = args.bindings, args.clusters, args.dims
    rng = np.random.default_rng(0)
    scales = np.asarray([512_000, 4 << 40, 5_500, 1 << 42], np.int64)[:r]
    available_cap = (
        rng.uniform(0.05, 1.0, (c, r)) * scales[None, :]
    ).astype(np.int64)
    has_summary = np.ones(c, bool)
    requests = (
        np.asarray([250, 1 << 29, 1, 1 << 30], np.int64)[:r]
        * (rng.integers(1, 9, b))[:, None]
    )
    strategy = np.full(b, 2, np.int32)
    replicas = rng.integers(1, 100, b).astype(np.int32)
    candidates = rng.random((b, c)) < 0.9
    static_w = np.zeros((b, c), np.int32)
    prev = np.where(
        rng.random((b, c)) < 8.0 / c, rng.integers(1, 30, (b, c)), 0
    ).astype(np.int32)
    fresh = rng.random(b) < 0.05
    inputs = (available_cap, has_summary, requests, strategy, replicas,
              candidates, static_w, prev, fresh)
    statics = (False, False, None)  # has_aggregated, wide, fast

    sharded = make_sharded_step(mesh, shard_clusters=c_mesh > 1)
    ref = np.asarray(schedule_step(*inputs, *statics).assignment)
    out = sharded(*inputs, *statics)
    got = np.asarray(out.assignment)
    identical = bool(np.array_equal(ref, got))
    print(f"# identity under {args.shard} sharding: {identical}", file=sys.stderr)

    times = []
    for rep in range(args.repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(sharded(*inputs, *statics))
        times.append(time.perf_counter() - t0)
        print(f"# pass {rep}: {times[-1]:.3f}s", file=sys.stderr)
    t0 = time.perf_counter()
    jax.block_until_ready(schedule_step(*inputs, *statics))
    t_unsharded = time.perf_counter() - t0
    p50 = float(np.median(times))
    print(f"# unsharded single-device: {t_unsharded:.3f}s", file=sys.stderr)
    return {
        "metric": f"p50_sharded_{args.shard}_{b}x{c}",
        "value": round(p50, 4),
        "unit": "s",
        "vs_baseline": round(t_unsharded / p50, 2) if p50 else 0.0,
        "identical": identical,
    }


#: the device fields every record carries (on-chip-measurement guide:
#: a result names the device it ran on)
DEVICE_FIELDS = ("platform", "device_kind", "device_count")


def device_record(allow_cpu: bool) -> dict:
    """The device this process runs on, as jax reports it. Finding only
    the CPU is an error unless ``--cpu`` asked for it by name: no tier
    measures the CPU by accident."""
    import jax

    devs = jax.devices()
    rec = dict(zip(
        DEVICE_FIELDS, (devs[0].platform, devs[0].device_kind, len(devs))
    ))
    print(
        f"# device: {rec['device_count']} x {rec['platform']}:"
        f"{rec['device_kind']}",
        file=sys.stderr,
    )
    if rec["platform"] == "cpu" and not allow_cpu:
        raise SystemExit(
            "bench.py: jax found no accelerator (platform cpu). Nothing "
            "here measures the CPU by accident — pass --cpu to run on it "
            "deliberately"
        )
    return rec


def _mesh_sizes(args) -> list:
    sizes = [int(s) for s in args.mesh_sizes.split(",") if s.strip()]
    for s in sizes:
        if s & (s - 1):
            raise SystemExit(f"--mesh-sizes: {s} is not a power of two")
    return sizes


def record_failures(record: dict) -> list:
    """What in a finished record makes the run a failure: a tier whose
    status is not "ok", placement mismatches, or an identity check that
    came out False (per mesh size for the multichip tier)."""
    bad = [
        f"tier {k}: {v}"
        for k, v in (record.get("tiers") or {}).items()
        if v != "ok"
    ]
    if record.get("verified_mismatches"):
        bad.append(f"{record['verified_mismatches']} placement mismatches")
    for k, v in record.items():
        if k.endswith("identical") and (
            v is False or (isinstance(v, dict) and False in v.values())
        ):
            bad.append(f"{k}: {v}")
    return bad


def main():
    args = build_parser().parse_args()
    if args.check:
        # the guard is pure JSON comparison — no jax, no plane; it must
        # stay runnable on a laptop that cannot build an engine
        import os

        repo_root = os.path.dirname(os.path.abspath(__file__))
        if repo_root not in sys.path:
            sys.path.insert(0, repo_root)
        from tools.benchguard import main as benchguard_main

        sys.exit(benchguard_main([args.check, "--root", repo_root]))
    # per-tier default scale (see build_parser): explicit flags always win
    small = (args.observability or args.chaos or args.quota
             or args.multichip or args.preemption)
    if args.bindings is None:
        args.bindings = 20_000 if small else 100_000
    if args.clusters is None:
        args.clusters = 512 if small else 5_000
    if args.cpu:
        # JAX_PLATFORMS (and the virtual device count the mesh tiers
        # need) are read at the first jax import, which has not happened
        import __graft_entry__ as graft

        n_dev = 1
        if args.multichip:
            n_dev = max(_mesh_sizes(args))
        elif args.shard:
            b_mesh, _, c_mesh = args.shard.partition("x")
            n_dev = int(b_mesh) * int(c_mesh or 1)
        graft._cpu_env(n_dev)
    if args.cold_start:
        # the parent stays off jax: its children check the device
        record = run_cold_start(args)
    else:
        device = device_record(allow_cpu=args.cpu)
        if args.cold_child:
            record = run_cold_child(args)
        elif args.observability:
            record = run_observability(args)
        elif args.chaos:
            record = run_chaos(args)
        elif args.quota:
            record = run_quota(args)
        elif args.preemption:
            record = run_preemption(args)
        elif args.multichip:
            record = run_multichip(args)
        elif args.estimator_only:
            tier_status: dict = {}
            record = run_estimator_tier(args, tier_status)
            if tier_status:
                record["tiers"] = tier_status
        elif args.config != 5:
            record = run_engine_config(args.config)
        elif args.shard:
            record = run_sharded_kernel(args)
        elif args.kernel_only:
            record = run_kernel_only(args)
        else:
            record = run_engine_north_star(args)
        record.update(device)
    print(json.dumps(record))
    failures = record_failures(record)
    if failures:
        print("# FAILED: " + "; ".join(failures), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
