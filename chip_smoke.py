"""Chip smoke: the scheduling plane's main path, once, on the local TPU.

``python chip_smoke.py`` drives the system through the entry points a user
calls, at the full width of ``BASELINE.json`` config 5 (100k bindings x 5k
clusters), checks every result against the repo's own oracles
(``karmada_tpu.refimpl``), and prints two lines on stdout: a summary
(versions, each stage's outcome and facts, smoke wall times) and then, as the
LAST line, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It exits non-zero — and prints no result line — when jax finds no TPU, when
the ``karmada_tpu`` package is not beside it, or when any stage fails. There
is no report-and-continue: a stage raises, its child exits non-zero, the
smoke stops.

One process owns a chip at a time, so this parent never imports jax. It
runs the stages one after another as child processes (``--stage NAME``):
each owns the chip in turn and frees its HBM on exit.

    engine   100k x 5k through TensorScheduler.schedule(): warm + settle,
             3 steady passes, 1 dirty-row pass, 1 full-drift churn pass
    kernels  the quota, preemption and explain kernels through the engine
             at 20k x 512, each against its refimpl oracle; then
             scheduler.prewarm.warmup() on the manifest written so far
    plane    cli.cmd_init -> 512 joins -> 1 policy + 20k Deployments ->
             settle -> a WorkloadRebalancer storm -> settle
    sidecar  a CPU-pinned plane + ``python -m karmada_tpu.solver`` owning
             the chip (localup.spawn_child), placements equal to the
             in-process engine; then the next process takes the chip
    mesh     the engine stage on a 4-device mesh, bit-identical to mesh off
             (skipped on one device)

All children share one compile cache: ``JAX_COMPILATION_CACHE_DIR`` when it
is set, ``<checkout>/.jax_cache/<platform set>`` otherwise
(karmada_tpu.utils.compilecache) — the trace manifest lives beside it.

Wall times printed here are smoke timings — how long this script spent, on
a host whose cores it shares — not metrics of the system.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: (stage, whether the stage's own process takes the chip, seconds after
#: which it counts as hung — about twice what it took on one v5e with a
#: cold compile cache: 226, 361, 157, 101 s; the mesh stage 303 s on four
#: chips). The sidecar stage's own process is the CPU-pinned plane; the
#: solver it spawns is the one that owns the chip.
STAGES = (
    ("engine", True, 700),
    ("kernels", True, 700),
    ("plane", True, 400),
    ("sidecar", False, 300),
    ("mesh", True, 700),
)

ENGINE_SHAPE = (100_000, 5_000)  # BASELINE.json config 5
TIER_SHAPE = (20_000, 512)  # every bench tier's builder record since PR 6
#: ``--tiny`` (the CPU rehearsal of this script, tests/test_chip_smoke.py):
#: the same stages and checks at sizes a CPU finishes in seconds, on
#: whatever platform jax finds — so chip time is not spent debugging Python
TINY_ENGINE_SHAPE = (2_048, 64)
TINY_TIER_SHAPE = (512, 64)


# --------------------------------------------------------------------------
# shared by the stage bodies (jax is imported only inside them)
# --------------------------------------------------------------------------


def device_facts() -> dict:
    """Device and versions as jax reports them — on every result line."""
    import importlib.metadata as md

    import jax
    import jaxlib

    import bench

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    return {
        **bench.device_record(allow_cpu=True),  # the parent judges it
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }


def _check(cond, what: str) -> None:
    # not ``assert``: the checks must survive ``python -O``
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _manifest_path():
    """The trace manifest beside the compile cache (None = disabled)."""
    from karmada_tpu.utils import compilecache

    return compilecache.default_manifest_path() or None


def _spread(n: int, k: int) -> list:
    """>= min(n, k) row indices spread evenly over [0, n) — every chunk of
    the batch contributes."""
    return list(range(0, n, max(1, n // max(1, k))))


def _placements(results) -> list:
    return [(r.success, sorted(r.clusters.items())) for r in results]


def _select_np(snap, problems, feasible, strategy, reps, avail, prev):
    """``feasible`` narrowed, row by row, to what refimpl.spread's
    SelectClusters oracle selects for the rows whose placement carries
    spread constraints (none selected = FitError). Static weights ignore
    them (select_clusters.go:63-78); Duplicated ignores availability."""
    import numpy as np

    from karmada_tpu.refimpl.divider import DUPLICATED, STATIC_WEIGHT
    from karmada_tpu.refimpl.spread import select_spread_clusters

    region_of = {j: cl.spec.region for j, cl in enumerate(snap.clusters)}
    out = feasible.copy()
    for k, p in enumerate(problems):
        scs = p.placement.spread_constraints if p.placement else ()
        if not scs or strategy[k] == STATIC_WEIGHT:
            continue
        sel = select_spread_clusters(
            np.flatnonzero(feasible[k]).tolist(), region_of,
            {int(j): 100 for j in np.flatnonzero(prev[k] > 0)},
            {j: int(avail[k, j]) + int(prev[k, j])
             for j in range(feasible.shape[1])},
            {sc.spread_by_field: (sc.min_groups, sc.max_groups) for sc in scs},
            int(reps[k]), strategy[k] == DUPLICATED,
        )
        out[k] = False
        out[k, sel or []] = True
    return out


def _numpy_mismatches(snap, problems, results, host_eng, idx) -> int:
    """Rows of ``idx`` whose engine placement differs from the vectorized
    numpy divider (refimpl.divider_np) over the general estimator's
    availability and, for spread-constrained rows, refimpl.spread's
    selection — the bench's full-set check, on a sample."""
    import numpy as np

    import bench
    from karmada_tpu.refimpl.divider_np import assign_batch_np
    from karmada_tpu.refimpl.failover_np import solve_one_ordered

    bad = 0
    names = snap.names
    cap_np = snap.available_cap
    for start in range(0, len(idx), 1024):
        part = idx[start:start + 1024]
        sub = [problems[i] for i in part]
        feasible, strategy, reps, static_w, requests, prev, fresh = (
            bench._oracle_inputs(snap, sub, host_eng)
        )
        uniq, inv = np.unique(requests, axis=0, return_inverse=True)
        avail = bench._general_avail_np(cap_np, uniq)[inv]
        avail = np.minimum(
            np.where(avail == 2**31 - 1, reps[:, None], avail), 2**31 - 1
        ).astype(np.int32)
        # a binding without replicas asks the estimators nothing
        avail = np.where(reps[:, None] == 0, 0, avail)
        feasible = _select_np(
            snap, sub, feasible, strategy, reps, avail, prev
        )
        got, unsched = assign_batch_np(
            strategy, reps, feasible, static_w, avail, prev, fresh
        )
        # rows under ordered affinity terms go through the reference's own
        # retry loop (try a group, divide, on failure the next)
        ordered = [k for k, p in enumerate(sub) if p.placement is not None
                   and len(p.placement.cluster_affinities) > 1]
        if ordered:
            compiled = [host_eng._compiled(p.placement) for p in sub]
            base = host_eng._pack_chunk(sub, compiled, 0, with_affinity=False)[0]
        for k, i in enumerate(part):
            res = results[i]
            if k in ordered:
                terms = compiled[k].terms
                row, ti, err = solve_one_ordered(
                    np.stack([m for _, m in terms]), base[k], strategy[k],
                    reps[k], static_w[k], avail[k], prev[k], fresh[k])
                good = res.affinity_name == terms[ti][0] and (
                    not res.success if err else res.success
                    and dict(res.clusters) == {
                        names[j]: int(row[j]) for j in np.flatnonzero(row)})
            elif unsched[k] or not feasible[k].any():
                good = not res.success
            else:
                want = {
                    names[j]: int(got[k, j]) for j in np.flatnonzero(got[k])
                }
                good = res.success and dict(res.clusters) == want
            bad += not good
    return bad


def _drift(clusters, seed: int):
    """Every cluster's allocation moves by -3..3 x 0.5% of its allocatable
    a dim, in place; returns the snapshot of the moved fleet."""
    import numpy as np

    from karmada_tpu.scheduler import ClusterSnapshot

    rng = np.random.default_rng(seed)
    for cl in clusters:
        rs = cl.status.resource_summary
        for dim, q in list(rs.allocated.items()):
            alloc = rs.allocatable.get(dim, 0)
            step = int(rng.integers(-3, 4)) * max(1, alloc // 200)
            rs.allocated[dim] = int(min(max(0, q + step), alloc))
    return ClusterSnapshot(clusters)


#: placements of the engine stage's mixed-policy batch (_policy_problems);
#: the last two hold two ordered affinity terms, so the fleet table holds
#: one slot more for each of them (a slot a (placement, term))
N_POLICIES = 8
N_POLICY_SLOTS = N_POLICIES + 2


def _policy_problems(clusters, n: int) -> list:
    """``n`` bindings under the documented policy kinds side by side:
    Duplicated under a label selector, dynamic and static weights,
    Aggregated, dynamic weight / Aggregated under spread constraints, and
    dynamic weight / Aggregated under two ordered affinity terms (primary: a
    region, then a backup), a share of whose rows hold eviction tasks."""
    import numpy as np

    from karmada_tpu.api.policy import (
        ClusterAffinity,
        ClusterAffinityTerm,
        FieldSelector,
        LabelSelector,
        LabelSelectorRequirement,
        SpreadConstraint,
    )
    from karmada_tpu.scheduler import BindingProblem
    from karmada_tpu.utils import builders

    rng = np.random.default_rng(31)
    names = [cl.name for cl in clusters]
    weighted = rng.choice(len(names), min(15, len(names)), replace=False)

    def sc(field, lo, hi):
        return SpreadConstraint(
            spread_by_field=field, min_groups=lo, max_groups=hi
        )

    regions = sorted({cl.spec.region for cl in clusters if cl.spec.region})

    def term(name, region=None):
        return ClusterAffinityTerm(
            affinity_name=name,
            field_selector=FieldSelector(match_expressions=[
                LabelSelectorRequirement(
                    key="region", operator="In", values=[region])])
            if region else None)

    policies = [
        builders.duplicated_placement(cluster_affinity=ClusterAffinity(
            label_selector=LabelSelector(match_labels={"env": "prod"}))),
        builders.dynamic_weight_placement(),
        builders.static_weight_placement(
            {names[j]: 1 + int(j) % 4 for j in weighted}),
        builders.aggregated_placement(),
        builders.dynamic_weight_placement(spread_constraints=[
            sc("region", 2, 3), sc("cluster", 3, 6)]),
        builders.aggregated_placement(spread_constraints=[
            sc("cluster", 2, 4)]),
        builders.dynamic_weight_placement(cluster_affinities=[
            term("primary", regions[0]), term("backup", regions[-1])]),
        builders.aggregated_placement(cluster_affinities=[
            term("primary", regions[-1]), term("backup")]),
    ]
    assert len(policies) == N_POLICIES
    kinds = rng.choice(
        N_POLICIES, n, p=[0.27, 0.22, 0.13, 0.18, 0.05, 0.05, 0.05, 0.05])
    primary = {
        6: [j for j, cl in enumerate(clusters) if cl.spec.region == regions[0]],
        7: [j for j, cl in enumerate(clusters) if cl.spec.region == regions[-1]],
    }
    problems = []
    for i in range(n):
        size = 1 + i % 8
        pool = primary.get(kinds[i], range(len(names)))
        held = (
            rng.choice(pool, min(len(pool), int(rng.integers(1, 5))),
                       replace=False)
            if rng.random() < 0.7 else ()
        )
        # half the failover rows were evicted from some of what they held,
        # one in eight from their whole primary region (it falls back)
        evict = ()
        if kinds[i] in primary and rng.random() < 0.5:
            evict = tuple(names[j] for j in held[: 1 + len(held) // 2])
            held = held[len(evict):]
            if rng.random() < 0.25:
                evict = tuple(names[j] for j in primary[kinds[i]][:8])
        problems.append(BindingProblem(
            key=f"policy-{i}", placement=policies[kinds[i]],
            replicas=int(rng.integers(1, 40)),
            requests={"cpu": 250 * size, "memory": (512 << 20) * size},
            gvk="apps/v1/Deployment",
            prev={names[j]: int(rng.integers(1, 9)) for j in held},
            evict_clusters=evict,
            fresh=bool(rng.random() < 0.05),
        ))
    return problems


# --------------------------------------------------------------------------
# stage: engine
# --------------------------------------------------------------------------


def stage_engine(
    b: int = ENGINE_SHAPE[0],
    c: int = ENGINE_SHAPE[1],
    *,
    numpy_rows: int = 4096,
    oracle_rows: int = 128,
    mesh=None,
    keep: dict | None = None,
) -> dict:
    """The hot path: build_headline_workload -> TensorScheduler.schedule.

    Returns the stage's facts. The mesh stage passes ``mesh`` and a
    ``keep`` dict, which receives the engine and the steady placements."""
    import jax
    import numpy as np

    import bench
    from karmada_tpu import native
    from karmada_tpu.scheduler import BindingProblem, TensorScheduler

    timings: dict = {}
    t0 = time.perf_counter()
    w = bench.build_headline_workload(b, c)
    problems = w.problems
    timings["build_s"] = time.perf_counter() - t0

    engine = TensorScheduler(
        w.snap, chunk_size=4096, trace_manifest=_manifest_path(), mesh=mesh
    )
    t0 = time.perf_counter()
    engine.schedule(problems)
    timings["warm_pass_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    settle = bench.settle_engine(
        engine, lambda i: engine.schedule(problems),
        floor=2, cap=12, label="smoke settle",
    )
    timings["settle_s"] = time.perf_counter() - t0
    _check(not engine.last_pass_new_trace, "engine never settled: the last "
           f"of {settle} settle passes still dispatched a fresh trace")

    steady = []
    results = None
    for _ in range(3):
        t0 = time.perf_counter()
        results = engine.schedule(problems)
        steady.append(time.perf_counter() - t0)
        _check(not engine.last_pass_new_trace,
               "a steady pass dispatched a fresh trace")
    timings["steady_pass_s"] = steady
    if keep is not None:
        keep.update(engine=engine, steady=_placements(results))

    # every row rode the device-resident fleet table: the batch-identity
    # token is armed only when no row fell to _schedule_host
    fleet = engine._fleet
    _check(fleet is not None, "the fleet table never engaged")
    _check(
        fleet.batch.armed and len(fleet.batch.problems) == b,
        f"not all {b} rows rode the fleet table",
    )
    platform = jax.devices()[0].platform
    _check(
        fleet._buffer_platform() == platform,
        f"fleet buffers live on {fleet._buffer_platform()!r}, "
        f"not {platform!r}",
    )
    if mesh is not None:
        _check(fleet._mesh is mesh, "the fleet's mesh-divisibility guard "
               "fired: the table runs single-device")

    host_eng = TensorScheduler(w.snap, mesh=False)
    np_idx = _spread(b, numpy_rows)
    oracle_idx = _spread(b, oracle_rows)
    t0 = time.perf_counter()
    np_bad = _numpy_mismatches(w.snap, problems, results, host_eng, np_idx)
    _, oracle_bad = bench._verify_rows(
        w.snap, problems, results, host_eng, oracle_idx
    )
    timings["verify_steady_s"] = time.perf_counter() - t0
    _check(np_bad == 0, f"{np_bad}/{len(np_idx)} steady rows differ from "
           "the numpy divider")
    _check(oracle_bad == 0, f"{oracle_bad}/{len(oracle_idx)} steady rows "
           "differ from refimpl.assign_replicas")

    # ---- dirty-row pass: ~1% of the keys changed, named by dirty_keys.
    # It runs BEFORE the churn pass: an availability swap leaves the
    # engine's delta base on the old snapshot generation until a full
    # prologue re-arms it, so a delta right after a churn runs full.
    rng = np.random.default_rng(2021)
    dirty = np.sort(rng.choice(b, max(1, b // 100), replace=False))
    problems2 = list(problems)
    for i in dirty:
        p = problems[i]
        problems2[i] = BindingProblem(
            key=p.key, placement=p.placement,
            replicas=(p.replicas % 99) + 1, requests=p.requests,
            gvk=p.gvk, prev=p.prev, fresh=p.fresh,
        )
    prior = {i: (results[i].success, dict(results[i].clusters))
             for i in oracle_idx}
    t0 = time.perf_counter()
    results2 = engine.schedule(
        problems2, dirty_keys={problems2[i].key for i in dirty}
    )
    timings["dirty_pass_s"] = time.perf_counter() - t0
    bd = fleet.last_breakdown
    packed = int(bd.get("rows_packed", -1))
    replayed = int(bd.get("rows_replayed", -1))
    _check(
        int(bd.get("dirty_rows", -1)) == len(dirty)
        and packed == len(dirty) and replayed == b - len(dirty),
        f"the delta pass packed {packed} / replayed {replayed} rows "
        f"(dirty_rows={bd.get('dirty_rows')}) for a {len(dirty)}-row dirty "
        f"set of {b}",
    )
    dirty_bad = _numpy_mismatches(
        w.snap, problems2, results2, host_eng, [int(i) for i in dirty]
    )
    _check(dirty_bad == 0, f"{dirty_bad}/{len(dirty)} dirty rows differ "
           "from the numpy divider")
    dirty_set = set(int(i) for i in dirty)
    moved = sum(
        1 for i in oracle_idx
        if i not in dirty_set
        and (results2[i].success, dict(results2[i].clusters)) != prior[i]
    )
    _check(moved == 0, f"{moved} untouched rows changed in the delta pass")

    # ---- churn pass: every cluster's allocation drifts, the snapshot
    # swaps in place (update_snapshot), every row re-divides
    drifted = _drift(w.clusters, 99)
    t0 = time.perf_counter()
    _check(engine.update_snapshot(drifted), "update_snapshot refused an "
           "availability-only drift")
    results3 = engine.schedule(problems2)
    timings["churn_pass_s"] = time.perf_counter() - t0
    _check(engine._fleet is fleet, "the fleet table did not survive the "
           "availability swap")
    churn_idx = _spread(b, 1024)
    churn_bad = _numpy_mismatches(
        drifted, problems2, results3, TensorScheduler(drifted, mesh=False),
        churn_idx,
    )
    _check(churn_bad == 0, f"{churn_bad}/{len(churn_idx)} churn rows "
           "differ from the numpy divider")

    # ---- mixed-policy batch: the documented policy kinds side by side,
    # two of them under spread constraints, over two snapshot generations.
    # A spread selection is row state of the fleet table: the placement
    # table keeps one slot a placement however the selections move.
    pol_problems = _policy_problems(w.clusters, min(b, 2048))
    pol_eng = TensorScheduler(
        drifted, chunk_size=4096, trace_manifest=_manifest_path(), mesh=mesh
    )
    pol_idx = _spread(len(pol_problems), 512)
    t0 = time.perf_counter()
    pol_bad, pol_slots = 0, []
    for gen_snap in (drifted, _drift(w.clusters, 7)):
        _check(pol_eng.update_snapshot(gen_snap), "update_snapshot refused "
               "an availability-only drift")
        pol_res = pol_eng.schedule(pol_problems)
        _check(pol_eng._fleet is not None, "the mixed-policy batch never "
               "engaged the fleet table")
        pol_slots.append(len(pol_eng._fleet._cp_pl))
        pol_bad += _numpy_mismatches(
            gen_snap, pol_problems, pol_res,
            TensorScheduler(gen_snap, mesh=False), pol_idx,
        )
    timings["policy_passes_s"] = time.perf_counter() - t0
    # the spread rows' Select stage ran on the device (the fleet table's
    # own kernel) unless the federation holds more regions than its table
    pol_select = pol_eng._fleet.batch.select_rows
    pol_device_rows = pol_select.n if pol_select is not None else 0
    _check(
        pol_device_rows > 0 or pol_eng._fleet._dev_spread is None,
        "the mixed-policy batch's spread rows were not selected on the "
        "device though the snapshot's regions fit the kernel's table",
    )
    _check(pol_bad == 0, f"{pol_bad}/{2 * len(pol_idx)} mixed-policy rows "
           "differ from refimpl (divider_np + spread)")
    _check(
        pol_slots[0] == pol_slots[1] <= N_POLICY_SLOTS,
        f"the placement table went {pol_slots} slots over two snapshot "
        f"generations of {N_POLICIES} placements ({N_POLICY_SLOTS} terms)",
    )
    # the rows under ordered affinity terms had their term chosen on the
    # device (the fleet table's term kernel), at this stage's width
    pol_terms = pol_eng._fleet.batch.terms
    failover_rows = pol_terms.n if pol_terms is not None else 0
    _check(
        failover_rows == sum(
            1 for p in pol_problems if len(p.placement.cluster_affinities) > 1),
        "the mixed-policy batch's multi-term rows did not all ride the "
        "fleet table's term kernel",
    )

    lib = native.get()
    no_native = os.environ.get("KARMADA_TPU_NO_NATIVE") == "1"
    _check(lib is not None or no_native, "the native fold (karmada_tpu/"
           "native/fold.c) did not build or load; the numpy fallback ran")
    mem = jax.devices()[0].memory_stats() or {}
    return {
        "shape": f"{b}x{c}",
        "rows_on_fleet": f"{len(fleet.batch.problems)}/{b}",
        "buffer_platform": fleet._buffer_platform(),
        "settle_passes": settle,
        "numpy_checked": len(np_idx) + len(dirty) + len(churn_idx),
        "policy_rows_checked": 2 * len(pol_idx),
        "policy_slots": pol_slots[1],
        "policy_rows_device_selected": pol_device_rows,
        "failover_rows_device_chosen": failover_rows,
        "oracle_checked": len(oracle_idx),
        "mismatches": 0,
        "delta_rows_packed": packed,
        "delta_rows_replayed": replayed,
        "scheduled": sum(1 for r in results3 if r.success),
        "native_fold": "loaded" if lib is not None else "disabled by env",
        "hbm_peak_bytes": mem.get("peak_bytes_in_use", "not reported"),
        "hbm_limit_bytes": mem.get("bytes_limit", "not reported"),
        "device_bytes": engine.device_bytes(),
        "smoke_timings": timings,
    }


# --------------------------------------------------------------------------
# stage: kernels
# --------------------------------------------------------------------------


def _tier_snapshot(c: int, *, saturated: bool = False):
    from karmada_tpu.scheduler import ClusterSnapshot
    from karmada_tpu.utils.builders import new_cluster

    clusters = []
    for i in range(c):
        cpu = str(2000 + 8 * (i % 37))
        clusters.append(new_cluster(
            f"k{i:04d}", cpu=cpu, memory="4000Gi", pods=1_000_000,
            **({"allocated": {"cpu": cpu}} if saturated else {}),
        ))
    return ClusterSnapshot(clusters)


def _quota_wave(b: int, c: int, n_ns: int = 32) -> tuple:
    """One quota'd wave: quota_admit partitions it, quota_cluster_caps
    folds the static-assignment caps into availability; admission AND
    placements against refimpl.quota_np + the numpy divider. Returns
    (facts, engine, problems) — the explain pass re-runs the wave."""
    import numpy as np

    from karmada_tpu.api.core import ObjectMeta
    from karmada_tpu.api.policy import (
        FederatedResourceQuota,
        FederatedResourceQuotaSpec,
        StaticClusterAssignment,
    )
    from karmada_tpu.refimpl.divider_np import assign_batch_np
    from karmada_tpu.refimpl.quota_np import (
        admit_wave_np,
        asking_ns_ids,
        cluster_caps_seq,
    )
    from karmada_tpu.scheduler import (
        QUOTA_EXCEEDED_ERROR,
        BindingProblem,
        TensorScheduler,
        build_quota_snapshot,
    )
    from karmada_tpu.scheduler.quota import per_replica_vector
    from karmada_tpu.scheduler.snapshot import compile_placement
    from karmada_tpu.utils.builders import dynamic_weight_placement
    from karmada_tpu.utils.quantity import parse_resource_list

    snap = _tier_snapshot(c)
    names = snap.names
    pl = dynamic_weight_placement()
    req = parse_resource_list({"cpu": "500m", "memory": "512Mi"})
    namespaces = [f"nsq{k:02d}" for k in range(n_ns)]
    capped = namespaces[:min(4, n_ns)]
    # each namespace's new demand is (b / n_ns) rows x ~2.5 replicas x
    # 500m; a limit of 40% of it denies the tail of every FIFO queue
    per_ns = -(-b // n_ns)
    limit = int(per_ns * 2.5 * req["cpu"] * 0.4)
    frqs = [
        FederatedResourceQuota(
            meta=ObjectMeta(name="quota", namespace=ns),
            spec=FederatedResourceQuotaSpec(
                overall={"cpu": limit},
                static_assignments=(
                    [StaticClusterAssignment(
                        cluster_name=names[0], hard={"cpu": 2000}
                    )]
                    if ns in capped else []
                ),
            ),
        )
        for ns in namespaces
    ]
    rng = np.random.default_rng(8)
    held = rng.random(b) < 0.5  # half hold a replica already: delta demand
    problems = [
        BindingProblem(
            key=f"{namespaces[i % n_ns]}/w{i}", placement=pl,
            replicas=(i % 4) + 1, requests=req, gvk="apps/v1/Deployment",
            prev={names[int(rng.integers(0, c))]: 1} if held[i] else {},
            namespace=namespaces[i % n_ns],
        )
        for i in range(b)
    ]
    engine = TensorScheduler(
        snap, chunk_size=4096, trace_manifest=_manifest_path()
    )
    quota = build_quota_snapshot(frqs, snap, generation=1)
    engine.set_quota(quota)
    remaining = quota.remaining.copy()
    ns_index = dict(quota.ns_index)
    results = engine.schedule(problems)
    kernels = {k[0] for k in engine._engine_traces}
    _check({"Q", "K"} <= kernels, "quota_admit / quota_cluster_caps were "
           f"not dispatched (engine traces: {sorted(kernels)})")

    dims = list(snap.dims)
    req_vec = per_replica_vector(req, dims)
    ns_ids = [ns_index.get(p.namespace, -1) for p in problems]
    demand = np.zeros((b, len(dims)), np.int64)
    for i, p in enumerate(problems):
        delta = p.replicas - sum(p.prev.values())
        if delta > 0:
            demand[i] = req_vec * delta
    want_admit, _ = admit_wave_np(
        asking_ns_ids(ns_ids, demand), demand, remaining
    )
    got_admit = [r.error != QUOTA_EXCEEDED_ERROR for r in results]
    adm_bad = sum(1 for w_, g in zip(want_admit, got_admit) if w_ != g)
    denied = got_admit.count(False)
    _check(adm_bad == 0, f"{adm_bad}/{b} admission decisions differ from "
           "refimpl.quota_np.admit_wave_np")
    _check(0 < denied < b, f"the wave denied {denied}/{b}: both outcomes "
           "must occur")

    cpl = compile_placement(pl, snap)
    base_mask = cpl.terms[0][1] & cpl.taint_ok & cpl.spread_field_ok
    caps = np.full((1, c, len(dims)), 2**62, np.int64)
    caps[0, 0, dims.index("cpu")] = 2000
    cap_row = cluster_caps_seq(caps, 0, req_vec).astype(np.int64)
    adm_idx = [i for i in range(b) if want_admit[i]]
    n = len(adm_idx)
    reps = np.fromiter((problems[i].replicas for i in adm_idx), np.int32, n)
    prev = np.zeros((n, c), np.int32)
    avail = np.zeros((n, c), np.int64)
    avail_rows: dict = {}
    for row, i in enumerate(adm_idx):
        p = problems[i]
        for name, r_prev in p.prev.items():
            prev[row, snap.index[name]] = r_prev
        a = avail_rows.get(p.replicas)
        if a is None:
            a = engine._availability_np(
                req_vec[None, :], np.asarray([p.replicas], np.int32)
            )[0].astype(np.int64)
            avail_rows[p.replicas] = a
        avail[row] = np.minimum(a, cap_row) if p.namespace in capped else a
    assignment, unsched = assign_batch_np(
        np.full(n, cpl.strategy, np.int32), reps,
        np.broadcast_to(base_mask, (n, c)), np.zeros((n, c), np.int32),
        np.minimum(avail, 2**31 - 1).astype(np.int32), prev,
        np.zeros(n, bool),
    )
    pl_bad = 0
    for row, i in enumerate(adm_idx):
        res = results[i]
        if unsched[row]:
            pl_bad += res.success
            continue
        want = {
            names[j]: int(assignment[row, j])
            for j in np.flatnonzero(assignment[row] > 0)
        }
        pl_bad += not (res.success and dict(res.clusters) == want)
    _check(pl_bad == 0, f"{pl_bad}/{n} admitted placements differ from "
           "the numpy divider over cap-folded availability")
    facts = {"rows": b, "namespaces": n_ns, "denied": denied,
             "admission_checked": b, "placements_checked": n}
    return facts, engine, problems


def _preempt_wave(b: int, c: int) -> dict:
    """One preemption wave: a saturated fleet, ``b`` priority-0 residents
    as the victim pool, a high-priority surge that cannot fit;
    preempt_select picks victims and the surge re-solves in the same pass
    — victims AND placements against refimpl.preempt_np."""
    import numpy as np

    from karmada_tpu.refimpl.preempt_np import preempt_and_place_np
    from karmada_tpu.scheduler import BindingProblem, TensorScheduler
    from karmada_tpu.scheduler.quota import per_replica_vector
    from karmada_tpu.scheduler.snapshot import compile_placement
    from karmada_tpu.utils.builders import dynamic_weight_placement
    from karmada_tpu.utils.quantity import parse_resource_list

    snap = _tier_snapshot(c, saturated=True)
    names = snap.names
    pl = dynamic_weight_placement()
    req = parse_resource_list({"cpu": "500m", "memory": "512Mi"})
    rng = np.random.default_rng(14)
    pool = []
    for i in range(b):
        sites = rng.choice(c, int(rng.integers(1, 4)), replace=False)
        prev = {names[int(j)]: int(rng.integers(1, 5)) for j in sites}
        pool.append(BindingProblem(
            key=f"low/w{i}", placement=pl, replicas=sum(prev.values()),
            requests=req, gvk="apps/v1/Deployment", prev=prev, priority=0,
        ))
    n_hi = max(256, b // 20)  # >= the fleet threshold: the surge's first
    # solve rides the device path like any storm
    surge = [
        BindingProblem(
            key=f"high/h{i}", placement=pl, replicas=(i % 4) + 1,
            requests=req, gvk="apps/v1/Deployment", priority=100,
        )
        for i in range(n_hi)
    ]
    engine = TensorScheduler(
        snap, chunk_size=4096, trace_manifest=_manifest_path()
    )
    engine.set_preemption(lambda exclude: pool)
    results = engine.schedule(surge)
    outcome = engine.last_preemption
    # the pass logs and swallows its own failures: an outcome with
    # victims is the proof the kernel ran
    _check(outcome is not None and outcome.victims, "the preemption pass "
           "selected no victim (it logs and swallows its own failures)")
    _check(any(k[0] == "P" for k in engine._engine_traces),
           "preempt_select was not dispatched")

    dims = list(snap.dims)
    rows = surge + pool
    vec = per_replica_vector(req, dims)
    zeros = np.zeros(len(dims), np.int64)
    demand = np.stack(
        [vec * p.replicas for p in surge] + [zeros] * len(pool)
    )
    weights = [0] * n_hi + [sum(p.prev.values()) for p in pool]
    freed = np.stack([zeros] * n_hi + [vec * w_ for w_ in weights[n_hi:]])
    cpl = compile_placement(pl, snap)
    base_mask = np.asarray(
        cpl.terms[0][1] & cpl.taint_ok & cpl.spread_field_ok
    )
    want_victims, want_placed = preempt_and_place_np(
        [p.key for p in rows], [p.priority for p in rows], demand, freed,
        [False] * n_hi + [True] * len(pool), weights,
        names=names,
        assigned={p.key: p.prev for p in pool},
        requests={p.key: vec for p in rows},
        base_caps=np.asarray(snap.available_cap).copy(),
        demanders=[p.key for p in surge],
        candidates={p.key: base_mask for p in surge},
        strategies={p.key: int(cpl.strategy) for p in surge},
        replicas={p.key: p.replicas for p in surge},
        prev={p.key: {} for p in surge},
    )
    got_victims = {key for key, _prev, _prio in outcome.victims}
    vic_bad = len(got_victims ^ set(want_victims))
    _check(vic_bad == 0, f"{vic_bad} victims differ from "
           "refimpl.preempt_np.select_victims_np")
    pl_bad = sum(
        1 for p, res in zip(surge, results)
        if (dict(res.clusters) if res.success else {})
        != want_placed.get(p.key, {})
    )
    _check(pl_bad == 0, f"{pl_bad}/{n_hi} surge placements differ from "
           "refimpl.preempt_np.preempt_and_place_np")
    _check(outcome.placed, "no demander was placed on the freed capacity")
    return {"pool": b, "surge": n_hi, "victims": len(got_victims),
            "placed": len(outcome.placed)}


def _explain_pass(engine, problems, rows_per_chunk: int = 64) -> dict:
    """One armed explain pass over the quota'd wave (denied rows, capped
    clusters and plain rows in one batch): explain_pass's exclusion masks
    and top-k summaries against refimpl.explain_np on rows of every
    chunk."""
    import numpy as np

    from karmada_tpu.ops import explain as explain_ops
    from karmada_tpu.refimpl.explain_np import explain_batch_np
    from karmada_tpu.utils import explainstore

    calls: list = []
    kernel = explain_ops.explain_pass

    def recording(*arrays, **statics):
        out = kernel(*arrays, **statics)
        calls.append(([np.asarray(a) for a in arrays], statics,
                      [np.asarray(o) for o in out]))
        return out

    explainstore.reset_store()
    store = explainstore.store()
    explain_ops.explain_pass = recording
    try:
        engine.set_explain(store)
        engine.schedule(problems)
    finally:
        explain_ops.explain_pass = kernel
        engine.set_explain(None)
    n_chunks = -(-len(problems) // engine.chunk_size)
    # the capture logs and swallows its own failures: count what landed
    captured = sum(cap.bindings for cap in store.captures())
    _check(len(calls) == n_chunks and captured == len(problems),
           f"explain captured {captured}/{len(problems)} bindings in "
           f"{len(calls)}/{n_chunks} dispatches")
    checked = 0
    for arrays, statics, (masks, topk) in calls:
        live = min(rows_per_chunk, arrays[0].shape[0])
        want_masks, want_topk = explain_batch_np(
            *(a[:live] for a in arrays), statics["k"]
        )
        _check(
            np.array_equal(masks[:live], want_masks)
            and np.array_equal(topk[:live], want_topk),
            "explain_pass differs from refimpl.explain_np",
        )
        checked += live
    return {"rows": len(problems), "dispatches": len(calls),
            "oracle_checked": checked}


def stage_kernels(b: int = TIER_SHAPE[0], c: int = TIER_SHAPE[1]) -> dict:
    """The three kernel families through the engine, then the manifest
    prewarm: every trace the stages RECORDED so far is executed on zeros.
    The synthesized neighbour buckets (``expand``) stay out: each is a
    fresh compile of a 100k x 5k kernel, minutes the smoke's time limit
    does not have."""
    from karmada_tpu.scheduler import prewarm

    timings: dict = {}
    t0 = time.perf_counter()
    quota, engine, problems = _quota_wave(b, c)
    timings["quota_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    explain = _explain_pass(engine, problems)
    timings["explain_s"] = time.perf_counter() - t0
    del engine, problems
    t0 = time.perf_counter()
    preempt = _preempt_wave(b, c)
    timings["preempt_s"] = time.perf_counter() - t0

    out = {"shape": f"{b}x{c}", "quota": quota, "explain": explain,
           "preempt": preempt}
    manifest = _manifest_path()
    if manifest:
        t0 = time.perf_counter()
        stats = prewarm.warmup(manifest, expand=False)
        timings["warmup_s"] = time.perf_counter() - t0
        _check(
            stats["failed"] == 0 and stats["compiled"] == stats["specs"] > 0,
            f"prewarm of {manifest}: {stats['failed']} failed, "
            f"{stats['compiled']}/{stats['specs']} compiled "
            f"({stats.get('errors')})",
        )
        out["warmup"] = {k: stats[k] for k in
                         ("records", "specs", "compiled", "failed")}
        out["warmup"]["expand"] = False
    else:
        out["warmup"] = "skipped: trace manifest disabled by env"
    out["smoke_timings"] = timings
    return out


# --------------------------------------------------------------------------
# stage: plane
# --------------------------------------------------------------------------


def _build_plane(cp, n: int, c: int):
    """c joins, one dynamic-weight policy, n Deployments (the caller
    settles)."""
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

    for i in range(c):
        cp.join_cluster(
            new_cluster(f"sm{i:04d}", cpu="2000", memory="4000Gi")
        )
    cp.settle()
    cp.store.apply(PropagationPolicy(
        meta=ObjectMeta(name="smoke-policy", namespace="default"),
        spec=PropagationSpec(
            resource_selectors=[
                ResourceSelector(api_version="apps/v1", kind="Deployment")
            ],
            placement=dynamic_weight_placement(),
        ),
    ))
    for i in range(n):
        cp.store.apply(new_deployment(f"smk{i}", replicas=(i % 8) + 1))


def _storm(cp, n: int, tag: str) -> None:
    from karmada_tpu.api.core import ObjectMeta
    from karmada_tpu.controllers.extras import (
        ObjectReferenceSelector,
        WorkloadRebalancer,
        WorkloadRebalancerSpec,
    )

    cp.store.apply(WorkloadRebalancer(
        meta=ObjectMeta(name=f"smoke-storm-{tag}"),
        spec=WorkloadRebalancerSpec(workloads=[
            ObjectReferenceSelector(kind="Deployment", name=f"smk{i}")
            for i in range(n)
        ]),
    ))


def _check_bindings(cp, n: int, sample: int = 256) -> int:
    """Sampled ResourceBindings: at their latest generation, replicas
    summing to the template, a Work in every assigned cluster."""
    idx = _spread(n, sample)
    for i in idx:
        key = f"default/smk{i}-deployment"
        rb = cp.store.get("ResourceBinding", key)
        _check(rb is not None and rb.spec.clusters, f"{key} never divided")
        _check(
            rb.status.scheduler_observed_generation == rb.meta.generation,
            f"{key} is not at its latest generation",
        )
        total = sum(tc.replicas for tc in rb.spec.clusters)
        _check(total == (i % 8) + 1, f"{key}: {total} replicas assigned, "
               f"template asks {(i % 8) + 1}")
        for tc in rb.spec.clusters:
            work = f"karmada-es-{tc.name}/default.smk{i}-deployment"
            _check(cp.store.get("Work", work) is not None,
                   f"no Work {work}")
    return len(idx)


def stage_plane(b: int = TIER_SHAPE[0], c: int = TIER_SHAPE[1]) -> dict:
    """The normal entry points, in-process: init, join, propagate ``b``
    Deployments, settle, one rebalancer storm, settle."""
    import jax

    from karmada_tpu import cli
    from karmada_tpu.utils.metrics import device_bytes
    from karmada_tpu.utils.tracing import tracer

    timings: dict = {}
    clock = [10_000.0]
    cp = cli.cmd_init(clock=lambda: clock[0])
    t0 = time.perf_counter()
    _build_plane(cp, b, c)
    timings["build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    cp.settle()
    timings["cold_wave_s"] = time.perf_counter() - t0
    checked = _check_bindings(cp, b)
    clock[0] += 60
    _storm(cp, b, "a")
    t0 = time.perf_counter()
    cp.settle()
    timings["storm_wave_s"] = time.perf_counter() - t0
    checked += _check_bindings(cp, b)

    device_spans = sum(
        1 for s in tracer.dump() if s.get("name") == "kernel.device"
    )
    _check(device_spans > 0, "no kernel.device span was recorded: the "
           "waves never reached the device path")
    platform = jax.devices()[0].platform
    samples = device_bytes.samples()
    _check(samples, "no karmada_tpu_device_bytes sample was published")
    wrong = [k for k in samples if dict(k).get("platform") != platform]
    _check(not wrong, f"device_bytes samples not on {platform!r}: {wrong}")
    return {
        "shape": f"{b}x{c}",
        "reduced": None,
        "works": len(cp.store.list("Work")),
        "bindings_checked": checked,
        "kernel_device_spans": device_spans,
        "device_bytes_samples": len(samples),
        "smoke_timings": timings,
    }


# --------------------------------------------------------------------------
# stage: sidecar
# --------------------------------------------------------------------------


def stage_sidecar(
    b: int = TIER_SHAPE[0], c: int = TIER_SHAPE[1],
    *, solver_platform: str = "tpu",
) -> dict:
    """The deployment BASELINE.json names. THIS process is the plane and
    stays on CPU jax; the solver sidecar it spawns is the one process
    that owns the chip. Every pass the plane sent is re-run on the
    in-process (CPU) engine and must give the same placements."""
    import jax

    from karmada_tpu import cli
    from karmada_tpu.localup import (
        drain_output,
        scrape_line,
        scrape_solver_backend,
        spawn_child,
    )
    from karmada_tpu.scheduler import ClusterSnapshot, TensorScheduler
    from karmada_tpu.solver.client import RemoteSolver
    from karmada_tpu.utils.metrics import degraded_passes

    _check(jax.devices()[0].platform == "cpu", "the plane process of the "
           "sidecar stage must be pinned to CPU: the chip is the solver's")
    timings: dict = {}
    # the counter is the process's: a test that ran here before may have
    # degraded a pass of its own
    degraded_before = degraded_passes.value(channel="solver")
    t0 = time.perf_counter()
    proc = spawn_child(
        [sys.executable, "-m", "karmada_tpu.solver", "--address",
         "127.0.0.1:0", "--report-backend", "--metrics-port", "0",
         "--warmup-manifest", ""],
        platform=solver_platform,
    )
    solver = None
    try:
        port = int(scrape_line(proc, r"port (\d+)"))
        scrape_line(proc, r"metrics listening on port (\d+)")
        backend = scrape_solver_backend(proc, solver_platform)
        timings["sidecar_up_s"] = time.perf_counter() - t0
        # nothing below reads the sidecar's output: keep its pipe empty, or
        # a chatty sidecar blocks in a write while a solve is in flight
        drain_output(proc)

        solver = RemoteSolver(f"127.0.0.1:{port}", timeout_seconds=600.0)
        passes: list = []
        remote_schedule = solver.schedule

        def recording(problems, *a, **kw):
            snap = ClusterSnapshot(cp.scheduler._sorted_clusters())
            res = remote_schedule(problems, *a, **kw)
            passes.append((snap, list(problems), list(res)))
            return res

        solver.schedule = recording
        clock = [10_000.0]
        cp = cli.cmd_init(clock=lambda: clock[0], solver=solver)
        _build_plane(cp, b, c)
        t0 = time.perf_counter()
        cp.settle()
        timings["cold_wave_s"] = time.perf_counter() - t0
        checked = _check_bindings(cp, b)
        clock[0] += 60
        _storm(cp, b, "sidecar")
        t0 = time.perf_counter()
        cp.settle()
        timings["storm_wave_s"] = time.perf_counter() - t0
        checked += _check_bindings(cp, b)
        _check(degraded_passes.value(channel="solver") == degraded_before
               and cp.scheduler._engine is None,
               "a pass fell back to the plane's in-process engine")

        t0 = time.perf_counter()
        rows = bad = 0
        for snap, problems, results in passes:
            ref = TensorScheduler(snap, trace_manifest="").schedule(problems)
            for got, want in zip(results, ref):
                rows += 1
                bad += not (
                    dict(got.clusters) == dict(want.clusters)
                    and got.error == want.error
                )
        timings["reference_s"] = time.perf_counter() - t0
        _check(rows >= 2 * b, f"the sidecar solved {rows} rows, expected "
               f"at least {2 * b}")
        _check(bad == 0, f"{bad}/{rows} sidecar placements differ from "
               "the in-process engine")
    finally:
        if solver is not None:
            solver.close()
        if proc.poll() is None:
            proc.terminate()
        try:
            rc = proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            rc = "killed after 30 s"
    _check(rc == 0, f"the sidecar did not shut down cleanly (rc={rc})")

    # the chip is free for the next process as soon as the sidecar exits
    t0 = time.perf_counter()
    nxt = spawn_child(
        [sys.executable, "-c",
         "import jax; print('next owner', jax.devices()[0].platform)"],
        platform=solver_platform,
    )
    try:
        took = scrape_line(nxt, r"next owner (\S+)", timeout=120.0)
    finally:
        if nxt.poll() is None:
            nxt.kill()
        nxt.wait()
    timings["next_owner_s"] = time.perf_counter() - t0
    _check(took == backend, f"the next process came up on {took!r}")
    return {
        "shape": f"{b}x{c}",
        "solver_backend": backend,
        "plane_platform": "cpu",
        "passes": len(passes),
        "rows_compared": rows,
        "mismatches": 0,
        "bindings_checked": checked,
        "sidecar_exit": rc,
        "next_owner": took,
        "smoke_timings": timings,
    }


# --------------------------------------------------------------------------
# stage: mesh
# --------------------------------------------------------------------------


def stage_mesh(
    b: int = ENGINE_SHAPE[0], c: int = ENGINE_SHAPE[1], *, n_devices: int = 4
) -> dict:
    """The engine on a 4-device mesh, in one process: placements
    bit-identical to mesh off, the row-sharded residents on four distinct
    devices with a quarter each, the replicated state on all four."""
    import gc

    import jax

    import bench
    from karmada_tpu.parallel.mesh import mesh_shape, scheduling_mesh
    from karmada_tpu.scheduler import TensorScheduler

    if len(jax.devices()) < n_devices:
        return {"skipped": f"{len(jax.devices())} device"}
    w = bench.build_headline_workload(b, c)
    single = TensorScheduler(w.snap, chunk_size=4096, mesh=False,
                             trace_manifest="")
    ref = _placements(single.schedule(w.problems))
    del single, w
    gc.collect()

    mesh = scheduling_mesh(n_devices)
    kept: dict = {}
    facts = stage_engine(b, c, mesh=mesh, keep=kept)
    diff = sum(1 for a, b_ in zip(ref, kept["steady"]) if a != b_)
    _check(diff == 0, f"{diff}/{b} placements differ between mesh off "
           f"and mesh {n_devices}")
    fleet = kept["engine"]._fleet
    _check(fleet._mesh is mesh and fleet._resident_mesh == mesh_shape(mesh),
           "the mesh-divisibility guard fired: the table is single-device")
    layout = {}
    for name, arr, sharded in (
        ("dense", fleet._res_dense, True),
        ("meta", fleet._res_meta, True),
        ("state", fleet._dev_state[0], False),
    ):
        _check(arr is not None, f"no {name} resident")
        per_dev = [s.data.nbytes for s in arr.addressable_shards]
        _check(len(arr.sharding.device_set) == n_devices,
               f"{name} lives on {len(arr.sharding.device_set)} devices")
        want = arr.nbytes // n_devices if sharded else arr.nbytes
        _check(all(nb == want for nb in per_dev),
               f"{name}: per-device bytes {per_dev}, expected {want} each")
        layout[name] = {"devices": n_devices, "per_device_bytes": per_dev[0],
                        "total_bytes": int(arr.nbytes)}
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", "not reported")
        for d in jax.devices()[:n_devices]
    ]
    return {"shape": f"{b}x{c}", "mesh": dict(mesh.shape),
            "identical_to_single": True, "layout": layout,
            "hbm_peak_bytes_per_device": peaks,
            "engine": {k: facts[k] for k in
                       ("rows_on_fleet", "mismatches", "delta_rows_packed")}}


# --------------------------------------------------------------------------
# child and parent
# --------------------------------------------------------------------------

STAGE_BODIES = {
    "engine": stage_engine,
    "kernels": stage_kernels,
    "plane": stage_plane,
    "sidecar": stage_sidecar,
    "mesh": stage_mesh,
}


def _rounded(obj):
    """Smoke timings to the millisecond, recursively."""
    if isinstance(obj, float):
        return round(obj, 3)
    if isinstance(obj, dict):
        return {k: _rounded(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_rounded(v) for v in obj]
    return obj


def run_child(stage: str, platform: str, tiny: bool) -> None:
    """One stage in this process; the result is the last stdout line.
    ``platform`` is what the parent's probe found: the chip's."""
    if stage == "probe":
        print(json.dumps(device_facts()), flush=True)
        return
    wide = stage in ("engine", "mesh")
    if tiny:
        shape = TINY_ENGINE_SHAPE if wide else TINY_TIER_SHAPE
    else:
        shape = ENGINE_SHAPE if wide else TIER_SHAPE
    extra: dict = {}
    if tiny and stage == "engine":
        extra.update(numpy_rows=256, oracle_rows=32)
    if stage == "sidecar":
        extra["solver_platform"] = platform
    facts = _rounded(STAGE_BODIES[stage](*shape, **extra))
    # device and versions on every result line; the sidecar stage's own
    # process is the CPU-pinned plane and says so in ``plane_platform``
    print(json.dumps({"stage": stage, **facts, **device_facts()},
                     default=str), flush=True)


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def spawn_stage(
    stage: str, timeout: float, *, jax_platforms: str | None = None,
    chip: str = "", tiny: bool = False,
) -> dict:
    """Run one stage as a child in its own process group (so whatever it
    started dies with it), stream its stdout through to OUR stderr — this
    script's stdout carries the summary and the result line, nothing else — and
    return the JSON object of the child's last stdout line. Raises on a
    non-zero exit."""
    _check("jax" not in sys.modules, "the smoke parent imported jax: a "
           "parent that has touched jax holds the chip its children need")
    env = dict(os.environ)
    if jax_platforms is not None:
        env["JAX_PLATFORMS"] = jax_platforms
    # every compile persists: later stages (and the prewarm) reuse them
    env.setdefault("KARMADA_TPU_CACHE_MIN_COMPILE_SECS", "0")
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", stage,
           "--platform", chip]
    proc = subprocess.Popen(
        cmd + (["--tiny"] if tiny else []),
        cwd=HERE, env=env, stdout=subprocess.PIPE, text=True,
        start_new_session=True,
    )
    def on_alarm(signum, frame):
        raise TimeoutError(f"stage {stage} exceeded {timeout:.0f} s")

    last = ""
    try:
        signal.signal(signal.SIGALRM, on_alarm)
        signal.alarm(int(timeout))
        for line in proc.stdout:
            last = line.rstrip("\n") or last
            print(f"[{stage}] {line}", end="", file=sys.stderr, flush=True)
        rc = proc.wait()
    finally:
        signal.alarm(0)
        _kill_group(proc)
    if rc != 0:
        raise RuntimeError(f"stage {stage} exited rc={rc}")
    return json.loads(last)


def result_line(device: dict) -> dict:
    """The LAST stdout line of a passing run: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``), the device as jax reported it to
    the probe child. Whoever runs the smoke parses this line; everything
    else the stages found is on the summary line before it."""
    return {
        "ok": True,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": int(device["device_count"])},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stage", default="", help=argparse.SUPPRESS)
    ap.add_argument("--platform", default="", help=argparse.SUPPRESS)
    ap.add_argument(
        "--tiny", action="store_true",
        help="CPU rehearsal: every stage and check at sizes a CPU finishes "
        "in seconds, on whatever platform jax finds (the result line says "
        "so; it is not the chip smoke)",
    )
    args = ap.parse_args()
    if args.stage:
        run_child(args.stage, args.platform, args.tiny)
        return 0

    if not os.path.isdir(os.path.join(HERE, "karmada_tpu")):
        print("chip_smoke: no karmada_tpu package beside chip_smoke.py — "
              "nothing to smoke", file=sys.stderr)
        return 1
    t_all = time.perf_counter()
    # whatever platform jax picks under the caller's environment decides:
    # only a TPU goes on
    try:
        device = spawn_stage("probe", 120)
    except (RuntimeError, TimeoutError, ValueError) as exc:
        print(f"chip_smoke: jax could not report a device ({exc})",
              file=sys.stderr)
        return 1
    chip = device["platform"]
    if chip != "tpu" and not args.tiny:
        print(
            f"chip_smoke: jax found platform {chip!r} "
            f"({device['device_count']} x {device['device_kind']}), not a "
            "tpu — nothing was run", file=sys.stderr,
        )
        return 1

    stages: dict = {}
    facts_by_stage: dict = {}
    walls: dict = {}
    for stage, owns_chip, timeout in STAGES:
        t0 = time.perf_counter()
        try:
            facts = spawn_stage(
                stage, timeout, jax_platforms=chip if owns_chip else "cpu",
                chip=chip, tiny=args.tiny,
            )
            if owns_chip and "skipped" not in facts:
                for field in ("platform", "device_kind"):
                    _check(facts.get(field) == device[field], "it ran on "
                           f"{facts.get(field)!r}, not {device[field]!r}")
        except (RuntimeError, TimeoutError, ValueError) as exc:
            print(f"chip_smoke: FAILED in stage {stage}: {exc}",
                  file=sys.stderr)
            return 1
        walls[stage] = round(time.perf_counter() - t0, 1)
        if "skipped" in facts:
            stages[stage] = f"skipped: {facts['skipped']}"
            continue
        stages[stage] = "ok"
        facts_by_stage[stage] = {
            k: v for k, v in facts.items()
            if k != "stage" and k not in device
        }
    walls["total"] = round(time.perf_counter() - t_all, 1)
    # two stdout lines: what the stages found, then the result line
    print(json.dumps({
        "summary": "chip_smoke",
        **({"tiny": True} if args.tiny else {}),
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "stages": stages,
        "facts": facts_by_stage,
        "smoke_wall_s": walls,
    }), flush=True)
    print(json.dumps(result_line(device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
