"""ISSUE 32: SelectClusters on the device. ``_fleet_select`` computes a
spread-constrained row's selection from the fleet table's resident state
and writes it into ``sel_bits``; ``scheduler/spread.py`` + ``groups.py`` stay
the semantics, and these cases hold the kernel to them bit for bit.

(a) the kernel against ``select_clusters_batch`` on seeded random
    federations and on ``test_groups_selection.py``'s hand cases;
(b) a fleet batch with spread rows == ``_schedule_host`` == ``refimpl/`` on
    every row, FitError rows included, over three generations;
(c) an availability-only snapshot swap takes the batch-identity fast path;
(d) a snapshot with more than ``R_CAP`` regions keeps the host selection;
(e) one trace of the kernel over eight waves of a drifting ring;
(f) the selection math stays in 32 bits and carries its scope.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from karmada_tpu.api.policy import SpreadConstraint
from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot, TensorScheduler
from karmada_tpu.scheduler import fleet as fleet_mod
from karmada_tpu.scheduler import select as select_mod
from karmada_tpu.scheduler.snapshot import compile_placement
from karmada_tpu.scheduler.spread import select_clusters_batch
from karmada_tpu.utils import builders, metrics
from karmada_tpu.utils.tracing import tracer

MI = 2**31 - 1
K_PREV = fleet_mod.K_PREV
STRATEGIES = (
    builders.dynamic_weight_placement,
    builders.aggregated_placement,
    builders.duplicated_placement,
)


def _placement(make, shape):
    return make(spread_constraints=[
        SpreadConstraint(spread_by_field=f, min_groups=lo, max_groups=hi)
        for f, lo, hi in shape])


def _shapes(n_regions: int) -> list:
    """Every constraint shape the issue names, for a federation of
    ``n_regions`` regions."""
    return [
        [("cluster", 1, 0)], [("cluster", 2, 4)], [("cluster", 3, 3)],
        [("cluster", 0, 2)],
        [("region", 1, 0)], [("region", 2, 3)],  # region only: cluster max 0
        [("region", n_regions, n_regions)],  # minGroups = number of regions
        [("region", 2, 3), ("cluster", 3, 6)],
        [("region", 1, 2), ("cluster", 0, 0)],
        [("region", 0, 0), ("cluster", 0, 5)],  # the empty path is recorded
        [("region", 1, 1), ("cluster", 4, 4)],
        [("region", n_regions + 1, n_regions + 1)],  # too few regions
        [("zone", 1, 2)], [("zone", 1, 2), ("cluster", 1, 3)],  # zone-only
        [("region", 2, 2), ("cluster", 2, 200)],
        [("region", 3, 0), ("cluster", 5, 7)],
    ]


def _federation(rng, c: int, n_regions: int) -> ClusterSnapshot:
    # names whose lexicographic order is not their creation order
    regions = [f"r{'hcfaegbd'[k % 8]}{k}" for k in range(n_regions)]
    clusters = []
    for j in range(c):
        region = "" if rng.random() < 0.1 else str(rng.choice(regions))
        clusters.append(builders.new_cluster(f"m{j:04d}", region=region))
    return ClusterSnapshot(clusters)


def _run_kernel(snap, compiled_slots, aff, prof_table, cp, pf, replicas,
                prev_sites, prev_counts, chunk=None):
    """``_fleet_select`` over hand-built device tables: every row of the
    state is a spread row. Returns (selection bool[B, C], counts)."""
    b, c = len(cp), snap.num_clusters
    w8 = (c + 7) // 8
    ones = np.ones((len(compiled_slots), c), bool)
    cp_bits = np.concatenate(
        [np.packbits(aff, axis=1, bitorder="little"),
         np.packbits(ones, axis=1, bitorder="little")], axis=1)
    chunk = chunk or fleet_mod._select_chunk(b, c)
    n_chunks = -(-b // chunk)
    rows = np.full(chunk * n_chunks, -1, np.int32)
    rows[:b] = np.arange(b)
    sel_bits, counts = fleet_mod._fleet_select(
        jnp.asarray(cp_bits),
        jnp.zeros((len(compiled_slots), c), jnp.int32),
        jnp.asarray(np.packbits(np.ones((1, c), bool), axis=1,
                                bitorder="little")),
        jnp.asarray(prof_table), jnp.zeros(c, bool),
        jnp.asarray(np.asarray(
            [select_mod.constraint_params(s) for s in compiled_slots],
            np.int32)),
        jnp.asarray(select_mod.region_table(snap)),
        *(jnp.asarray(a) for a in select_mod.subset_table()),
        jnp.asarray(rows),
        jnp.asarray(cp), jnp.zeros(b, jnp.int32), jnp.asarray(pf),
        jnp.asarray(replicas),
        jnp.asarray(prev_sites), jnp.asarray(prev_counts),
        jnp.full((b, fleet_mod.K_EVICT), -1, jnp.int32),
        jnp.full((b, w8), 0xFF, jnp.uint8),
        chunk=chunk, n_chunks=n_chunks,
    )
    got = np.unpackbits(np.asarray(sel_bits), axis=1,
                        bitorder="little")[:, :c].astype(bool)
    return got, np.asarray(counts)


def _host(snap, compiled_slots, aff, prof_table, cp, pf, replicas,
          prev_sites, prev_counts):
    """The same rows through ``select_clusters_batch``."""
    b, c = len(cp), snap.num_clusters
    prev = np.zeros((b, c), np.int32)
    for i in range(b):
        np.add.at(prev[i], prev_sites[i], prev_counts[i])
    avail = np.where(replicas[:, None] == 0, 0, prof_table[pf]).astype(np.int32)
    return select_clusters_batch(
        snap, [SimpleNamespace(replicas=int(r)) for r in replicas],
        [compiled_slots[k] for k in cp], 0, aff[cp], avail, prev)


@pytest.mark.parametrize("n_regions", [1, 3, 5, 8])
@pytest.mark.parametrize("c", [7, 100, 300])
def test_kernel_equals_select_clusters_batch(c, n_regions):
    rng = np.random.default_rng(c * 131 + n_regions)
    snap = _federation(rng, c, n_regions)
    slots, aff = [], []
    for shape in _shapes(n_regions):
        for make in STRATEGIES:
            slots.append(compile_placement(_placement(make, shape), snap))
            dense = rng.random(c) < rng.choice([0.3, 0.9, 1.0])
            aff.append(dense & slots[-1].spread_field_ok)
    aff = np.stack(aff)
    # availability tables: scarce (capacity short after repair), heavily
    # tied, near MAX_INT32, plain
    prof_table = np.stack([
        rng.integers(0, 4, c), rng.choice([0, 5, 10], c),
        rng.integers(MI - 3, MI, c), rng.integers(0, 300, c),
        rng.integers(0, 40, c), np.full(c, 7),
    ]).astype(np.int32)
    b = 6 * len(slots)
    cp = rng.integers(0, len(slots), b).astype(np.int32)
    cp[: len(slots)] = np.arange(len(slots))  # every shape at least once
    pf = rng.integers(0, len(prof_table), b).astype(np.int32)
    replicas = rng.integers(0, fleet_mod.MAX_REPLICAS_FAST + 1, b).astype(np.int32)
    prev_sites = np.zeros((b, K_PREV), np.int32)
    prev_counts = np.zeros((b, K_PREV), np.int32)
    for i in range(b):
        k = min(int(rng.integers(0, 9)), c)
        prev_sites[i, :k] = rng.choice(c, size=k, replace=False)
        prev_counts[i, :k] = rng.integers(1, 6, k)
    args = (snap, slots, aff, prof_table, cp, pf, replicas, prev_sites,
            prev_counts)
    want = _host(*args)
    got, counts = _run_kernel(*args, chunk=256)  # several chunks a call
    bad = np.flatnonzero((got != want).any(axis=1))
    assert not len(bad), (
        bad[:5], select_mod.constraint_params(slots[cp[bad[0]]]),
        np.flatnonzero(got[bad[0]]), np.flatnonzero(want[bad[0]]))
    assert want.any(axis=1).sum() > b // 4  # the case selects something
    assert counts[0] == (~want.any(axis=1)).sum()  # FitErrors counted
    assert counts[1] == b  # every row left the all-ones state


def _hand(names_regions, feasible, score, credited, replicas, make, shape):
    """One row by hand: credited availability is given directly (no
    previous replicas unless ``score`` says so)."""
    clusters = [builders.new_cluster(n, region=r) for n, r in names_regions]
    snap = ClusterSnapshot(clusters)
    c = len(clusters)
    slot = compile_placement(_placement(make, shape), snap)
    prev = np.where(np.asarray(score) > 0, 1, 0).astype(np.int32)
    table = (np.asarray(credited, np.int64) - prev).astype(np.int32)[None, :]
    sites = np.zeros((1, K_PREV), np.int32)
    counts = np.zeros((1, K_PREV), np.int32)
    held = np.flatnonzero(prev)
    sites[0, : len(held)] = held
    counts[0, : len(held)] = 1
    aff = (np.asarray(feasible, bool) & slot.spread_field_ok)[None, :]
    args = (snap, [slot], aff, table, np.zeros(1, np.int32),
            np.zeros(1, np.int32), np.asarray([replicas], np.int32), sites,
            counts)
    want = _host(*args)
    got, _ = _run_kernel(*args)
    assert (got == want).all(), (np.flatnonzero(got[0]), np.flatnonzero(want[0]))
    return sorted(clusters[j].name for j in np.flatnonzero(got[0]))


EW = [("a1", "east"), ("a2", "east"), ("b1", "west"), ("b2", "west"), ("nr", "")]
DYN, DUP = builders.dynamic_weight_placement, builders.duplicated_placement
HAND = {
    # test_groups_selection.py's cases, replayed through the kernel
    "region_only_one_cluster_a_region": (
        EW, [1] * 5, [0] * 5, [10] * 5, 4, DYN, [("region", 2, 2)],
        ["a1", "b1"]),
    "cluster_constraint_fills_from_remainder": (
        EW, [1, 1, 1, 1, 0], [0, 100, 0, 0, 0], [10] * 5, 4, DYN,
        [("region", 2, 2), ("cluster", 2, 3)], ["a1", "a2", "b1"]),
    "zone_without_region_is_fit_error": (
        EW, [1] * 5, [0] * 5, [10] * 5, 1, DYN, [("zone", 1, 0)], []),
    "too_few_regions_is_fit_error": (
        EW, [1] * 5, [0] * 5, [10] * 5, 1, DYN, [("region", 3, 0)], []),
    # sub-path over super-path: [east] and [east, west] are both feasible
    # and west adds no weight; the shorter prefix wins
    "subpath_preferred_over_superpath": (
        [("a1", "east"), ("a2", "east"), ("a3", "east"), ("b1", "west")],
        [1] * 4, [0] * 4, [9, 9, 9, 0], 9, DUP,
        [("region", 1, 2), ("cluster", 2, 0)], ["a1"]),
    # weight over value: the one-member region that covers the replicas
    # beats the five-member region that does not
    "weight_dominates_value": (
        [("s1", "small")] + [(f"l{k}", "large") for k in range(5)],
        [1] * 6, [0] * 6, [50, 1, 1, 1, 1, 1], 20, DUP,
        [("region", 1, 1)], ["s1"]),
    # one region alone cannot reach the cluster minGroups
    "cluster_min_groups_forces_combination": (
        [("a1", "east"), ("b1", "west")], [1, 1], [0, 0], [5, 4], 2, DYN,
        [("region", 1, 2), ("cluster", 2, 2)], ["a1", "b1"]),
    # the swap-repair: the top two by score cannot hold the replicas, the
    # best of the rest replaces the last of them
    "swap_repair_takes_the_largest_leftover": (
        [(f"m{k}", "") for k in range(5)], [1] * 5, [100, 100, 0, 0, 0],
        [2, 1, 3, 9, 9], 10, DYN, [("cluster", 1, 2)], ["m0", "m3"]),
    "capacity_short_after_repair_is_fit_error": (
        [(f"m{k}", "") for k in range(4)], [1] * 4, [0] * 4, [3, 3, 2, 1],
        9, DYN, [("cluster", 1, 2)], []),
    "duplicated_ignores_capacity": (
        [(f"m{k}", "") for k in range(4)], [1] * 4, [0] * 4, [3, 3, 2, 1],
        9, DUP, [("cluster", 1, 2)], ["m0", "m1"]),
}


@pytest.mark.parametrize("case", sorted(HAND))
def test_hand_cases_through_the_kernel(case):
    *args, want = HAND[case]
    assert _hand(*args) == want


# -- the engine ---------------------------------------------------------------

REGIONS = ["ra", "rb", "rc", "rd"]
ENGINE_SHAPES = [
    ("dyn-region-cluster", DYN, [("region", 2, 3), ("cluster", 3, 6)]),
    ("agg-cluster", builders.aggregated_placement, [("cluster", 2, 4)]),
    ("dup-region", DUP, [("region", 2, 2), ("cluster", 2, 5)]),
    ("dyn-region-only", DYN, [("region", 2, 0)]),
    ("unsatisfiable", DYN, [("region", 5, 5)]),
    ("zone-only", builders.aggregated_placement, [("zone", 1, 2)]),
    ("plain", DYN, []),
    ("dup-plain", DUP, []),
]


def _clusters(regions, c=24):
    rng = np.random.default_rng(5)
    out = []
    for j in range(c):
        region = "" if j % 11 == 10 else regions[j % len(regions)]
        out.append(builders.new_cluster(
            f"m{j:03d}", cpu=str(int(rng.integers(40, 400))),
            memory=f"{int(rng.integers(80, 800))}Gi", pods=5000,
            region=region, zone=f"z{j % 3}"))
    return out


def _generation(clusters, g: int) -> ClusterSnapshot:
    """The same members with another load: an availability-only move."""
    rng = np.random.default_rng(100 + g)
    for cl in clusters:
        alloc = cl.status.resource_summary.allocatable
        cl.status.resource_summary.allocated = {
            d: int(v * rng.uniform(0.1, 0.98)) for d, v in alloc.items()}
    return ClusterSnapshot(clusters)


def _problems(clusters, n=420):
    rng = np.random.default_rng(9)
    placements = [_placement(mk, sh) for _, mk, sh in ENGINE_SHAPES]
    names = [cl.name for cl in clusters]
    out = []
    for i in range(n):
        k = int(rng.integers(0, 5))
        held = rng.choice(len(names), size=k, replace=False)
        out.append(BindingProblem(
            key=f"b{i}", placement=placements[i % len(placements)],
            replicas=int(rng.integers(0, 40)),
            requests={"cpu": int(rng.choice([250, 1000, 4000])),
                      "memory": int(rng.choice([1, 4])) << 30},
            gvk="apps/v1/Deployment",
            prev={names[j]: int(rng.integers(1, 4)) for j in held},
            fresh=bool(rng.random() < 0.1),
        ))
    return out


def _copy_out(results) -> list:
    """Fleet results are views, good until the next pass."""
    return [
        SimpleNamespace(
            success=r.success, error=r.error, clusters=dict(r.clusters),
            feasible=tuple(r.feasible), affinity_name=r.affinity_name)
        for r in results]


def _same(a, b, i):
    assert a.success == b.success, i
    assert a.error == b.error, (i, a.error, b.error)
    assert dict(a.clusters) == dict(b.clusters), i
    assert tuple(sorted(a.feasible)) == tuple(sorted(b.feasible)), i
    assert a.affinity_name == b.affinity_name, i


def _host_path(snap, problems):
    ref = TensorScheduler(snap, mesh=False)
    return ref, ref._schedule_host(
        problems, [ref._compiled(p.placement) for p in problems])


@pytest.fixture(scope="module")
def fleet_run():
    clusters = _clusters(REGIONS)
    problems = _problems(clusters)
    engine = TensorScheduler(_generation(clusters, 0), chunk_size=256)
    device0 = metrics.spread_selections.value(outcome="device")
    tracer.clear()
    gens = []
    for g in range(3):
        snap = _generation(clusters, g)
        if g:
            assert engine.update_snapshot(snap)
        solves = engine.solve_batches
        res = engine.schedule(problems)
        gens.append(SimpleNamespace(
            snap=snap, results=_copy_out(res),
            breakdown=dict(engine.last_breakdown),
            solves=engine.solve_batches - solves))
    return SimpleNamespace(
        engine=engine, problems=problems, gens=gens, spans=tracer.dump(),
        host_rows=metrics.spread_host_selected_rows.value(),
        device=metrics.spread_selections.value(outcome="device") - device0)


@pytest.mark.parametrize("g", range(3))
def test_fleet_batch_equals_host_path_and_refimpl(fleet_run, g):
    gen = fleet_run.gens[g]
    problems = fleet_run.problems
    ref, want = _host_path(gen.snap, problems)
    for i, (a, b) in enumerate(zip(gen.results, want)):
        _same(a, b, i)
    assert chip_smoke._numpy_mismatches(
        gen.snap, problems, gen.results, ref, list(range(len(problems)))) == 0
    # FitErrors ride the fleet and read as on the host path
    kinds = len(ENGINE_SHAPES)
    for name in ("unsatisfiable", "zone-only"):
        k = [n for n, _, _ in ENGINE_SHAPES].index(name)
        rows = gen.results[k::kinds]
        assert rows and all(
            r.error == "no clusters fit the placement" for r in rows)
    assert gen.solves == 1  # one fleet pass, no host chunk
    assert any(r.success and r.clusters for r in gen.results[0::kinds])


def test_an_availability_swap_takes_the_identity_fast_path(fleet_run):
    # generation 0 ran the prologue; 1 and 2 moved the generation under an
    # armed spread batch and ran none of it
    first, *later = fleet_run.gens
    assert "select" in first.breakdown and "eligible" in first.breakdown
    for gen in later:
        assert "select" not in gen.breakdown
        assert "eligible" not in gen.breakdown
        assert "select_dispatch" in gen.breakdown
    assert len([s for s in fleet_run.spans
                if s["name"] == "scheduler.pack"]) == 1
    select = [s["attrs"] for s in fleet_run.spans
              if s["name"] == "scheduler.select"]
    spread = sum(1 for p in fleet_run.problems
                 if p.placement.spread_constraints)
    assert [a["device"] for a in select] == [spread] * 3
    assert all(a["computed"] == 0 and a["hits"] == 0 for a in select)
    fit = select[0]["fit_errors"]
    assert fit >= 2 * (len(fleet_run.problems) // len(ENGINE_SHAPES))
    assert fleet_run.device == sum(a["rows"] - a["fit_errors"] for a in select)
    assert fleet_run.host_rows == 0
    assert not fleet_run.engine._row_selections


def test_more_regions_than_the_table_keeps_the_host_selection(capfd):
    regions = [f"r{k}" for k in range(select_mod.R_CAP + 1)]
    clusters = _clusters(regions, c=30)
    problems = _problems(clusters)
    snap = _generation(clusters, 0)
    assert select_mod.region_table(snap) is None
    engine = TensorScheduler(snap, chunk_size=256)
    computed0 = metrics.spread_selections.value(outcome="computed")
    device0 = metrics.spread_selections.value(outcome="device")
    for g in range(2):
        snap = _generation(clusters, g)
        assert engine.update_snapshot(snap)
        tracer.clear()
        got = _copy_out(engine.schedule(problems))
        spans = tracer.dump()
        _, want = _host_path(snap, problems)
        for i, (a, b) in enumerate(zip(got, want)):
            _same(a, b, i)
        select = [s for s in spans if s["name"] == "scheduler.select"]
        assert len(select) == 1  # the host's, under scheduler.pack
        a = select[0]["attrs"]
        assert a["device"] == 0 and a["computed"] == a["rows"] > 0
        assert engine._fleet._dev_spread is None
        # the fallback says so: the gauge, and one line a snapshot layout
        assert metrics.spread_host_selected_rows.value() == a["rows"]
        # the rows the host accepted ride the fleet with uploaded masks;
        # its FitErrors take the host path
        assert [s["attrs"]["rows"] for s in spans
                if s["name"] == "scheduler.host"] == [a["fit_errors"]]
    assert metrics.spread_selections.value(outcome="device") == device0
    assert metrics.spread_selections.value(outcome="computed") > computed0
    assert engine._fleet.batch.token is None  # not reused across a generation
    told = [ln for ln in capfd.readouterr().err.splitlines()
            if ln.startswith("# spread selection on the host")]
    assert len(told) == 1 and f"{select_mod.R_CAP + 1} regions" in told[0]


def test_one_trace_over_eight_waves_of_a_drifting_ring():
    clusters = _clusters(REGIONS)
    problems = _problems(clusters)
    engine = TensorScheduler(_generation(clusters, 0), chunk_size=256)
    engine.schedule(problems)
    table = engine._fleet
    traces = {k for k in table._seen_traces if k[0] == "T"}
    assert len(traces) == 1
    size = fleet_mod._fleet_select._cache_size()
    rows_dev = table.batch.select_rows.rows_dev
    for g in range(8):
        assert engine.update_snapshot(_generation(clusters, g % 4))
        engine.schedule(problems)
        assert not engine.last_pass_new_trace, g
        # one upload for the ring
        assert table.batch.select_rows.rows_dev is rows_dev
    assert {k for k in table._seen_traces if k[0] == "T"} == traces
    assert fleet_mod._fleet_select._cache_size() == size


def test_the_selection_math_is_32_bit_and_scoped():
    b, c = 8, 12
    bits, prefix = select_mod.subset_table()
    structs = (
        jax.ShapeDtypeStruct((b, c), jnp.bool_),
        jax.ShapeDtypeStruct((b, c), jnp.int32),
        jax.ShapeDtypeStruct((b, c), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b, select_mod.N_PARAMS), jnp.int32),
        jax.ShapeDtypeStruct((c,), jnp.int32),
        jax.ShapeDtypeStruct(bits.shape, jnp.int32),
        jax.ShapeDtypeStruct(prefix.shape, jnp.float32),
    )
    jaxpr = jax.make_jaxpr(select_mod.select_rows)(*structs)
    wide = set()

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                if getattr(v.aval, "dtype", None) is not None and (
                        v.aval.dtype.itemsize > 4):
                    wide.add((eqn.primitive.name, str(v.aval.dtype)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert not wide, wide
    # the subset table is DFS discovery order with the empty path first
    assert bits[0] == 0 and bits[1] == 1 and bits[2] == 3
    assert len(bits) == 1 << select_mod.R_CAP
    assert prefix[0, 1:].all() and not prefix[:, 0].any()
    text = fleet_mod._fleet_select.lower(
        *[jax.ShapeDtypeStruct(s, d) for s, d in (
            ((4, 4), "uint8"), ((4, c), "int32"), ((2, 2), "uint8"),
            ((3, c), "int32"), ((c,), "bool"),
            ((4, select_mod.N_PARAMS), "int32"), ((c,), "int32"),
            (bits.shape, "int32"), (prefix.shape, "float32"),
            ((256,), "int32"))],
        *[jax.ShapeDtypeStruct((256,), "int32")] * 4,
        *[jax.ShapeDtypeStruct((256, K_PREV), "int32")] * 2,
        jax.ShapeDtypeStruct((256, fleet_mod.K_EVICT), "int32"),
        jax.ShapeDtypeStruct((256, 2), "uint8"),
        chunk=256, n_chunks=1).as_text(debug_info=True)
    assert "fleet.select" in text and "select.paths" in text
