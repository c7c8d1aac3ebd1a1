"""ISSUE 31: the documented policy kinds side by side on the fleet path, and
a spread selection as ROW STATE of the fleet table. ISSUE 32: that state is
written on the device by the table's own kernel (``_fleet_select``), so the
cases that read the host's selection cache read the ``device`` counts and
the resident ``sel_bits`` instead.

The deployment is the benchmark's own (``fed-100c-policies`` at its
rehearsal size: 12 members in 3 regions x 2 zones, 600 bindings, the six
placements) with zero-replica rows and a seventh placement whose constraint
cannot be met. Over three turns of a drifting ring:

(a) ``schedule()`` == refimpl (divider_np + spread), row by row;
(b) == benchmark/reference/policies.py, so tier-1 holds the benchmark's
    copy of the semantics;
(c) every row is answered by the fleet, the FitError rows too;
(d) the placement table's slot count never moves and nothing is rebuilt,
    also with more distinct selections a wave than the table has slots;
(e) a Duplicated row read after a later pass replaced the tables answers
    with its own pass's sets;
(f) a moved selection changes that row's answer alone, and an unmoved
    generation re-selects nothing;
(g) the spans and counters carry those counts.
"""

import copy
from types import SimpleNamespace

import numpy as np
import pytest

import chip_smoke
from benchmark import gen
from benchmark import run as bench_run
from benchmark.drivers import policies as driver
from benchmark.reference import policies as reference
from karmada_tpu.scheduler import ClusterSnapshot, TensorScheduler
from karmada_tpu.scheduler import fleet as fleet_mod
from karmada_tpu.utils import metrics
from karmada_tpu.utils.tracing import tracer

CELL = "fed-100c-policies.drift"
SEED = 2147483777
TURNS = 3


def _deployment(seed: int = SEED, layout: dict | None = None):
    """(deployment built, first snapshot, ring of snapshots, their allocs)."""
    _, _, cfg, traffic = bench_run.load_cell(CELL, True)
    cfg = copy.deepcopy(cfg)
    cfg["layout"].update(layout or {})
    cfg["bindings_mix"]["replicas_min"] = 0  # zero-replica rows
    cfg["placements"][0]["share"] = 0.28
    cfg["placements"].append({
        "name": "unsatisfiable", "share": 0.02, "strategy": "dynamic",
        "spread_constraints": [
            {"by": "region", "min_groups": 4, "max_groups": 4}],
    })
    dep = driver.Deployment(cfg, seed, lambda m: None)
    first = dep.build()
    allocs = gen.drift_ring(dep.fleet, traffic, cfg, seed)
    snaps = []
    for a in allocs:
        dep.set_allocated(a)
        snaps.append(ClusterSnapshot(dep.clusters))
    return dep, first, snaps, allocs


def _copy_out(results) -> list:
    return [
        SimpleNamespace(success=r.success, clusters=dict(r.clusters),
                        feasible=tuple(sorted(r.feasible)))
        for r in results
    ]


def _counts() -> dict:
    """The counters of ISSUE 31 as they stand."""
    return {
        "rebuilds": metrics.fleet_table_rebuilds.value(),
        "minted": metrics.fleet_slots_minted.value(),
        **{o: metrics.spread_selections.value(outcome=o)
           for o in ("device", "hit", "computed", "fit_error")},
    }


@pytest.fixture(scope="module")
def storm():
    """Three turns of the ring through one engine, everything recorded."""
    dep, first, snaps, allocs = _deployment()
    engine = TensorScheduler(first, chunk_size=256)
    engine.schedule(dep.problems)
    before = _counts()
    tracer.clear()
    waves = []
    for g in range(TURNS * len(snaps)):
        assert engine.update_snapshot(snaps[g % len(snaps)])
        solves = engine.solve_batches
        res = engine.schedule(dep.problems)
        waves.append(SimpleNamespace(
            g=g, results=_copy_out(res), slots=len(engine._fleet._cp_pl),
            solves=engine.solve_batches - solves, table=id(engine._fleet)))
    spans = tracer.dump()
    after = _counts()
    kinds = np.asarray(
        [bool(p["spread"]) and p["strategy"] != "static"
         for p in dep.placements])
    return SimpleNamespace(
        dep=dep, snaps=snaps, allocs=allocs, waves=waves, spans=spans,
        delta={k: after[k] - before[k] for k in after},
        spread_rows=int(kinds[dep.kind].sum()),
        unsat_rows=int((dep.kind == len(dep.placements) - 1).sum()),
        slots_gauge=metrics.fleet_placement_slots.value(),
    )


def _turn(storm, turn: int) -> list:
    n = len(storm.snaps)
    return storm.waves[turn * n:(turn + 1) * n]


def test_the_case_holds_what_it_says(storm):
    dep = storm.dep
    assert len(dep.placements) == 7 and len(dep.problems) == 600
    assert np.bincount(dep.kind, minlength=7).min() >= 12
    assert (dep.bind["replicas"] == 0).sum() >= 5
    assert storm.spread_rows >= 60 and storm.unsat_rows == 12
    assert len({r for r in dep.members["region"]}) == 3
    assert len({z for z in dep.members["zone"]}) == 6


@pytest.mark.parametrize("turn", range(TURNS))
def test_schedule_equals_refimpl(storm, turn):
    dep = storm.dep
    for w in _turn(storm, turn):
        snap = storm.snaps[w.g % len(storm.snaps)]
        bad = chip_smoke._numpy_mismatches(
            snap, dep.problems, w.results, TensorScheduler(snap, mesh=False),
            list(range(len(dep.problems))))
        assert bad == 0, (w.g, bad)


@pytest.mark.parametrize("turn", range(TURNS))
def test_schedule_equals_the_benchmarks_reference(storm, turn):
    dep = storm.dep
    fl, bd = dep.fleet, dep.bind
    names = fl["names"]
    rows = np.arange(len(dep.problems))
    prev = gen.prev_dense(bd, rows, len(names))
    for w in _turn(storm, turn):
        out, placed, selected = reference.place(
            dep.placements, dep.kind, bd["replicas"], dep.profiles,
            bd["prof_idx"], prev, bd["fresh"],
            fl["allocatable"] - storm.allocs[w.g % len(storm.allocs)],
            dep.members)
        for i, res in enumerate(w.results):
            want = {names[k]: int(out[i, k]) for k in np.flatnonzero(out[i])}
            assert res.success == bool(placed[i]), (w.g, i)
            assert res.clusters == (want if placed[i] else {}), (w.g, i)
            if placed[i] and bd["replicas"][i] == 0:
                # a binding without replicas answers the set it may go to
                assert list(res.feasible) == [
                    names[k] for k in np.flatnonzero(selected[i])], (w.g, i)
        # the unsatisfiable placement is a FitError on every row it has
        assert not placed[dep.kind == 6].any()
        assert placed[dep.kind != 6].all()


def test_every_row_rides_the_fleet_the_fit_errors_too(storm):
    host = [s for s in storm.spans if s["name"] == "scheduler.host"]
    solve = [s for s in storm.spans if s["name"] == "scheduler.solve"]
    assert not host and len(solve) == len(storm.waves)
    assert {s["attrs"]["rows"] for s in solve} == {600}
    # one fleet pass a wave and nothing else
    assert {w.solves for w in storm.waves} == {1}
    # a FitError is the device's empty selection: no candidate, that error
    for w in storm.waves:
        unsat = [r for r, k in zip(w.results, storm.dep.kind) if k == 6]
        assert len(unsat) == storm.unsat_rows
        assert not any(r.success or r.clusters for r in unsat)


def test_the_placement_table_holds_policies_not_selections(storm):
    # all seven placements ride the fleet (the seventh's rows are all
    # FitErrors, which the device selection answers)
    assert {w.slots for w in storm.waves} == {7}
    assert len({w.table for w in storm.waves}) == 1
    assert storm.delta["rebuilds"] == 0
    assert storm.delta["minted"] == 0  # all seven were interned by pass 0
    assert storm.slots_gauge == 7


def test_more_selections_a_wave_than_slots_rebuilds_nothing(monkeypatch):
    """The old design interned a selection as a placement: a wave with
    more distinct selections than the slot budget exhausted the table."""
    monkeypatch.setattr(fleet_mod, "MAX_SLOTS", 16)
    monkeypatch.setattr(fleet_mod, "MAX_SLOTS_HARD", 16)
    monkeypatch.setattr(fleet_mod, "CP_TABLE_MAX_BYTES", 0)
    dep, first, snaps, _ = _deployment(seed=11)
    engine = TensorScheduler(first, chunk_size=256)
    engine.schedule(dep.problems)
    table = engine._fleet
    assert table._max_slots() == 16
    rebuilds = metrics.fleet_table_rebuilds.value()
    spread = np.flatnonzero(np.isin(dep.kind, (4, 5)))
    rows = np.asarray([table._key_row[dep.problems[i].key] for i in spread])
    seen = set()
    for g in range(2 * len(snaps)):
        assert engine.update_snapshot(snaps[g % len(snaps)])
        engine.schedule(dep.problems)
        # the selections of this wave, as the resident row state holds them
        resident = np.asarray(table._dev_state[-1])[rows]
        distinct = {r.tobytes() for r in resident if r.any()}
        assert len(distinct) > 16, len(distinct)
        seen |= distinct
        assert len(table._cp_pl) == 7 and engine._fleet is table
    assert len(seen) > 32
    assert metrics.fleet_table_rebuilds.value() == rebuilds


def test_duplicated_rows_answer_with_their_own_passs_tables():
    dep, first, snaps, allocs = _deployment(seed=5)
    engine = TensorScheduler(first, chunk_size=256)
    dup = [i for i, k in enumerate(dep.kind)
           if dep.placements[k]["strategy"] == "duplicated"
           and dep.bind["replicas"][i] > 0]
    held = engine.schedule(dep.problems)  # read only after the next pass
    # the next snapshot moves a FILTER field: a prod member turns canary,
    # so the mask tables are rebuilt in full
    flipped = next(j for j, lb in enumerate(dep.members["labels"])
                   if lb["env"] == "prod")
    dep.clusters[flipped].meta.labels["env"] = "canary"
    dep.set_allocated(allocs[0])
    assert engine.update_snapshot(ClusterSnapshot(dep.clusters))
    later = engine.schedule(dep.problems)
    names = dep.fleet["names"]
    prod = [n for n, lb in zip(names, dep.members["labels"])
            if lb["env"] == "prod"]
    for i in dup:
        want = dict.fromkeys(prod, int(dep.bind["replicas"][i]))
        assert later[i].clusters == {
            n: v for n, v in want.items() if n != names[flipped]}, i
        assert held[i].clusters == want, i


def test_a_moved_selection_changes_that_rows_answer_alone():
    """Row state the HOST brings (``selections=``): what a snapshot with
    more regions than the device kernel's table runs on. The same 12
    members, each a region of its own."""
    dep, first, _, _ = _deployment(seed=3, layout={
        "regions": 12, "zones_per_region": 1, "members_per_zone": 1})
    engine = TensorScheduler(first, chunk_size=256)
    base = _copy_out(engine.schedule(dep.problems))
    table = engine._fleet
    assert table._dev_spread is None and table.batch.select_rows is None
    assert metrics.spread_host_selected_rows.value() > 0
    # every row rides (four regions exist here, so no selection fails),
    # none of them device-selected
    rec = table.batch
    fp, fc, select = rec.problems, rec.compiled, rec.select
    assert select is None and len(fp) == len(dep.problems)
    rides = list(range(len(fp)))
    resident = np.asarray(table._dev_state[-1]).copy()
    # a dynamic-weight row under spread constraints, narrowed by hand to
    # one of the members it was given
    pos = next(k for k, i in enumerate(rides)
               if dep.kind[i] == 4 and len(base[i].clusters) >= 2)
    row = rides[pos]
    keep = sorted(base[row].clusters)[0]
    mask = np.zeros(len(dep.fleet["names"]), bool)
    mask[dep.fleet["names"].index(keep)] = True
    bits = np.packbits(mask, bitorder="little")[None, :]
    # the moved selection is uploaded and is what the resident state holds,
    # at that row alone
    res = table.schedule(fp, fc, selections=(np.asarray([pos]), bits))
    assert table.last_breakdown["sel_moved"] == 1
    after = np.asarray(table._dev_state[-1])
    trow = table._key_row[fp[pos].key]
    assert (after[trow] == bits[0]).all()
    others = np.arange(len(after)) != trow
    assert (after[others] == resident[others]).all()
    got = _copy_out(res)
    assert set(got[pos].clusters) == {keep}
    assert sum(got[pos].clusters.values()) == dep.bind["replicas"][row]
    for k, i in enumerate(rides):
        if k != pos:
            assert got[k].clusters == base[i].clusters, i
    # the same selection again moves nothing and uploads nothing
    table.schedule(fp, fc, selections=(np.asarray([pos]), bits))
    assert table.last_breakdown["sel_moved"] == 0
    assert table.last_breakdown["upload_mb"] == 0


def test_an_unmoved_generation_reselects_nothing():
    """On the device every pass selects its spread rows again; what an
    unmoved generation shows is that no selection MOVED, and that the host
    computed none either way."""
    dep, first, snaps, _ = _deployment(seed=3)
    engine = TensorScheduler(first, chunk_size=256)
    engine.schedule(dep.problems)
    assert engine.update_snapshot(snaps[1])
    tracer.clear()
    engine.schedule(dep.problems)
    engine.schedule(list(dep.problems))  # another list: the prologue runs
    first_pass, again = [
        s["attrs"] for s in tracer.dump() if s["name"] == "scheduler.select"]
    assert first_pass["device"] == first_pass["rows"] > 0
    assert first_pass["moved"] > 0  # the generation moved
    assert again["device"] == again["rows"] == first_pass["rows"]
    assert again["moved"] == 0
    for a in (first_pass, again):
        assert a["computed"] == a["hits"] == 0
        assert a["fit_errors"] == 12
    assert not engine._row_selections  # the host cache holds nothing


def test_spans_and_counters_carry_the_counts(storm):
    select = [s for s in storm.spans if s["name"] == "scheduler.select"]
    solve = {s["span_id"]: s for s in storm.spans
             if s["name"] == "scheduler.solve"}
    assert len(select) == len(solve) == len(storm.waves)
    # the waves ride the batch-identity fast path: no prologue at all
    assert not [s for s in storm.spans if s["name"] == "scheduler.pack"]
    n = storm.spread_rows
    for s in select:
        # every wave the kernel selects every spread row; the host none
        a = s["attrs"]
        assert (a["rows"], a["device"], a["hits"], a["computed"]) == (
            n, n, 0, 0)
        assert a["fit_errors"] == storm.unsat_rows
        assert 0 <= a["moved"] <= n
        # the Select stage's host share is the solve span's child, inside
        # its interval
        parent = solve[s["parent_id"]]
        assert parent["start"] <= s["start"]
        assert (s["start"] + s["duration_s"]
                <= parent["start"] + parent["duration_s"] + 1e-6)
    assert sum(s["attrs"]["moved"] for s in select) > 0
    waves = len(storm.waves)
    assert storm.delta["hit"] == storm.delta["computed"] == 0
    assert storm.delta["fit_error"] == waves * storm.unsat_rows
    assert storm.delta["device"] == waves * (n - storm.unsat_rows)
    assert {a["attrs"]["slots"] for a in solve.values()} == {7}
    assert {a["attrs"]["slots_minted"] for a in solve.values()} == {0}


def test_the_bits_pass_is_a_phase_of_its_own():
    dep, first, _, _ = _deployment(seed=3)
    engine = TensorScheduler(first, chunk_size=256)
    res = engine.schedule(dep.problems)
    dup = next(i for i, k in enumerate(dep.kind)
               if k == 0 and dep.bind["replicas"][i] > 0)
    tracer.clear()
    assert res[dup].clusters and res[dup + 1].success  # one dispatch a batch
    res[dup].clusters
    bits = [s for s in tracer.dump() if s["name"] == "kernel.bits"]
    assert len(bits) == 1
    a = bits[0]["attrs"]
    assert a["rows"] == 600 and a["fetch_mb"] > 0
    assert a["dispatch_s"] + a["device_s"] <= bits[0]["duration_s"]
    lowered = fleet_mod._fleet_bits.lower(
        *engine._fleet._dev_tables,
        np.zeros(256, np.int32), *engine._fleet._dev_state,
        chunk=256, n_chunks=1)
    assert "fleet.bits" in lowered.as_text(debug_info=True)
