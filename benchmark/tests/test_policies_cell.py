"""The cell ``fed-100c-policies.drift`` at its rehearsal size, on whatever
device is there (the CPU): a sound run is correct and prints no time, rate
or device metric; its control (the reference without its spread
constraints) comes out not correct; the stratified sample holds its floor
of every placement; with the timed path broken underneath (selections never
reaching the fleet's rows; a stale selection kept across snapshots),
``correct`` comes out false; a program whose placement table grows with the
snapshot generation ends the set-up. Then the five readers the cell brings,
each over a small hand-written span list and a reduced trace, and ``None``
where the program records nothing for them to read."""

import json

import numpy as np
import pytest

from benchmark import control, placements, run
from benchmark.metrics import (
    fleet_bits_device_s,
    fleet_bits_roofline,
    fleet_slots_minted,
    select_rows_computed,
    select_self_s,
)
from benchmark.reference import policies
from benchmark.roofline_bits import fleet_bits_count, least_seconds

CELL = "fed-100c-policies.drift"


def _run(capsys, seed=2147483777, trace=0, seconds="0.6"):
    res = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    seconds, "--trace", str(trace)], rehearse=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    return res


def test_sound_run_is_correct_and_prints_no_device_metric(capsys):
    res = _run(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"] == {} and res["rehearsal"] is True
    checks = res["checks"]
    assert checks["mismatched_rows"]["value"] == 0
    assert checks["undivided_rows"]["value"] == 0
    for floor in ("rows_of_each_kind", "selection_decided_rows",
                  "rows_compared"):
        assert checks[floor]["value"] >= checks[floor]["limit"] > 0
    assert list(res)[-1] == "checks"


def test_traced_rehearsal_reads_the_selection_layers(capsys):
    res = _run(capsys, trace=1, seconds="5")
    assert res["correct"] is True and res["metrics"] == {}
    assert {"select_self_s", "select_rows_computed", "fleet_slots_minted",
            "prologue_self_s", "fleet_host_self_s", "compiles_in_window",
            "spans_dropped"} <= set(res["per_layer_read"])
    assert "busy_s" not in res["device"] and "breakdown" not in res


@pytest.mark.parametrize("seed", [1, 2, 2147483777])
def test_the_control_is_not_correct(seed):
    checks = control.control_checks(CELL, seed, 40, rehearse=True)
    checks.pop("_failed")
    assert run.verdict(checks) is False
    assert checks["mismatched_rows"]["value"] > 0
    # only rows the constraints decide can differ, and they all do
    assert (checks["mismatched_rows"]["value"]
            >= checks["selection_decided_rows"]["value"] > 0)


@pytest.mark.parametrize("seed", [3, 2147483777])
def test_the_sample_holds_its_floor_of_every_placement(seed):
    _, _, cfg, _ = run.load_cell(CELL, False)
    kind = placements.kinds(cfg, seed)
    counts = np.bincount(kind)
    assert counts.tolist() == [30000, 25000, 15000, 20000, 5000, 5000]
    check = cfg["check"]
    for salt in (0, 17, 44):
        rows = placements.sample_rows(
            kind, 6, check["rows_per_kind"], check["rows_per_wave"], seed, salt)
        assert len(rows) == len(set(rows.tolist())) == check["rows_per_wave"]
        assert np.bincount(kind[rows], minlength=6).min() >= 128


def test_selections_that_never_reach_the_rows_are_not_correct(
        capsys, monkeypatch):
    from karmada_tpu.scheduler.fleet import FleetTable

    monkeypatch.setattr(FleetTable, "_apply_selections",
                        lambda self, rows_np, selections: 0)
    res = _run(capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


def test_a_selection_kept_across_snapshots_is_not_correct(
        capsys, monkeypatch):
    from karmada_tpu.scheduler import TensorScheduler

    real = TensorScheduler._select_spread_rows
    kept = {}

    def stale(self, problems, compiled):  # the first answer stands for ever
        if "sel" not in kept:
            kept["sel"] = real(self, problems, compiled)
        return kept["sel"]

    monkeypatch.setattr(TensorScheduler, "_select_spread_rows", stale)
    res = _run(capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


@pytest.mark.parametrize("when, message", [
    ("set-up", "slots more than the 6 placements"),
    ("ring", "placement table grew"),
])
def test_a_placement_table_that_grows_ends_the_set_up(
        monkeypatch, when, message):
    from benchmark.drivers.policies import Deployment

    first = {}

    def slots(self):  # a slot a selection from set-up on, or a generation
        if when == "set-up":
            return 6 + 100 * self.engine.solve_batches
        gen = self.engine._snapshot_gen
        return 6 + 100 * (gen - first.setdefault("gen", gen))

    monkeypatch.setattr(Deployment, "slot_count", slots)
    with pytest.raises(SystemExit, match=message):
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.3",
                  "--trace", "0"], rehearse=True)


# -- the reference ------------------------------------------------------------


def test_the_reference_selects_as_upstream_documents():
    # 6 members in 3 regions; member 4 already holds the binding
    region = ["a", "a", "b", "b", "c", "c"]
    cand = np.ones(6, bool)
    score = np.asarray([0, 0, 0, 0, 100, 0])
    credited = np.asarray([50, 40, 45, 5, 12, 30])
    # [cluster 2..3]: by (score, credited): 4, 0, 2; their 107 cover 60
    sel = policies.select_clusters(
        cand, score, credited, region, [("cluster", 2, 3)], 60)
    assert np.flatnonzero(sel).tolist() == [0, 2, 4]
    # 130 is more than the three hold: the last kept (2: 45) cannot be
    # bettered from the rest (1: 40), the one before (0) neither, the first
    # (4: 12) is exchanged for member 1 -> 50 + 45 + 40 = 135
    sel = policies.select_clusters(
        cand, score, credited, region, [("cluster", 2, 3)], 130)
    assert np.flatnonzero(sel).tolist() == [0, 1, 2]
    assert policies.select_clusters(
        cand, score, credited, region, [("cluster", 2, 3)], 136) is None
    # [region 2..2, cluster 3..3]: 20 replicas, target ceil(20 / 2) = 10 a
    # region over max(3, 2) members (a region has 2: all of them): a scores
    # 10*1000, b 10*1000, c 10*1000 + 100 // 2; the best pair is c with a
    # (a before b by name); best of each (4, 0) and the better of the rest
    sel = policies.select_clusters(
        cand, score, credited, region,
        [("region", 2, 2), ("cluster", 3, 3)], 20)
    assert np.flatnonzero(sel).tolist() == [0, 1, 4]
    assert policies.select_clusters(
        cand, score, credited, region, [("region", 4, 4)], 20) is None
    assert policies.select_clusters(
        cand, score, credited, region, [("zone", 1, 2)], 20) is None


def test_the_reference_assigns_as_upstream_documents():
    cand = np.asarray([True, True, True, False])
    prev = np.asarray([0, 2, 0, 0])
    # static 3:2:1 of 7 -> floors 3, 2, 1 and one more to the heaviest
    out = policies.assign_static(7, cand, np.asarray([3, 2, 1, 9]), prev)
    assert out.tolist() == [4, 2, 1, 0]
    # all weights zero: every candidate weighs 1; the tie goes to the one
    # that held replicas
    out = policies.assign_static(4, cand, np.zeros(4, np.int64), prev)
    assert out.tolist() == [1, 2, 1, 0]
    # aggregated scale-up: 2 held, 9 asked: 7 more over the shortest prefix
    # of (held first, then availability desc): member 1 (4) then member 0
    # (10) cover 7; dispensed by weight 4:10 -> 2, 5
    avail = np.asarray([10, 4, 8, 99])
    out, short = policies.assign_aggregated(9, cand, avail, prev, False)
    assert (out.tolist(), short) == ([5, 4, 0, 0], False)
    # fresh: all 9 over availability credited with what is held: member 0
    out, short = policies.assign_aggregated(9, cand, avail, prev, True)
    assert (out.tolist(), short) == ([9, 0, 0, 0], False)
    out, short = policies.assign_aggregated(30, cand, avail, prev, False)
    assert short and not out.any()


# -- the readers --------------------------------------------------------------

WAVES = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]
CFG = {"bindings": 100000, "clusters": 100, "placements": [{}] * 6,
       "bindings_mix": {"prev_sites_max": 8}}
PEAK = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def span(name, start, dur, span_id=0, parent_id=None, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": start, "duration_s": dur, "attrs": attrs}


def wave(t, sid, scale=1.0, minted=0):
    """One wave's prologue and solve spans at their true intervals."""
    d = 0.1 * scale
    return [
        span("scheduler.pack", t, 5 * d, span_id=sid, rows=100000),
        span("scheduler.select", t + d, 3 * d, span_id=sid + 1, parent_id=sid,
             rows=10000, hits=0, computed=int(10000 * scale), fit_errors=0,
             moved=4000),
        span("scheduler.solve", t + 5 * d, d, span_id=sid + 2, rows=100000,
             slots=6, slots_minted=minted),
        span("kernel.bits", t + 6 * d, 0.01, span_id=sid + 3, rows=100000,
             fetch_mb=1.6),
    ]


def ctx_of(spans, op_s=None, waves=4):
    return {"spans": spans, "waves": WAVES, "rest_wall": 3.0, "cfg": CFG,
            "peak": PEAK, "trace": {"op_s": op_s or {}, "waves": waves}}


def test_span_readers_take_the_median_wave():
    ctx = ctx_of(wave(10.1, 10) + wave(11.1, 20, 2.0) + wave(12.1, 30, 3.0))
    assert select_self_s.read(ctx) == pytest.approx(0.3 * 2)
    assert select_rows_computed.read(ctx) == 20000
    assert fleet_slots_minted.read(ctx) == 0.0
    ctx = ctx_of(wave(10.1, 10) + wave(11.1, 20, minted=3) + wave(12.1, 30))
    assert fleet_slots_minted.read(ctx) == 3.0


def test_device_readers_read_the_bits_kernel():
    ctx = ctx_of([], {"jit__fleet_bits": 0.008, "jit__fleet_pass": 0.3})
    assert fleet_bits_device_s.read(ctx) == pytest.approx(0.002)
    count = fleet_bits_count(100000, 100, 6, 8)
    assert count["bytes"] == (100000 * (8 + 32 + 13) + 6 * 26 + 13
                              + 100000 * 13)
    assert count["int_ops"] == 100000 * 100 * 13
    least, bound = least_seconds(count, PEAK)
    assert bound == "bytes" and 8.0e-6 < least < 8.2e-6
    share = fleet_bits_roofline.read(ctx)
    assert share == pytest.approx(100 * least / 0.002) and 0 < share < 100
    assert any("fleet_bits_roofline bound=bytes" in n for n in ctx["notes"])


def test_readers_read_nothing_where_the_program_has_no_such_span():
    parent = [span("scheduler.pack", 10.1, 0.5, rows=100000),
              span("scheduler.solve", 10.6, 0.1, rows=100000)]
    ctx = ctx_of(parent, {"jit__fleet_pass": 0.3})
    for reader in (select_self_s, select_rows_computed, fleet_slots_minted,
                   fleet_bits_device_s, fleet_bits_roofline):
        assert reader.read(ctx) is None, reader.__name__
