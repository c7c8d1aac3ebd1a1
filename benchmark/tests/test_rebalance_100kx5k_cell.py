"""The cell ``rebalance-100kx5k.drift``: the generator's members and bindings
(at the rehearsal size and, arrays only, at the configuration's own), the
program against the reference at the rehearsal size with every floor met,
the control (the taint filter left out) coming out not correct, and the
three readers of the wire home over hand-written spans (``None`` where the
program records nothing for them, 0 where phase B stayed dark)."""

import json

import numpy as np
import pytest

from benchmark import control, gen, run, tainted
from benchmark.metrics import fleet_delta_cells, fleet_entries_s, fleet_entry_rows

CELL = "rebalance-100kx5k.drift"


def _arrays(rehearse: bool, seed: int):
    _, _, cfg, _ = run.load_cell(CELL, rehearse)
    return cfg, tainted.members(cfg, seed), tainted.bindings(cfg, seed)


# -- the generator ------------------------------------------------------------


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_members_and_bindings_are_what_the_files_say(rehearse):
    cfg, fl, bd = _arrays(rehearse, 2147483777)
    c, b = int(cfg["clusters"]), int(cfg["bindings"])
    assert fl["allocatable"].shape == fl["allocated"].shape == (c, 3)
    nodes = fl["allocatable"][:, 2] // 110
    assert nodes.min() >= 2 and nodes.max() <= 49
    cores = fl["allocatable"][:, 0] // 1000
    assert np.isin(cores // nodes, [16, 32, 64, 128]).all()
    assert (fl["allocatable"][:, 1] == cores * 4 * gen.GIB).all()
    share = fl["allocated"] / fl["allocatable"]
    assert share.min() >= 0.19 and share.max() <= 0.8
    assert len(bd["replicas"]) == b and bd["replicas"].max() <= 99
    if not rehearse:
        assert (c, b) == (5000, 100000)
        assert 380 <= fl["tainted"].sum() <= 420
        assert 0.29 < bd["tolerates"].mean() < 0.31
        assert 0.69 < (bd["n_prev"] > 0).mean() < 0.71
        assert bd["prev_counts"].max() == 29


def test_every_seed_holds_the_same_content_dealt_otherwise():
    _, a, x = _arrays(False, 7)
    _, b, y = _arrays(False, 8)
    assert not np.array_equal(a["tainted"], b["tainted"])
    assert a["tainted"].sum() == b["tainted"].sum()
    assert np.array_equal(np.sort(a["allocatable"], axis=0),
                          np.sort(b["allocatable"], axis=0))
    for key in ("replicas", "tolerates", "fresh"):
        assert not np.array_equal(x[key], y[key])
        assert np.array_equal(np.sort(x[key]), np.sort(y[key])), key


# -- the cell at its rehearsal size ------------------------------------------


def _run(capsys, seed=2147483777, trace=0, seconds="2"):
    res = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    seconds, "--trace", str(trace)], rehearse=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    return res


def test_sound_run_is_correct_with_every_floor_met(capsys):
    res = _run(capsys, trace=1, seconds="3")
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"] == {} and res["rehearsal"] is True
    checks = res["checks"]
    assert checks["mismatched_rows"]["value"] == 0
    for floor in ("rows_compared", "taint_decided_rows", "lenient_rows",
                  "wide_answer_rows"):
        assert checks[floor]["value"] >= checks[floor]["limit"] > 0, floor
    assert {"fleet_delta_cells", "fleet_entry_rows", "fleet_entries_s",
            "spans_dropped"} <= set(res["per_layer_read"])


@pytest.mark.parametrize("seed", [7, 2147483777])
def test_the_control_is_not_correct(seed):
    checks = control.control_checks(CELL, seed, 40, rehearse=True)
    checks.pop("_failed")
    assert run.verdict(checks) is False
    assert checks["mismatched_rows"]["value"] >= checks["taint_decided_rows"][
        "value"] > 0
    # the floors are the reference's own: the control meets them too
    for floor in ("taint_decided_rows", "lenient_rows", "wide_answer_rows"):
        assert checks[floor]["value"] >= checks[floor]["limit"], floor


# -- the readers --------------------------------------------------------------

WAVES = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]


def span(name, start, dur, span_id=0, parent_id=None, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": start, "duration_s": dur, "attrs": attrs}


def fetch(t, sid, cells, entry_rows, *entries):
    out = [span("kernel.fetch", t, 0.05, span_id=sid, fetch_mb=1.0,
                changed_rows=50000.0, delta_rows=50000 - entry_rows,
                delta_cells=cells, entry_rows=entry_rows)]
    out += [span("kernel.entries", t + 0.01 * (k + 1), dur, span_id=sid + 1 + k,
                 parent_id=sid, route=route, rows=rows, entries=rows * 3,
                 fetch_mb=0.1)
            for k, (route, rows, dur) in enumerate(entries)]
    return out


def ctx_of(spans):
    return {"spans": spans, "waves": WAVES, "rest_wall": 3.0,
            "trace": {"op_s": {}, "waves": 3}}


def test_the_readers_read_the_recorded_spans():
    spans = (fetch(10.1, 10, 120000, 0)
             + fetch(11.1, 20, 121000, 300, ("exact", 300, 0.02),
                     ("drained", 0, 0.004))
             + fetch(12.1, 30, 119000, 40, ("overflow", 40, 0.01)))
    ctx = ctx_of(spans)
    assert fleet_delta_cells.read(ctx) == 120000
    assert fleet_entry_rows.read(ctx) == 40
    # per wave 0 (no stretch), 0.024, 0.01: the median
    assert fleet_entries_s.read(ctx) == pytest.approx(0.01)


def test_a_dark_phase_b_reads_zero():
    spans = fetch(10.1, 10, 120000, 0) + fetch(11.1, 20, 118000, 0)
    ctx = ctx_of(spans)
    assert fleet_entry_rows.read(ctx) == 0
    assert fleet_entries_s.read(ctx) == 0.0
    assert fleet_delta_cells.read(ctx) == 119000


def test_the_readers_read_nothing_where_the_program_has_nothing():
    # a program whose kernel.fetch carries no wire attributes: None, not 0
    older = [span("kernel.fetch", 10.1, 0.05, fetch_mb=1.0,
                  changed_rows=50000.0),
             span("scheduler.solve", 10.0, 0.2, rows=100000)]
    ctx = ctx_of(older)
    for reader in (fleet_delta_cells, fleet_entry_rows, fleet_entries_s):
        assert reader.read(ctx) is None, reader.__name__
