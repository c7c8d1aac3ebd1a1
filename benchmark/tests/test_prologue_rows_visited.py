"""``prologue_rows_visited`` over hand-written span lists (``tracer.dump()``
dicts): per wave the sum of the ``rows`` the ``scheduler.pack`` spans
carry, the median over the waves, None where no pack carries ``rows``; and
the cl2load cell's rehearsal, whose waves move a generation under a
standing ``mask_token``: ``correct``, and the prologue visits fewer
positions than the batch holds."""

import json

import pytest

from benchmark import run
from benchmark import trace as trace_mod
from benchmark.metrics import prologue_rows_visited

WAVES = [(10.0, 10.5), (11.0, 11.5), (12.0, 12.5)]


def span(name, span_id, parent, start, dur, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent,
            "start": start, "duration_s": dur, "attrs": attrs}


def full_pass(t, first_id, rows, kept):
    r = first_id
    return [
        span("scheduler.schedule", r, None, t, 0.17, rows=rows + kept,
             path="full"),
        span("scheduler.identity", r + 1, r, t, 0.007, rows=rows + kept,
             hit=0, moved=rows),
        span("scheduler.pack", r + 2, r, t + 0.007, 0.01, rows=rows,
             kept=kept),
        span("scheduler.compile", r + 3, r + 2, t + 0.007, 0.001, rows=rows),
        span("scheduler.eligible", r + 4, r + 2, t + 0.008, 0.008,
             rows=rows, fleet_rows=rows + kept),
        span("scheduler.solve", r + 5, r, t + 0.02, 0.14),
    ]


def test_the_packs_of_a_wave_are_summed_and_the_median_taken():
    spans = (full_pass(10.0, 1, 9750, 90250)
             + full_pass(11.0, 11, 9760, 90240)
             + full_pass(12.0, 21, 100000, 0)  # a walk
             + full_pass(3.0, 31, 7, 99993))  # set-up's: outside the waves
    assert prologue_rows_visited.read(
        {"spans": spans, "waves": WAVES}) == 9760
    # a delta that tried one row, then the walk: both packs of the wave
    spans[6 + 2]["attrs"]["rows"] = 99000  # the second wave's pack
    tried = span("scheduler.pack", 99, 11, 11.001, 0.0001, rows=1)
    assert prologue_rows_visited.read(
        {"spans": spans + [tried], "waves": WAVES}) == 99001


def test_nothing_to_read_reads_none():
    identity = [
        span("scheduler.schedule", 1, None, 10.0, 0.05, rows=100000,
             path="identity"),
        span("scheduler.identity", 2, 1, 10.0, 0.007, rows=100000, hit=1,
             moved=0),
        span("scheduler.solve", 3, 1, 10.007, 0.04),
    ]
    assert prologue_rows_visited.read(
        {"spans": identity, "waves": WAVES}) is None
    bare = [span("scheduler.pack", 1, None, 10.0, 0.06)]
    assert prologue_rows_visited.read(
        {"spans": bare, "waves": WAVES}) is None
    assert prologue_rows_visited.read({"spans": [], "waves": WAVES}) is None


def test_the_cl2load_rehearsal_visits_its_moved_positions(
        capsys, monkeypatch):
    cell = "fed-100c-cl2load.size-drift"
    _, _, cfg, traffic = run.load_cell(cell, True)
    read = {}
    per_layer = trace_mod.per_layer

    def kept(bench, name, ctx):
        read.update(per_layer(bench, name, ctx))
        return read

    monkeypatch.setattr(trace_mod, "per_layer", kept)
    res = run.main(["--workload", cell, "--seed", "2147483777", "--seconds",
                    "3", "--trace", "1"], rehearse=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["mismatched_rows"]["value"] == 0
    n = cfg["deployments"]
    visited = read["prologue_rows_visited"]["value"]
    # consecutive waves differ at no more than twice the step's share
    assert 0 < visited <= 2 * float(traffic["scale_share"]) * n + 3 < n
