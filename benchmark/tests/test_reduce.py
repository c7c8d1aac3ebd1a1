"""The trace reduction on hand-made intervals and on the small recorded
trace (recorded_trace.json: device module events and harness annotations
of two dirty-row waves at 100k x 5k on a TPU v5e, recorded in this PR's first
session from a cell since taken out, as trace.extract returned them)."""

import json
import os

from benchmark import reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def test_busy_union_merges_overlaps():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("a", 32, 1)]
    ns, merged = reduce.busy_union(ev)
    assert merged == [[0, 15], [30, 35]]
    assert ns == 20


def test_clip_and_op_sums():
    ev = [("jit_f(123)", 0, 10), ("jit_f(456)", 20, 10), ("jit_g(1)", 5, 2)]
    cut = reduce.clip(ev, 5, 25)
    assert cut == [("jit_f(123)", 5, 5), ("jit_f(456)", 20, 5), ("jit_g(1)", 5, 2)]
    assert reduce.op_sums(cut) == {"jit_f": 10, "jit_g": 2}


def test_gaps_and_attribution():
    _, merged = reduce.busy_union([("k", 10, 10), ("k", 40, 10)])
    idle = reduce.gaps(merged, 0, 60)
    assert idle == [(0, 10), (20, 40), (50, 60)]
    spans = [("outer", 0, 45), ("inner", 25, 10)]
    by = reduce.attribute_gaps(idle, spans)
    assert by == {"outer": 10, "inner": 20, "_no_span_open_": 10}
    assert reduce.top(by, 2) == [["inner", 2e-8], ["outer", 1e-8]]


def test_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["device"]]
    lo, hi = rec["lo"], rec["hi"]
    cut = reduce.clip(events, lo, hi)
    ns, merged = reduce.busy_union(cut)
    sums = reduce.op_sums(cut)
    idle = reduce.gaps(merged, lo, hi)
    assert ns + sum(b - a for a, b in idle) == hi - lo
    assert ns == rec["expect"]["busy_ns"]
    assert sums == rec["expect"]["op_sums"]
    by = reduce.attribute_gaps(idle, [tuple(e) for e in rec["host"]])
    assert sum(by.values()) == (hi - lo) - ns
    assert set(by) <= {"harness.schedule", "_no_span_open_"}
