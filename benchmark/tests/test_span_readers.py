"""The per-layer readers that PR 25 added, each over a small hand-written
span list (``tracer.dump()`` dicts), and ``None`` where what a reader reads
is missing, as it is in a program that predates the spans. Then a traced
rehearsal of both cells lists the new names among what it read."""

import json

import pytest

from benchmark import run
from benchmark.metrics import (
    fence_window_s,
    fleet_fetch_mb,
    fleet_post_device_s,
    fleet_pre_dispatch_s,
    full_gc_share,
    reconcile_useful_share,
    spans_dropped,
)

WAVES = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]


def span(name, start, dur, **attrs):
    return {"name": name, "span_id": 0, "parent_id": None, "start": start,
            "duration_s": dur, "attrs": attrs}


def fleet_pass(t, scale=1.0):
    """One pass's kernel spans at their true intervals, starting at t."""
    d = 0.01 * scale
    return [
        span("kernel.host", t, d, phase="upsert"),
        span("kernel.host", t + d, d, phase="sync", upload_mb=0.1),
        span("kernel.host", t + 2 * d, d, phase="prep"),
        span("kernel.dispatch", t + 3 * d, 2 * d),
        span("kernel.device", t + 5 * d, 10 * d, kind="device"),
        span("kernel.fetch", t + 15 * d, 4 * d, fetch_mb=0.5 * scale),
        span("kernel.host", t + 19 * d, 3 * d, phase="post"),
    ]


def ctx_of(spans):
    return {"spans": spans, "waves": WAVES, "rest_wall": 3.0}


def test_fleet_readers_take_the_median_wave():
    ctx = ctx_of(fleet_pass(10.1) + fleet_pass(11.1, 2.0) + fleet_pass(12.1, 3.0)
                 + fleet_pass(5.0, 9.0))  # before the waves: not read
    assert fleet_pre_dispatch_s.read(ctx) == pytest.approx(0.05 * 2)
    assert fleet_post_device_s.read(ctx) == pytest.approx(0.07 * 2)
    assert fence_window_s.read(ctx) == pytest.approx(0.10 * 2)
    assert fleet_fetch_mb.read(ctx) == pytest.approx(1.0)


def test_two_passes_in_one_wave_are_summed():
    ctx = ctx_of(fleet_pass(10.1) + fleet_pass(10.5))
    assert fence_window_s.read(ctx) == pytest.approx(0.20)
    assert fleet_fetch_mb.read(ctx) == pytest.approx(1.0)


def test_fleet_readers_read_nothing_where_the_spans_are_missing():
    lumped = [  # a program that records one kernel.host a pass, after the fact
        span("kernel.host", 10.4, 0.03, upload_mb=0.1),
        span("kernel.dispatch", 10.4, 0.02),
        span("kernel.fetch", 10.4, 0.04, fetch_mb=0.5),
    ]
    assert fleet_pre_dispatch_s.read(ctx_of(lumped)) is None
    assert fleet_post_device_s.read(ctx_of(lumped)) is None
    assert fence_window_s.read(ctx_of(lumped)) is None
    assert fleet_fetch_mb.read(ctx_of(lumped)) == pytest.approx(0.5)
    plane_only = [span("controller.binding", 10.2, 0.3, items=4)]
    for reader in (fleet_pre_dispatch_s, fleet_post_device_s,
                   fence_window_s, fleet_fetch_mb, reconcile_useful_share):
        assert reader.read(ctx_of(plane_only)) is None, reader.__name__


def test_reconcile_useful_share_counts_the_drains_that_can_tell():
    spans = [
        span("controller.scheduler", 10.1, 0.2, items=1, keys=2000, noop=1950),
        span("controller.binding", 10.4, 0.2, items=1, keys=50, noop=0),
        span("controller.execution", 10.7, 0.2, items=9, keys=400),  # no noop
        span("controller.scheduler", 9.0, 0.2, keys=7, noop=7),  # before
        span("settle", 10.0, 0.9, keys=1, noop=1),  # not a controller
    ]
    assert reconcile_useful_share.read(ctx_of(spans)) == pytest.approx(
        100.0 * 100 / 2050)


def test_full_gc_share_reads_the_full_collections_of_the_stretch():
    spans = [
        span("runtime.gc", 10.5, 0.3, generation=2, collected=10),
        span("runtime.gc", 12.2, 0.3, generation=2, collected=0),
        span("runtime.gc", 9.0, 5.0, generation=2, collected=0),  # before
        span("controller.binding", 10.2, 0.9, items=1),
    ]
    assert full_gc_share.read(ctx_of(spans)) == pytest.approx(20.0)
    # the program has the span and no full collection fell in: 0, not None
    assert full_gc_share.read(ctx_of(spans[3:])) == 0.0


def test_spans_dropped_reads_the_programs_tracer():
    from karmada_tpu.utils.tracing import tracer

    tracer.clear()
    assert spans_dropped.read({}) == 0.0


@pytest.mark.parametrize("cell,names", [
    ("rebalance-100kx100.drift",
     {"fleet_pre_dispatch_s", "fleet_post_device_s", "fence_window_s",
      "fleet_fetch_mb", "full_gc_share", "spans_dropped"}),
    # the rehearsal's plane is too small for its waves to reach the fleet
    # kernel, so no kernel.* span is there to read
    ("fed-100c.rebalance",
     {"reconcile_useful_share", "full_gc_share", "spans_dropped"}),
])
def test_traced_rehearsal_lists_the_new_names(cell, names, capsys):
    res = run.main(["--workload", cell, "--seed", "2147483777", "--seconds",
                    "5", "--trace", "1"], rehearse=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["per_layer_read"] == res["per_layer_read"]
    assert res["correct"] is True and res["metrics"] == {}
    assert names <= set(res["per_layer_read"])
    if "reconcile_useful_share" not in names:
        assert "reconcile_useful_share" not in res["per_layer_read"]
