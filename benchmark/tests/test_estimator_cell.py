"""The cell ``fed-100c-estimator.node-churn`` at its rehearsal size, on
whatever device is there (the CPU): a sound run is correct and prints no
time, rate or device metric; its control comes out not correct; with the
timed path broken underneath, ``correct`` comes out false, once for each
fault the estimator path can have (answers never folded; node state never
taken in; the batch sent back to the host path). Then the four readers the
cell brings, each over a small hand-written span list and a reduced trace,
and ``None`` where the program records nothing for them to read."""

import json

import pytest

from benchmark import control, run
from benchmark.metrics import (
    estimator_device_s,
    estimator_refresh_s,
    estimator_upload_mb,
    node_sum_roofline,
)
from benchmark.reference import estimate
from benchmark.roofline_estimator import least_seconds, node_sum_count

CELL = "fed-100c-estimator.node-churn"


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
    assert (checks["estimator_decided_rows"]["value"]
            >= checks["estimator_decided_rows"]["limit"] > 0)
    assert list(res)[-1] == "checks"


def test_traced_rehearsal_reads_the_estimator_layers(capsys):
    res = _run(capsys, trace=1, seconds="5")
    assert res["correct"] is True and res["metrics"] == {}
    assert {"estimator_refresh_s", "estimator_upload_mb", "fleet_host_self_s",
            "fleet_pre_dispatch_s", "prologue_self_s", "compiles_in_window",
            "spans_dropped"} <= set(res["per_layer_read"])
    assert "busy_s" not in res["device"] and "breakdown" not in res


@pytest.mark.parametrize("seed", [1, 2, 2147483777])
def test_the_control_is_not_correct(seed):
    checks = control.control_checks(CELL, seed, 40, rehearse=True)
    checks.pop("_failed")
    assert run.verdict(checks) is False
    assert checks["mismatched_rows"]["value"] > 0
    # the control's rows ARE the rows the estimator decides
    assert (checks["mismatched_rows"]["value"]
            == checks["estimator_decided_rows"]["value"])


def test_answers_never_folded_are_not_correct(capsys, monkeypatch):
    from karmada_tpu.scheduler.fleet import FleetTable

    monkeypatch.setattr(FleetTable, "_estimates_moved", lambda self: False)
    res = _run(capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


def test_node_state_never_taken_in_is_not_correct(capsys, monkeypatch):
    from karmada_tpu.estimator.accurate import NodeTable

    real = NodeTable.sync

    def sync(self, members):  # the first upload stands for ever
        if self.dev is not None:
            return {"members": 0, "nodes": 0, "bytes": 0}
        return real(self, members)

    monkeypatch.setattr(NodeTable, "sync", sync)
    res = _run(capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


def test_a_batch_sent_back_to_the_host_path_ends_the_set_up(monkeypatch):
    from karmada_tpu.scheduler import TensorScheduler

    monkeypatch.setattr(TensorScheduler, "_host_only_estimators",
                        lambda self: bool(self.extra_estimators))
    with pytest.raises(SystemExit, match="left the fleet path"):
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.3",
                  "--trace", "0"], rehearse=True)


# -- the readers --------------------------------------------------------------

WAVES = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]
CFG = {"clusters": 100, "resource_dims": 3, "fleet": {"nodes": 5000},
       "request_profiles": [{}] * 8}
PEAK = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def span(name, start, dur, span_id=0, parent_id=None, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": start, "duration_s": dur, "attrs": attrs}


def refresh(t, sid, scale=1.0):
    """One wave's estimator spans at their true intervals, starting at t:
    sync and dispatch under refresh, the fold after it."""
    d = 0.001 * scale
    return [
        span("estimator.refresh", t, 10 * d, span_id=sid,
             requeried_clusters=100),
        span("estimator.sync", t + d, 6 * d, span_id=sid + 1, parent_id=sid,
             members=100, nodes=500000, upload_mb=12.288 * scale),
        span("estimator.dispatch", t + 7 * d, 2 * d, span_id=sid + 2,
             parent_id=sid, profiles=8, members=100),
        span("estimator.fold", t + 10 * d, d, span_id=sid + 3, profiles=8,
             clusters=100),
        span("kernel.host", t + 11 * d, d, span_id=sid + 4, phase="sync"),
    ]


def ctx_of(spans, op_s=None, waves=4):
    return {"spans": spans, "waves": WAVES, "rest_wall": 3.0, "cfg": CFG,
            "peak": PEAK, "trace": {"op_s": op_s or {}, "waves": waves}}


def test_span_readers_take_the_median_wave():
    ctx = ctx_of(refresh(10.1, 10) + refresh(11.1, 20, 2.0)
                 + refresh(12.1, 30, 3.0)
                 + refresh(5.0, 40, 9.0))  # before the waves: not read
    # self time: refresh 10 - (6 + 2) = 2, sync 6, dispatch 2, fold 1
    assert estimator_refresh_s.read(ctx) == pytest.approx(0.011 * 2)
    assert estimator_upload_mb.read(ctx) == pytest.approx(12.288 * 2)


def test_device_readers_read_the_node_sum_kernel():
    ctx = ctx_of([], {"jit_node_sum_table": 0.04, "jit__fleet_pass": 0.3})
    assert estimator_device_s.read(ctx) == pytest.approx(0.01)
    count = node_sum_count(100, 5000, 3, 8)
    assert count["bytes"] == 100 * 5000 * 3 * 8 + 8 * 3 * 8 + 8 * 100 * 4
    assert count["int_ops"] == 8 * 100 * 5000 * 7
    least, bound = least_seconds(count, PEAK)
    assert bound == "bytes" and 1.4e-5 < least < 1.5e-5
    share = node_sum_roofline.read(ctx)
    assert share == pytest.approx(100 * least / 0.01) and 0 < share < 100
    assert any("node_sum_roofline bound=bytes" in n for n in ctx["notes"])


def test_readers_read_nothing_where_the_program_has_no_estimator_path():
    parent = [span("kernel.host", 10.2, 0.01, phase="sync", upload_mb=0.1),
              span("scheduler.solve", 10.1, 0.2)]
    ctx = ctx_of(parent, {"jit__fleet_pass": 0.3})
    for reader in (estimator_refresh_s, estimator_upload_mb,
                   estimator_device_s, node_sum_roofline):
        assert reader.read(ctx) is None, reader.__name__


def test_the_reference_node_sum_and_merge():
    import numpy as np

    free = np.asarray([[[4000, 8 << 30, 10], [1900, 8 << 30, 0]],
                       [[-5, 1 << 30, 3], [2000, 1 << 30, 3]]], np.int64)
    reqs = np.asarray([[1000, 1 << 30, 1], [0, 0, 0]], np.int64)
    # member 0: node 0 fits min(4, 8, 10), node 1 has no pod left;
    # member 1: an overcommitted node reads as 0, node 1 fits min(2, 1, 3)
    assert estimate.node_sum(free, reqs).tolist() == [[4, 1], [0, 0]]
    table = np.asarray([[7, -1, 2**31 - 1]], np.int64)
    answers = np.asarray([[9, 5, -1]], np.int64)
    assert estimate.min_merge(table, answers).tolist() == [[7, 5, 2**31 - 1]]
