"""The harness end to end at the rehearsal size, on whatever device is
there (the CPU): a sound run is correct and prints no time, rate or device
metric; with the timed path broken underneath, ``correct`` comes out false,
once for each fault a cell can have:

- an answer altered where it is produced (TensorScheduler.schedule);
- a step that leaves the state unchanged (update_snapshot that swaps
  nothing; schedule that answers the previous wave; a plane whose store
  drops every other wave's writes; a scheduler that stamps a rebalanced
  binding as rescheduled and keeps its old division; a status collection
  that never reads the members' moved load).

Half a batch left out is the stale-schedule fault on the rows left out; no
cell here crosses chips."""

import json

import pytest

from benchmark import run

CELLS = ["rebalance-100kx100.drift", "fed-100c.rebalance"]


def _run(cell, capsys, seed=2147483777, trace=0, seconds="0.6"):
    res = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                    seconds, "--trace", str(trace)], rehearse=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_prints_no_device_metric(cell, capsys):
    res = _run(cell, capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"] == {} and res["rehearsal"] is True
    assert res["device"]["platform"] != "tpu"
    assert list(res)[-1] == "checks"
    assert all(set(c) >= {"value", "limit"} for c in res["checks"].values())


def test_traced_rehearsal_reads_the_layers_and_prints_none(capsys):
    res = _run("rebalance-100kx100.drift", capsys, trace=1, seconds="5")
    assert res["correct"] is True and res["metrics"] == {}
    assert {"loadgen_share", "compiles_in_window", "prologue_self_s",
            "fleet_host_self_s"} <= set(res["per_layer_read"])
    assert "busy_s" not in res["device"] and "breakdown" not in res


@pytest.fixture
def altered_answers(monkeypatch):
    from karmada_tpu.scheduler import TensorScheduler
    from karmada_tpu.scheduler.core import ScheduleResult

    real = TensorScheduler.schedule

    def schedule(self, problems, *a, **kw):
        out = []
        for r in real(self, problems, *a, **kw):
            clusters = dict(r.clusters)
            if clusters:
                k = next(iter(clusters))
                clusters[k] += 1
            out.append(ScheduleResult(
                key=r.key, clusters=clusters, feasible=tuple(r.feasible),
                affinity_name=r.affinity_name, error=r.error))
        return out

    monkeypatch.setattr(TensorScheduler, "schedule", schedule)


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, capsys, altered_answers):
    res = _run(cell, capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


def test_snapshot_left_unchanged_is_not_correct(capsys, monkeypatch):
    from karmada_tpu.scheduler import TensorScheduler

    monkeypatch.setattr(TensorScheduler, "update_snapshot",
                        lambda self, snap: True)
    res = _run("rebalance-100kx100.drift", capsys)
    assert res["correct"] is False


def test_answers_of_the_wave_before_are_not_correct(capsys, monkeypatch):
    from karmada_tpu.scheduler import TensorScheduler
    from karmada_tpu.scheduler.core import ScheduleResult

    real = TensorScheduler.schedule
    held = {}

    def schedule(self, problems, *a, **kw):
        out = [ScheduleResult(key=r.key, clusters=dict(r.clusters),
                              error=r.error)
               for r in real(self, problems, *a, **kw)]
        prev, held["out"] = held.get("out", out), out
        return prev

    monkeypatch.setattr(TensorScheduler, "schedule", schedule)
    res = _run("rebalance-100kx100.drift", capsys)
    assert res["correct"] is False


def test_plane_that_drops_writes_is_not_correct(capsys, monkeypatch):
    from karmada_tpu.utils.store import Store, obj_kind

    real_apply = Store.apply

    def apply(self, obj, *a, **kw):
        if (obj_kind(obj) == "WorkloadRebalancer"
                and int(obj.meta.name.rsplit("-", 1)[1]) % 2):
            return obj  # every other wave acknowledged, never written
        return real_apply(self, obj, *a, **kw)

    monkeypatch.setattr(Store, "apply", apply)
    res = _run("fed-100c.rebalance", capsys)
    assert res["correct"] is False
    assert res["checks"]["stale_bindings"]["value"] > 0


def test_stamped_and_not_solved_is_not_correct(capsys, monkeypatch):
    """The fault the review named: a rebalanced binding is stamped as
    rescheduled, the solve and the Work render are skipped."""
    from karmada_tpu.controllers.scheduler_controller import (
        SchedulerController,
    )

    real = SchedulerController._needs_scheduling

    def needs(self, rb):
        should, fresh = real(self, rb)
        if fresh and rb.spec.clusters:
            rb.status.last_scheduled_time = rb.spec.reschedule_triggered_at
            rb.status.scheduler_observed_generation = rb.meta.generation
            return False, False
        return should, fresh

    monkeypatch.setattr(SchedulerController, "_needs_scheduling", needs)
    res = _run("fed-100c.rebalance", capsys)
    assert res["correct"] is False
    checks = res["checks"]
    assert checks["stale_bindings"]["value"] == 0
    assert checks["mismatched_rows"]["value"] > 0
    assert checks["wrong_works"]["value"] > 0


def test_members_load_never_read_is_not_correct(capsys, monkeypatch):
    from karmada_tpu.controllers.cluster import ClusterStatusController

    monkeypatch.setattr(ClusterStatusController, "collect_all",
                        lambda self: None)
    res = _run("fed-100c.rebalance", capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0
