"""The wire that brings a fleet pass's answers home, traced: ``kernel.fetch``
carries ``delta_rows`` / ``delta_cells`` / ``entry_rows``, each phase-B
stretch is a ``kernel.entries`` child of it at its true interval (route
overflow | exact | speculative | drained), and the wire's counters add the
same numbers once a pass. Phase B is forced: rows past 62 changed cells, a
delta buffer that overflows, a speculation used and one drained. Every
pass's answers equal the host path's."""

import pytest

from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot, TensorScheduler
from karmada_tpu.utils.builders import dynamic_weight_placement, synthetic_fleet
from karmada_tpu.utils.metrics import (
    fleet_changed_rows,
    fleet_delta_cells,
    fleet_entry_speculations,
)
from karmada_tpu.utils.quantity import parse_resource_list
from karmada_tpu.utils.tracing import tracer

REQ = parse_resource_list({"cpu": "250m", "memory": "512Mi"})
ROWS = 512


def _problems(replicas):
    pl = dynamic_weight_placement()
    return [
        BindingProblem(key=f"b{i}", placement=pl, replicas=r, requests=REQ,
                       gvk="apps/v1/Deployment")
        for i, r in enumerate(replicas)
    ]


def _counts():
    return (fleet_changed_rows.value(route="delta"),
            fleet_changed_rows.value(route="entries"),
            fleet_delta_cells.value(),
            fleet_entry_speculations.value(outcome="used"),
            fleet_entry_speculations.value(outcome="drained"))


@pytest.fixture
def run():
    """An engine over 600 members, settled on 100 replicas a row;
    ``run(replicas)`` schedules a new list, checks its answers and the
    wire's invariants, and returns (the kernel.fetch attrs, the
    kernel.entries spans, the counters' increments, the fleet table)."""
    snap = ClusterSnapshot(synthetic_fleet(600, seed=44))
    eng = TensorScheduler(snap, chunk_size=128)
    eng.fleet_threshold = 1
    host = TensorScheduler(snap)

    def go(replicas):
        problems = _problems(replicas)
        before = _counts()
        tracer.clear()
        res = eng.schedule(problems)
        spans = tracer.dump()
        after = _counts()
        want = host._schedule_host(
            problems, [host._compiled(p.placement) for p in problems])
        for w, g in zip(want, res):
            assert (w.success, w.clusters) == (g.success, g.clusters), w.key
        fetch = [s for s in spans if s["name"] == "kernel.fetch"]
        assert len(fetch) == 1, [s["name"] for s in spans]
        entries = [s for s in spans if s["name"] == "kernel.entries"]
        f = fetch[0]
        for s in entries:
            assert s["parent_id"] == f["span_id"]
            assert f["start"] <= s["start"]
            assert (s["start"] + s["duration_s"]
                    <= f["start"] + f["duration_s"] + 1e-6)
        a = f["attrs"]
        assert a["delta_rows"] + a["entry_rows"] == a["changed_rows"]
        assert sum(s["attrs"]["rows"] for s in entries) == a["entry_rows"]
        inc = tuple(y - x for x, y in zip(before, after))
        assert inc[:3] == (a["delta_rows"], a["entry_rows"], a["delta_cells"])
        return a, entries, inc, eng._fleet

    go([100] * ROWS)
    go([100] * ROWS)
    return go


def test_a_pass_with_nothing_changed_carries_zeros(run):
    a, entries, inc, _ = run([100] * ROWS)
    assert (a["changed_rows"], a["delta_rows"], a["delta_cells"],
            a["entry_rows"]) == (0, 0, 0, 0)
    assert entries == [] and inc == (0,) * 5


def test_rows_past_62_changed_cells_come_home_on_the_entry_wire(run):
    # even rows 100 -> 3 replicas (97 cells go), odd rows 100 -> 90 (10 go)
    a, entries, inc, _ = run([3 if i % 2 == 0 else 90 for i in range(ROWS)])
    assert a["changed_rows"] == ROWS
    assert (a["delta_rows"], a["delta_cells"]) == (ROWS // 2, 10 * ROWS // 2)
    assert a["entry_rows"] == ROWS // 2
    assert [s["attrs"]["route"] for s in entries] == ["overflow"]
    assert entries[0]["attrs"]["entries"] == 3 * ROWS // 2
    assert entries[0]["attrs"]["fetch_mb"] > 0


def test_an_overflowing_delta_buffer_and_the_speculations(run):
    # 100 -> 40: 60 cells a row, 30,720 against the delta buffer's 8,192
    a, entries, inc, table = run([40] * ROWS)
    assert (a["delta_rows"], a["entry_rows"]) == (0, ROWS)
    assert a["delta_cells"] == 0
    assert [s["attrs"]["route"] for s in entries] == ["exact"]
    assert inc[3:] == (0, 0)

    # the cap tuning grows the delta buffer past the last pass's cells and
    # then expects the delta wire, so it never speculates at this size:
    # forget that demand (as a sub pass's fresh tuning does) so the next
    # pass dispatches phase B behind phase A on speculation
    table._last_dtotal = None
    # 40 -> 20: 20 cells a row, 10,240 over the 8,192 buffer; the
    # speculation sized from the last pass's 20,480 entries holds them
    a, entries, inc, table = run([20] * ROWS)
    assert (a["delta_rows"], a["entry_rows"]) == (0, ROWS)
    assert [s["attrs"]["route"] for s in entries] == ["speculative"]
    assert inc[3:] == (1, 0)

    table._last_dtotal = None
    # 20 -> 40: 20,480 entries past the speculation sized from 10,240:
    # the exact fetch brings them home, the speculation is drained
    a, entries, inc, _ = run([40] * ROWS)
    assert (a["delta_rows"], a["entry_rows"]) == (0, ROWS)
    routes = [s["attrs"]["route"] for s in entries]
    assert routes == ["exact", "drained"]
    drained = entries[1]["attrs"]
    assert (drained["rows"], drained["entries"], drained["fetch_mb"]) == (
        0, 0, 0.0)
    assert inc[3:] == (0, 1)
