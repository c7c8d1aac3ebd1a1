"""ISSUE 25: spans where the work happens.

- fleet and engine phases lie at their true intervals: the children of one
  ``scheduler.solve`` are disjoint, ordered and inside it, and each name's
  sum is the pass's ``last_breakdown`` entry (full pass and delta pass);
- ``record`` without ``start`` still ends now;
- every drain carries its work counts: settled bindings written again
  unchanged are a no-op storm in ``controller.scheduler``, a rebalancer
  wave is not;
- the collector from inside: counters for every collection, a
  ``runtime.gc`` span for full ones only, no lock taken in the callback;
- ``jax.named_scope`` names on the stages of the fleet kernels;
- new-trace flags are per pass (PERF.md section 7, fault 2).

ISSUE 35: an engine wave accounted for from entry to answer. One engine
driven through each path (identity, delta, full, host): one
``scheduler.schedule`` root with the ``path`` it took, its children inside
it, disjoint and ordered, the identity check and the full prologue's stages
as spans at the intervals ``last_breakdown`` times.

ISSUE 36: under a moved ``mask_token`` the full path diffs a batch of the
armed batch's length: the sweep is a ``scheduler.identity`` child of
``scheduler.pack``, the stages' ``rows`` are the positions visited, and a
pass sweeps the batch once (``scheduler.rearm`` makes no sweep).

Where the generation moved under a standing ``mask_token`` the
same diff takes the identity branch's sweep: ``scheduler.identity`` is the
root's child before ``scheduler.pack``, and pack holds the other three
stages.
"""

from __future__ import annotations

import copy
import gc
import itertools
import threading
import time

import numpy as np
import pytest

from karmada_tpu.api import PropagationPolicy, PropagationSpec, ResourceSelector
from karmada_tpu.api.core import ObjectMeta
from karmada_tpu.controllers import (
    ObjectReferenceSelector,
    WorkloadRebalancer,
    WorkloadRebalancerSpec,
)
from karmada_tpu.controlplane import ControlPlane
from karmada_tpu.scheduler import (
    BindingProblem,
    ClusterSnapshot,
    TensorScheduler,
)
from karmada_tpu.utils import metrics
from karmada_tpu.utils.builders import (
    dynamic_weight_placement,
    new_cluster,
    new_deployment,
    synthetic_fleet,
)
from karmada_tpu.scheduler import core as core_mod
from karmada_tpu.scheduler import fleet as fleet_mod
from karmada_tpu.scheduler import select as select_mod
from karmada_tpu.utils.tracing import (
    GcWatch,
    WaveTracer,
    gc_watch,
    span_name_registered,
    tracer,
)
from karmada_tpu.utils.worker import DONE, REQUEUE, Runtime, WriteCount

from test_delta_solve import build_problems, churned
from test_fleet_select import _clusters, _generation, _problems

HOST_KEYS = ("upsert", "sync", "prep", "post")


# --------------------------------------------------------------------------
# A: phases at their true intervals
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    snap = ClusterSnapshot(synthetic_fleet(48, seed=7, taint_fraction=0.08))
    eng = TensorScheduler(snap, trace_manifest="")
    eng.fleet_threshold = 1
    problems = build_problems(snap, 600)
    eng.schedule(problems)
    eng.schedule(problems)  # arm the batch-identity path
    return eng, problems


def _solve_tree(spans: list) -> tuple:
    """(the one scheduler.solve span, its kernel.* children by start)."""
    [solve] = [s for s in spans if s["name"] == "scheduler.solve"]
    kids = sorted(
        (s for s in spans if s["parent_id"] == solve["span_id"]
         and s["name"].startswith("kernel.")),
        key=lambda s: s["start"],
    )
    return solve, kids


def _check_tree(solve, kids, breakdown) -> None:
    eps = 2e-6  # dump() rounds start and duration to the microsecond
    assert kids, "the pass recorded no kernel phase"
    for a, b in zip(kids, kids[1:]):
        assert a["start"] + a["duration_s"] <= b["start"] + eps, (a, b)
    assert kids[0]["start"] >= solve["start"] - eps
    last = kids[-1]
    assert (last["start"] + last["duration_s"]
            <= solve["start"] + solve["duration_s"] + eps)
    sums: dict = {}
    for s in kids:
        sums[s["name"]] = sums.get(s["name"], 0.0) + s["duration_s"]
    want = {
        "kernel.host": sum(breakdown.get(k, 0.0) for k in HOST_KEYS),
        "kernel.dispatch": breakdown.get("dispatch", 0.0),
        "kernel.device": breakdown.get("device", 0.0),
        "kernel.fetch": breakdown.get("fetch", 0.0),
    }
    for name, seconds in want.items():
        assert sums.get(name, 0.0) == pytest.approx(
            seconds, abs=len(kids) * 1e-6
        ), name


class TestPhaseIntervals:
    def test_full_pass_children_disjoint_ordered_inside(self, engine):
        eng, problems = engine
        tracer.clear()
        eng.schedule(problems)
        solve, kids = _solve_tree(tracer.dump())
        _check_tree(solve, kids, eng._fleet.last_breakdown)
        # host -> dispatch -> device -> fetch -> host, one host span a
        # stretch and the phase named on each
        assert [s["name"].split(".")[1] for s in kids] == [
            "host", "host", "host", "dispatch", "device", "fetch", "host",
        ]
        assert [s["attrs"].get("phase") for s in kids
                if s["name"] == "kernel.host"] == list(HOST_KEYS)
        uploads = [s for s in kids if "upload_mb" in s["attrs"]]
        assert [s["attrs"]["phase"] for s in uploads] == ["sync"]

    def test_delta_pass_children_disjoint_ordered_inside(self, engine):
        eng, problems = engine
        changed, _ = churned(problems, np.random.default_rng(1), 30)
        eng.schedule(changed)  # certifies and compiles the sub-batch trace
        changed2, _ = churned(changed, np.random.default_rng(2), 30)
        tracer.clear()
        eng.schedule(changed2)
        bd = eng._fleet.last_breakdown
        assert bd["dirty_rows"] == 30
        solve, kids = _solve_tree(tracer.dump())
        _check_tree(solve, kids, bd)
        # the replay of the untouched rows is a second post stretch
        assert [s["attrs"]["phase"] for s in kids
                if s["name"] == "kernel.host"][-2:] == ["post", "post"]

    def test_phase_histogram_observed_once_a_phase_a_pass(self, engine):
        eng, problems = engine
        h = metrics.kernel_phase_seconds
        before = h.snapshot()
        eng.schedule(problems)
        after = h.snapshot()
        for phase in ("host", "dispatch", "device", "fetch"):
            key = f'phase="{phase}"'
            assert after[key]["count"] - before[key]["count"] == 1, phase

    def test_engine_spans_start_where_the_work_started(self, engine):
        eng, problems = engine
        fresh = [p for p in build_problems(eng.snapshot, 600, seed=11)]
        tracer.clear()
        t0 = time.perf_counter()
        eng.schedule(fresh)  # new objects: the full prologue runs
        [pack] = [s for s in tracer.dump() if s["name"] == "scheduler.pack"]
        [solve] = [s for s in tracer.dump() if s["name"] == "scheduler.solve"]
        assert t0 <= pack["start"]
        assert pack["start"] + pack["duration_s"] <= solve["start"] + 2e-6


# --------------------------------------------------------------------------
# A2: the engine wave from entry to answer (ISSUE 35)
# --------------------------------------------------------------------------

ENGINE_SPANS = (
    "scheduler.schedule", "scheduler.identity", "scheduler.compile",
    "scheduler.spread", "scheduler.eligible", "scheduler.handoff",
    "scheduler.rearm",
)
STAGES = {"scheduler.compile": "compile", "scheduler.spread": "select",
          "scheduler.eligible": "eligible"}
#: pack's children where the batch was diffed (the swap diff): the sweep,
#: the distinct placements' compile, the moved positions, the spread rows
SWAP_STAGES = ["scheduler.identity", "scheduler.compile",
               "scheduler.eligible", "scheduler.spread"]


def _ring() -> list:
    """The completed Span objects of the ring (unrounded stamps)."""
    return [sp for w in tracer.waves() for sp in tracer.spans_for(w)]


def _children(spans: list, parent) -> list:
    return sorted((s for s in spans if s.parent_id == parent.span_id),
                  key=lambda s: s.start)


def _inside_disjoint_ordered(parent, kids: list) -> None:
    eps = 1e-9  # a recorded span's end is start + duration: one rounding
    for a, b in zip(kids, kids[1:]):
        assert a.end <= b.start + eps, (a.name, b.name)
    if kids:
        assert parent.start <= kids[0].start + eps, kids[0].name
        assert kids[-1].end <= parent.end + eps, kids[-1].name


_TAINTS = itertools.count()


def _moved_token(eng) -> ClusterSnapshot:
    """The engine's members with one tainted anew: the filter fields move."""
    from karmada_tpu.api.cluster import Taint

    clusters = list(eng.snapshot.clusters)
    spare = next(cl for cl in clusters if not cl.spec.taints)
    spare.spec.taints = [
        Taint(key=f"issue35-{next(_TAINTS)}", effect="NoSchedule")]
    try:
        snap = ClusterSnapshot(clusters)
        assert snap.mask_token != eng.snapshot.mask_token
    finally:
        spare.spec.taints = []
    return snap


def _drive_identity(eng, problems):
    eng.schedule(problems)  # arms the batch, whatever ran before
    return problems, lambda: eng.schedule(problems)


def _drive_delta(eng, problems):
    eng.schedule(problems)
    changed, _ = churned(problems, np.random.default_rng(35), 30)
    return changed, lambda: eng.schedule(changed)


def _drive_full_token(eng, problems):
    eng.schedule(problems)
    snap = _moved_token(eng)

    def wave():
        assert eng.update_snapshot(snap)
        return eng.schedule(problems)
    return problems, wave


def _swapped(problems) -> list:
    """A new list in which 30 positions hold new objects of equal
    content."""
    batch = list(problems)
    for i in range(0, 30 * 7, 7):
        p = batch[i]
        batch[i] = BindingProblem(
            key=p.key, placement=p.placement, replicas=p.replicas,
            requests=dict(p.requests), gvk=p.gvk, prev=dict(p.prev),
            fresh=p.fresh)
    return batch


def _drive_full_swapped(eng, problems):
    """A moved token and a new list in which 30 positions hold new objects
    of equal content: the swap diff visits those."""
    eng.schedule(problems)
    snap = _moved_token(eng)
    batch = _swapped(problems)

    def wave():
        assert eng.update_snapshot(snap)
        return eng.schedule(batch)
    return batch, wave


def _drifted(eng) -> ClusterSnapshot:
    """The engine's members with other cpu allocations: availability alone
    moved, the filter fields stand."""
    clusters = copy.deepcopy(eng.snapshot.clusters)
    for j, cl in enumerate(clusters):
        summary = cl.status.resource_summary
        summary.allocated = dict(
            summary.allocated,
            cpu=summary.allocatable["cpu"] * (1 + j % 5) // 10)
    snap = ClusterSnapshot(clusters)
    assert snap.mask_token == eng.snapshot.mask_token
    return snap


def _drive_full_drifted(eng, problems):
    """Availability drifted and 30 positions hold new objects of equal
    content: the identity branch misses, the delta declines for the moved
    generation, the swap diff visits the 30 with the branch's sweep."""
    # packed anew from the members as they stand (the moved-token case
    # takes its taint off the shared objects again)
    assert eng.update_snapshot(ClusterSnapshot(eng.snapshot.clusters))
    eng.schedule(problems)
    snap = _drifted(eng)
    batch = _swapped(problems)

    def wave():
        assert eng.update_snapshot(snap)
        return eng.schedule(batch)
    return batch, wave


def _drive_full_host_row(eng, problems):
    eng.schedule(problems)
    batch = list(problems)
    names = eng.snapshot.names
    row = next(i for i, p in enumerate(batch) if p.replicas > 0)
    batch[row] = BindingProblem(
        key=batch[row].key, placement=batch[row].placement,
        replicas=batch[row].replicas, requests=batch[row].requests,
        gvk=batch[row].gvk,
        evict_clusters=tuple(names[: fleet_mod.K_EVICT + 1]))
    return batch, lambda: eng.schedule(batch)


PATHS = {
    # case: (driver, path, the root's children by start)
    "identity": (_drive_identity, "identity",
                 ["scheduler.identity", "scheduler.solve"]),
    # the one diff, then the moved positions' prologue in pack; the table
    # replays the rest
    "delta": (_drive_delta, "delta",
              ["scheduler.identity", "scheduler.pack", "scheduler.handoff",
               "scheduler.solve", "scheduler.rearm"]),
    # the same list under a moved token, and a swapped one: the sweep and
    # the diff lie INSIDE pack
    "full-moved-token": (
        _drive_full_token, "full",
        ["scheduler.pack", "scheduler.handoff", "scheduler.solve",
         "scheduler.rearm"]),
    "full-swapped": (
        _drive_full_swapped, "full",
        ["scheduler.pack", "scheduler.handoff", "scheduler.solve",
         "scheduler.rearm"]),
    # a moved generation under a standing token: the identity branch's
    # sweep and diff first, then the swap diff's stages in pack
    "full-drifted": (
        _drive_full_drifted, "full",
        ["scheduler.identity", "scheduler.pack", "scheduler.handoff",
         "scheduler.solve", "scheduler.rearm"]),
    # the id() sweep and diff come first; in pack the check of the one
    # moved row finds it left the fleet-eligible set, so the whole
    # prologue runs there
    "full-host-row": (
        _drive_full_host_row, "full",
        ["scheduler.identity", "scheduler.pack", "scheduler.handoff",
         "scheduler.solve", "scheduler.rearm"]),
}


class TestEngineWave:
    @pytest.mark.parametrize("case", sorted(PATHS))
    def test_one_root_names_the_path_and_holds_its_children(
            self, engine, case):
        eng, problems = engine
        drive, path, names = PATHS[case]
        batch, wave = drive(eng, problems)
        tracer.clear()
        wave()
        spans = _ring()
        [root] = [s for s in spans if s.name == "scheduler.schedule"]
        assert root.parent_id is None
        assert (root.attrs["path"], root.attrs["rows"]) == (path, len(batch))
        kids = _children(spans, root)
        assert [s.name for s in kids] == names
        _inside_disjoint_ordered(root, kids)
        ident = [s for s in spans if s.name == "scheduler.identity"]
        if case == "identity":
            [sp] = ident
            assert (sp.attrs["hit"], sp.attrs["moved"]) == (1, 0)
            # one clock read, two consumers
            assert sp.duration == pytest.approx(
                eng.last_breakdown["compile"], abs=1e-9)
        elif case == "delta":
            [sp] = ident
            assert (sp.attrs["hit"], sp.attrs["moved"]) == (0, 30)
            [pack] = [s for s in kids if s.name == "scheduler.pack"]
            assert (pack.attrs["rows"], pack.attrs["kept"]) == (
                30, len(batch) - 30)
            # the moved positions' stages, as a swapped batch's
            assert [s.name for s in _children(spans, pack)] == (
                SWAP_STAGES[1:])
            [solve] = [s for s in kids if s.name == "scheduler.solve"]
            assert solve.attrs["dirty_rows"] == 30
        elif case == "full-host-row":
            [sp] = ident
            assert (sp.attrs["hit"], sp.attrs["moved"]) == (0, 1)
            [pack] = [s for s in kids if s.name == "scheduler.pack"]
            assert pack.attrs["rows"] == len(batch)  # the walk
        elif case == "full-drifted":
            [sp] = ident
            assert sp.parent_id == root.span_id
            assert (sp.attrs["hit"], sp.attrs["moved"]) == (0, 30)
            [pack] = [s for s in kids if s.name == "scheduler.pack"]
            assert (pack.attrs["rows"], pack.attrs["kept"]) == (
                30, len(batch) - 30)
        else:
            # a moved token: the full path's own sweep and diff, in pack
            [sp] = ident
            [pack] = [s for s in kids if s.name == "scheduler.pack"]
            assert sp.parent_id == pack.span_id
            moved = 30 if case == "full-swapped" else 0
            assert (sp.attrs["hit"], sp.attrs["moved"]) == (0, moved)
            assert (pack.attrs["rows"], pack.attrs["kept"]) == (
                moved, len(batch) - moved)
        assert all(s.attrs["rows"] == len(batch) for s in ident)

    @pytest.mark.parametrize(
        "case",
        ["full-moved-token", "full-swapped", "full-host-row", "full-drifted"])
    def test_the_full_prologue_is_staged_under_pack(self, engine, case):
        eng, problems = engine
        drive, _, _ = PATHS[case]
        batch, wave = drive(eng, problems)
        tracer.clear()
        wave()
        spans = _ring()
        pack = [s for s in spans if s.name == "scheduler.pack"][-1]
        host_rows = 1 if case == "full-host-row" else 0
        # the positions the prologue visited: every one where it walked,
        # those holding another object where it diffed the batch
        visited = {"full-moved-token": 0, "full-swapped": 30,
                   "full-host-row": len(batch), "full-drifted": 30}[case]
        assert (pack.attrs["rows"], pack.attrs["kept"]) == (
            visited, len(batch) - visited)
        stages = _children(spans, pack)
        _inside_disjoint_ordered(pack, stages)
        if host_rows:
            assert [s.name for s in stages] == list(STAGES)
            compile_, spread, eligible = stages
        elif case == "full-drifted":
            # the sweep was the identity branch's, before pack
            assert [s.name for s in stages] == SWAP_STAGES[1:]
            compile_, eligible, spread = stages
            assert compile_.attrs["placements"] == len(
                {id(p.placement) for p in batch})
        else:
            assert [s.name for s in stages] == SWAP_STAGES
            ident, compile_, eligible, spread = stages
            assert (ident.attrs["rows"], ident.attrs["moved"]) == (
                len(batch), visited)
            assert compile_.attrs["placements"] == len(
                {id(p.placement) for p in batch})
        bd = eng.last_breakdown
        for sp in stages:
            if sp.name in STAGES:
                assert sp.duration == pytest.approx(
                    bd[STAGES[sp.name]], abs=1e-9), sp.name
        assert compile_.attrs["rows"] == visited
        assert (spread.attrs["rows"], spread.attrs["on_device"]) == (0, 0)
        assert (eligible.attrs["rows"], eligible.attrs["fleet_rows"]) == (
            visited, len(batch) - host_rows)
        # hand-off from pack's end to the table's door, re-arm from its
        # answer to the engine's
        [handoff] = [s for s in spans if s.name == "scheduler.handoff"]
        [solve] = [s for s in spans if s.name == "scheduler.solve"]
        [rearm] = [s for s in spans if s.name == "scheduler.rearm"]
        assert handoff.start == pack.end
        assert handoff.end <= solve.start + 1e-9
        assert solve.end <= rearm.start
        assert handoff.attrs["rows"] == len(batch) - host_rows
        assert rearm.attrs["host_rows"] == host_rows
        host = [s for s in spans if s.name == "scheduler.host"]
        if host_rows:
            [sp] = host
            assert sp.parent_id == rearm.span_id
            assert sp.attrs["rows"] == 1
        else:
            assert not host
            # the batch is armed again: the next pass takes the fast path
            tracer.clear()
            eng.schedule(batch)
            [root] = [s for s in _ring() if s.name == "scheduler.schedule"]
            assert root.attrs["path"] == "identity"

    @pytest.mark.parametrize("case", sorted(PATHS))
    def test_a_pass_sweeps_its_batch_once(self, engine, case, monkeypatch):
        """Whichever route a pass takes, ``id()`` reads each position once:
        the one diff (ResidentBatch.diff) keeps its ids for the re-arm (a
        walk no diff came before makes the one sweep there), and the table
        visits the diff's moved positions without a sweep of its own."""
        eng, problems = engine
        drive, _, _ = PATHS[case]
        batch, wave = drive(eng, problems)
        swept = [0]

        def counted(obj):
            swept[0] += isinstance(obj, BindingProblem)
            return id(obj)

        monkeypatch.setattr(core_mod, "id", counted, raising=False)
        monkeypatch.setattr(fleet_mod, "id", counted, raising=False)
        wave()
        assert swept[0] == len(batch)

    def test_a_host_selection_is_the_spread_stages_child(self):
        regions = [f"r{k}" for k in range(select_mod.R_CAP + 1)]
        clusters = _clusters(regions, c=30)
        problems = _problems(clusters)
        eng = TensorScheduler(_generation(clusters, 0), chunk_size=256)
        tracer.clear()
        eng.schedule(problems)
        spans = _ring()
        [root] = [s for s in spans if s.name == "scheduler.schedule"]
        assert root.attrs["path"] == "full"
        [spread] = [s for s in spans if s.name == "scheduler.spread"]
        [select] = [s for s in spans if s.name == "scheduler.select"]
        assert select.parent_id == spread.span_id
        assert spread.start <= select.start and select.end <= spread.end
        assert spread.attrs["on_device"] == 0
        assert spread.attrs["rows"] == select.attrs["rows"] > 0
        assert spread.duration == eng.last_breakdown["select"]
        # its FitErrors take the host path, inside the merge
        [rearm] = [s for s in spans if s.name == "scheduler.rearm"]
        [host] = [s for s in spans if s.name == "scheduler.host"]
        assert host.parent_id == rearm.span_id
        assert rearm.attrs["host_rows"] == host.attrs["rows"] > 0

    def test_below_the_threshold_the_path_is_host(self, engine):
        _, problems = engine
        small = problems[:40]
        eng = TensorScheduler(ClusterSnapshot(
            synthetic_fleet(48, seed=7, taint_fraction=0.08)),
            trace_manifest="")
        assert len(small) < eng.fleet_threshold
        tracer.clear()
        eng.schedule(small)
        spans = _ring()
        [root] = [s for s in spans if s.name == "scheduler.schedule"]
        assert (root.attrs["path"], root.attrs["rows"]) == ("host", 40)
        kids = _children(spans, root)
        assert [s.name for s in kids] == ["scheduler.pack", "scheduler.host"]
        _inside_disjoint_ordered(root, kids)
        assert [s.name for s in _children(spans, kids[0])] == list(STAGES)
        assert not [s for s in spans if s.name in (
            "scheduler.identity", "scheduler.handoff", "scheduler.rearm",
            "scheduler.solve")]

    def test_in_a_plane_settle_the_root_nests_under_the_pass(self):
        cp = ControlPlane(clock=lambda: 5000.0)
        for i in (1, 2, 3):
            cp.join_cluster(
                new_cluster(f"member{i}", cpu="100", memory="200Gi"))
        for i in range(6):
            cp.store.apply(new_deployment(f"app{i}", replicas=4))
        tracer.clear()
        cp.store.apply(_policy())
        cp.settle()
        spans = _ring()
        by_id = {s.span_id: s for s in spans}
        roots = [s for s in spans if s.name == "scheduler.schedule"]
        assert roots, "the settle ran no engine pass"
        for root in roots:
            parent = by_id[root.parent_id]
            assert parent.name == "scheduler.pass"
            assert parent.start <= root.start and root.end <= parent.end
            assert root.attrs["path"] in ("identity", "delta", "full", "host")

    def test_the_benchmarks_readers_read_the_engines_own_spans(self, engine):
        """An ``h h L h`` ring in small: what the program stamps is what the
        four readers take, wave for wave."""
        import statistics

        from benchmark.metrics import (
            identity_check_s,
            swap_prologue_rows,
            swap_prologue_s,
            swap_wave_s,
            wave_unspanned_s,
        )

        eng, problems = engine
        eng.schedule(problems)
        snap = _moved_token(eng)
        tracer.clear()
        waves = []
        for g in range(4):
            t0 = time.perf_counter()
            if g == 2:
                assert eng.update_snapshot(snap)
            eng.schedule(problems)
            waves.append((t0, time.perf_counter()))
        spans = tracer.dump()
        ctx = {"spans": spans, "waves": waves}
        roots = [s for s in spans if s["name"] == "scheduler.schedule"]
        assert [s["attrs"]["path"] for s in roots] == [
            "identity", "identity", "full", "identity"]
        swap = roots[2]
        assert swap_wave_s.read(ctx) == swap["duration_s"]
        own = sum(s["duration_s"] for s in spans
                  if s["parent_id"] == swap["span_id"] and s["name"] in (
                      "scheduler.pack", "scheduler.handoff",
                      "scheduler.rearm"))
        assert 0 < swap_prologue_s.read(ctx) == pytest.approx(own)
        assert swap_prologue_s.read(ctx) < swap_wave_s.read(ctx)
        # the same list came again: the diff kept every position
        assert swap_prologue_rows.read(ctx) == 0
        # one sweep a wave, the swap wave's (inside pack) among them
        ident = [s["duration_s"] for s in spans
                 if s["name"] == "scheduler.identity"]
        assert len(ident) == 4
        assert identity_check_s.read(ctx) == pytest.approx(
            statistics.median(ident))
        # the root covers the pass: what is left of a wave is the loop's own
        dark = wave_unspanned_s.read(ctx)
        assert 0 <= dark <= max(
            (b - a) - r["duration_s"] for (a, b), r in zip(waves, roots)
        ) + 2e-6

    @pytest.mark.parametrize("name", ENGINE_SPANS)
    def test_the_taxonomy_holds_every_name(self, name):
        assert span_name_registered(name)


class TestRecord:
    def test_record_without_start_ends_now(self):
        tr = WaveTracer()
        before = time.perf_counter()
        sp = tr.record("kernel.device", 0.25, kind="device")
        after = time.perf_counter()
        assert before <= sp.end <= after
        assert sp.end - sp.start == pytest.approx(0.25)

    def test_record_with_start_lies_at_its_interval(self):
        tr = WaveTracer()
        with tr.span("scheduler.solve") as parent:
            sp = tr.record("kernel.fetch", 0.5, start=100.0, fetch_mb=1.0)
        assert (sp.start, sp.end) == (100.0, 100.5)
        assert sp.parent_id == parent.span_id
        assert sp.wave == parent.wave


# --------------------------------------------------------------------------
# B: work counts on every drain
# --------------------------------------------------------------------------


def _policy():
    return PropagationPolicy(
        meta=ObjectMeta(name="p", namespace="default"),
        spec=PropagationSpec(
            resource_selectors=[
                ResourceSelector(api_version="apps/v1", kind="Deployment")
            ],
            placement=dynamic_weight_placement(),
        ),
    )


def _drains(spans: list, worker: str) -> list:
    return [s["attrs"] for s in spans if s["name"] == f"controller.{worker}"]


class TestWorkCounts:
    N = 12

    @pytest.fixture()
    def plane(self):
        clock = [5000.0]
        cp = ControlPlane(clock=lambda: clock[0])
        for i in (1, 2, 3):
            cp.join_cluster(
                new_cluster(f"member{i}", cpu="100", memory="200Gi")
            )
        for i in range(self.N):
            cp.store.apply(new_deployment(f"app{i}", replicas=4))
        cp.store.apply(_policy())
        cp.settle()
        cp.settle()
        return cp, clock

    def test_regated_bindings_are_a_noop_storm_in_the_scheduler(self, plane):
        cp, _ = plane
        tracer.clear()
        for rb in cp.store.list("ResourceBinding"):
            cp.store.apply(rb)  # written again unchanged: every one queued
        cp.settle()
        drains = _drains(tracer.dump(), "scheduler")
        assert drains, "the scheduler never drained"
        keys = sum(d["keys"] for d in drains)
        assert keys == self.N
        assert sum(d["noop"] for d in drains) == keys
        assert sum(d["enqueued"] for d in drains) >= keys
        assert all(d["writes"] == 0 for d in drains)
        # and a Cluster event over the settled plane queues none of them
        # (ISSUE 26: the gate turned each away, so no member can move it)
        cluster = cp.store.get("Cluster", "member1")
        cluster.meta.labels["touched"] = "1"
        tracer.clear()
        cp.store.apply(cluster)
        cp.settle()
        assert not _drains(tracer.dump(), "scheduler")

    def test_rebalancer_wave_does_work(self, plane):
        cp, clock = plane
        clock[0] += 10
        before = metrics.worker_noop_reconciles.value(worker="scheduler")
        tracer.clear()
        cp.store.apply(
            WorkloadRebalancer(
                meta=ObjectMeta(name="rb1"),
                spec=WorkloadRebalancerSpec(workloads=[
                    ObjectReferenceSelector(kind="Deployment", name="app0")
                ]),
            )
        )
        cp.settle()
        drains = _drains(tracer.dump(), "scheduler")
        keys = sum(d["keys"] for d in drains)
        noop = sum(d["noop"] for d in drains)
        assert keys >= 1 and noop < keys
        assert sum(d["writes"] for d in drains) >= 1
        assert (metrics.worker_noop_reconciles.value(worker="scheduler")
                - before) == noop
        # every drain of the wave carries its counts beside items
        for s in tracer.dump():
            if s["name"].startswith("controller."):
                assert {"items", "keys", "enqueued",
                        "writes"} <= set(s["attrs"]), s

    def test_single_key_noop_is_read_from_the_writes(self):
        rt = Runtime()
        wrote = rt.write_count = WriteCount()

        seen: list = []

        def reconcile(key):
            seen.append(key)
            if key == "w":
                wrote.n += 1
            # "r" comes back once and writes nothing: asked-for work
            return REQUEUE if key == "r" and seen.count("r") == 1 else DONE

        w = rt.new_worker("t", reconcile)
        for key in ("a", "w", "r", "a"):
            w.enqueue(key)
        tracer.clear()
        rt.run_until_settled()
        [d] = _drains(tracer.dump(), "t")
        # a: no-op; w: wrote; r: requeued, then a no-op
        # enqueued: a, w, r and r again; the second "a" found it queued
        assert (d["keys"], d["noop"], d["enqueued"], d["writes"]) == (
            4, 2, 4, 1), d

    def test_a_batch_that_cannot_tell_carries_no_noop(self):
        rt = Runtime()
        wrote = rt.write_count = WriteCount()

        def batch(keys):
            wrote.n += 1  # some key wrote; the reconciler does not say which
            return {k: DONE for k in keys}

        w = rt.new_worker("t", lambda k: DONE, reconcile_batch=batch)
        for key in "abc":
            w.enqueue(key)
        tracer.clear()
        rt.run_until_settled()
        [d] = _drains(tracer.dump(), "t")
        assert d["keys"] == 3 and "noop" not in d

    def test_reconcile_each_notes_the_keys_that_buffered_nothing(self):
        rt = Runtime()
        rt.write_count = WriteCount()
        pending: list = []

        def reconcile(key):
            if key in "bd":
                pending.append(key)
            return DONE

        def batch(keys):
            return w.reconcile_each(keys, reconcile, lambda: len(pending))

        w = rt.new_worker("t", reconcile, reconcile_batch=batch)
        for key in "abcde":
            w.enqueue(key)
        tracer.clear()
        rt.run_until_settled()
        [d] = _drains(tracer.dump(), "t")
        assert (d["keys"], d["noop"]) == (5, 3)


# --------------------------------------------------------------------------
# C: the collector, from inside
# --------------------------------------------------------------------------


class TestCollector:
    def test_full_collection_is_a_child_span_and_counted(self):
        runs = metrics.gc_collections.value(generation="2")
        pause = metrics.gc_pause_seconds.value(generation="2")
        tracer.clear()
        with tracer.span("controller.t") as parent:
            gc.collect()
        mine = [s for s in tracer.dump() if s["name"] == "runtime.gc"
                and s["parent_id"] == parent.span_id]
        assert len(mine) == 1
        [sp] = mine
        assert sp["attrs"]["generation"] == 2
        assert "collected" in sp["attrs"]
        assert parent.start <= sp["start"]
        assert sp["start"] + sp["duration_s"] <= parent.end + 2e-6
        assert metrics.gc_collections.value(generation="2") == runs + 1
        assert metrics.gc_pause_seconds.value(generation="2") > pause

    def test_young_collection_is_counted_and_not_spanned(self):
        runs = metrics.gc_collections.value(generation="0")
        tracer.clear()
        with tracer.span("controller.t"):
            gc.collect(0)
        assert metrics.gc_collections.value(generation="0") >= runs + 1
        assert not [s for s in tracer.dump() if s["name"] == "runtime.gc"]

    def test_installed_once_on_the_process_tracer(self):
        assert gc.callbacks.count(gc_watch) == 1
        assert gc_watch.tracer is tracer
        assert "karmada_tpu_gc_collections_total" in metrics.registry.render()

    def test_callback_takes_no_lock(self):
        """A collection can start under any lock its thread holds, the
        tracer's own included: the callback has to finish there."""
        tr = WaveTracer()
        watch = GcWatch(tr)
        done = threading.Event()

        def under_the_lock():
            with tr._lock:
                watch("start", {"generation": 2})
                watch("stop", {"generation": 2, "collected": 7})
            done.set()

        t = threading.Thread(target=under_the_lock, daemon=True)
        t.start()
        t.join(5.0)
        assert done.is_set(), "the gc callback blocked on the tracer's lock"
        [sp] = [s for s in tr.dump() if s["name"] == "runtime.gc"]
        assert sp["attrs"] == {"generation": 2, "collected": 7}


# --------------------------------------------------------------------------
# D: scopes inside the kernels
# --------------------------------------------------------------------------

SCOPES = ("fleet.gather", "fleet.masks", "fleet.prev", "fleet.estimate",
          "fleet.divide", "fleet.diff", "fleet.deltas", "fleet.wire")


def test_fleet_pass_stages_carry_named_scopes(engine):
    """Every stage name appears in ``_fleet_pass``'s lowered text with
    debug info, for the very arguments the engine dispatches."""
    import jax
    import jax.numpy as jnp

    import karmada_tpu.scheduler.fleet as fleet_mod

    eng, _ = engine
    table = eng._fleet
    n_pad, chunk = 1024, 1024
    rows = jnp.arange(n_pad, dtype=jnp.int32)
    lowered = fleet_mod._fleet_pass.lower(
        *table._dev_tables, rows, *table._dev_state,
        jax.ShapeDtypeStruct(table._res_dense.shape, jnp.uint8),
        jax.ShapeDtypeStruct(table._res_meta.shape, jnp.int32),
        chunk=chunk, n_chunks=n_pad // chunk, wide=False, fast=None,
        has_aggregated=False, all_rows=False, m_cap=4096, d_cap=8192,
    )
    text = lowered.as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope


# --------------------------------------------------------------------------
# fault 2: new-trace flags are per pass
# --------------------------------------------------------------------------


def test_host_path_pass_does_not_report_an_earlier_compile():
    snap = ClusterSnapshot(synthetic_fleet(48, seed=7))
    eng = TensorScheduler(snap, trace_manifest="")
    eng.fleet_threshold = 64
    problems = build_problems(snap, 256, prefix="h")
    eng.schedule(problems)  # cold fleet pass: compiles
    assert eng.last_pass_new_trace is True
    tracer.clear()
    eng.schedule(problems[:8])  # under the threshold: the host path
    assert eng.last_pass_new_trace is False
    assert not [s for s in tracer.dump() if s["attrs"].get("compile")]
    eng.schedule(problems)
    eng.schedule(problems)
    assert eng.last_pass_new_trace is False
