"""The general host path, traced: rows the fleet-eligibility predicate turns
away leave the fleet table for ``_schedule_host``, which records

- ``scheduler.host`` with ``rows``, ``replicas`` (their sum), ``prev_max``
  (the most previous sites of a row) and ``chunks``, and one child a stage
  of each chunk (``scheduler.host.pack`` / ``.estimate`` / ``.select`` /
  ``.assign`` / ``.unpack``, each with ``rows``) at the very intervals
  ``scheduling_algorithm_duration`` observed;
- on ``scheduler.eligible``, ``wide_rows``: the leaving rows past the
  replica bound (what a cell of the fleet table holds), each counted once;
- ``karmada_tpu_fleet_host_path_rows_total{reason}``, added once a pass.

A batch that rides the fleet table whole records none of it."""

import numpy as np
import pytest

from karmada_tpu.api.policy import (
    ClusterAffinityTerm,
    LabelSelector,
    SpreadConstraint,
)
from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot, TensorScheduler
from karmada_tpu.scheduler.core import HOST_PATH_REASONS
from karmada_tpu.scheduler.fleet import K_EVICT, K_PREV, MAX_REPLICAS_FAST, T_CAP
from karmada_tpu.utils import builders, metrics
from karmada_tpu.utils.tracing import tracer

REGIONS, PER_REGION = 10, 4  # past the select kernel's 8 regions
C = REGIONS * PER_REGION
NAMES = [f"m{j:02d}" for j in range(C)]
STAGES = ("scheduler.host.pack", "scheduler.host.estimate",
          "scheduler.host.select", "scheduler.host.assign",
          "scheduler.host.unpack")
STEPS = ("Filter", "Score", "Select", "AssignReplicas")
PER_REASON = 6


def _snapshot() -> ClusterSnapshot:
    return ClusterSnapshot([
        builders.new_cluster(
            name, cpu="512", memory="4096Gi", pods=20000,
            region=f"r{j // PER_REGION}", zone=f"r{j // PER_REGION}z0",
            labels={"region": f"r{j // PER_REGION}"})
        for j, name in enumerate(NAMES)])


def _terms(n: int) -> list:
    return [ClusterAffinityTerm(
        affinity_name=f"t{k}",
        label_selector=LabelSelector(match_labels={"region": f"r{k}"}))
        for k in range(n)]


PLAIN = builders.dynamic_weight_placement()
#: a placement for each reason a placement alone gives; the binding's own
#: reasons (tasks, replicas) ride on PLAIN
PLACEMENTS = {
    "terms": builders.dynamic_weight_placement(
        cluster_affinities=_terms(T_CAP + 1)),
    "terms_spread": builders.aggregated_placement(
        cluster_affinities=_terms(2), spread_constraints=[SpreadConstraint(
            spread_by_field="cluster", min_groups=2, max_groups=4)]),
    # more groups than members: the host's selection is a FitError, and the
    # row is given none
    "selection": builders.dynamic_weight_placement(spread_constraints=[
        SpreadConstraint(spread_by_field="cluster", min_groups=C + 1)]),
}


def _problem(rng, key: str, reason: str | None) -> BindingProblem:
    replicas = int(rng.integers(1, 40))
    sites = rng.choice(C, int(rng.integers(0, 9)), replace=False)
    evict = ()
    if reason == "terms":
        # a previous result wider than a row's columns: the host path
        # takes it whole (on the fleet it would ride in a wide slot)
        sites = rng.choice(C, K_PREV + 1 + int(rng.integers(0, 4)),
                           replace=False)
    elif reason == "replicas":
        replicas = MAX_REPLICAS_FAST + 1 + int(rng.integers(0, 200))
    elif reason == "evict_tasks":
        evict = tuple(NAMES[j] for j in rng.choice(C, K_EVICT + 1,
                                                   replace=False))
    return BindingProblem(
        key=key, placement=PLACEMENTS.get(reason, PLAIN), replicas=replicas,
        requests={"cpu": 500, "memory": 1 << 30}, gvk="apps/v1/Deployment",
        prev={NAMES[j]: int(rng.integers(1, 6)) for j in sites},
        evict_clusters=evict)


def _batch(reasons, seed=5, base=300) -> list:
    rng = np.random.default_rng(seed)
    problems = [_problem(rng, f"b{i}", None) for i in range(base)]
    for reason in reasons:
        problems += [_problem(rng, f"{reason}{k}", reason)
                     for k in range(PER_REASON)]
    return [problems[i] for i in rng.permutation(len(problems))]


def _tallies() -> dict:
    return {r: metrics.fleet_host_path_rows_total.value(reason=r)
            for r in HOST_PATH_REASONS}


def _steps() -> dict:
    snap = metrics.scheduling_algorithm_duration.snapshot()
    return {s: snap.get(f'schedule_step="{s}"', {"count": 0, "sum": 0.0})
            for s in STEPS}


def _pass(problems):
    engine = TensorScheduler(_snapshot(), chunk_size=256, mesh=False)
    before, steps = _tallies(), _steps()
    tracer.clear()
    engine.schedule(problems)
    spans = tracer.dump()
    after, steps_after = _tallies(), _steps()
    added = {r: after[r] - before[r] for r in HOST_PATH_REASONS}
    observed = {s: (steps_after[s]["count"] - steps[s]["count"],
                    steps_after[s]["sum"] - steps[s]["sum"]) for s in STEPS}
    return spans, added, observed


def test_the_host_span_and_its_stages_over_rows_past_every_bound():
    problems = _batch(HOST_PATH_REASONS)
    spans, added, observed = _pass(problems)
    leaving = [p for p in problems if not p.key.startswith("b")]
    (host,) = [s for s in spans if s["name"] == "scheduler.host"]
    a = host["attrs"]
    assert a["rows"] == len(leaving) == PER_REASON * len(HOST_PATH_REASONS)
    assert a["replicas"] == sum(p.replicas for p in leaving)
    assert a["prev_max"] == max(len(p.prev) for p in leaving) > K_PREV
    # the ranked path (several terms) and the round loop (several terms
    # beside spread constraints, and the single-term rows): a chunk each
    assert a["chunks"] >= 2
    by_name = {n: [s for s in spans if s["name"] == n] for n in STAGES}
    # a dumped span keeps its start and duration to the microsecond
    lo, hi = host["start"] - 2e-6, host["start"] + host["duration_s"] + 2e-6
    for name, stage in by_name.items():
        assert len(stage) == a["chunks"], name
        for s in stage:
            assert s["parent_id"] == host["span_id"], name
            assert lo <= s["start"] and s["start"] + s["duration_s"] <= hi
    assert sum(s["attrs"]["rows"] for s in by_name[STAGES[0]]) == a["rows"]
    # each chunk's stages follow one another, none overlapping the next
    for k in range(a["chunks"]):
        chunk = [by_name[n][k] for n in STAGES]
        for x, y in zip(chunk, chunk[1:]):
            assert x["start"] + x["duration_s"] <= y["start"] + 2e-6
            assert x["attrs"]["rows"] == y["attrs"]["rows"]
    # the four timed stages ARE the histogram's observations: the same
    # count and the same seconds, read once
    for name, step in zip(STAGES, STEPS):
        count, secs = observed[step]
        assert count == a["chunks"], step
        assert sum(s["duration_s"] for s in by_name[name]) == pytest.approx(
            secs, abs=1e-6 * count), step
    assert added == {r: PER_REASON for r in HOST_PATH_REASONS}
    (eligible,) = [s for s in spans if s["name"] == "scheduler.eligible"]
    assert eligible["attrs"]["wide_rows"] == PER_REASON
    assert eligible["attrs"]["fleet_rows"] == len(problems) - len(leaving)


@pytest.mark.parametrize("reason", HOST_PATH_REASONS)
def test_wide_rows_and_the_counter_by_reason(reason, capfd):
    problems = _batch([reason], seed=11)
    capfd.readouterr()
    spans, added, _ = _pass(problems)
    assert added == {r: PER_REASON if r == reason else 0
                     for r in HOST_PATH_REASONS}
    (eligible,) = [s for s in spans if s["name"] == "scheduler.eligible"]
    wide = PER_REASON if reason == "replicas" else 0
    assert eligible["attrs"]["wide_rows"] == wide
    assert metrics.fleet_host_path_rows.value() == PER_REASON
    (line,) = [ln for ln in capfd.readouterr().err.splitlines()
               if ln.startswith("# fleet host path")]
    assert f"{PER_REASON} of {len(problems)} rows" in line
    said = {
        "terms": f"with more than {T_CAP} affinity terms",
        "evict_tasks": f"with more than {K_EVICT} eviction tasks",
        "terms_spread": "with several terms and spread constraints",
        "replicas": f"with more than {MAX_REPLICAS_FAST} replicas",
        "selection": "with no spread selection",
    }
    for r, text in said.items():
        assert f"{PER_REASON if r == reason else 0} {text}" in line, r
    (host,) = [s for s in spans if s["name"] == "scheduler.host"]
    assert host["attrs"]["rows"] == PER_REASON


def test_a_batch_that_rides_whole_records_nothing_of_the_host_path(capfd):
    problems = _batch([])
    capfd.readouterr()
    spans, added, observed = _pass(problems)
    assert added == dict.fromkeys(HOST_PATH_REASONS, 0)
    names = {s["name"] for s in spans}
    assert "scheduler.solve" in names
    assert not names & {"scheduler.host", *STAGES}
    (eligible,) = [s for s in spans if s["name"] == "scheduler.eligible"]
    assert "wide_rows" not in eligible["attrs"]
    assert eligible["attrs"]["fleet_rows"] == len(problems)
    assert all(count == 0 for count, _ in observed.values())
    assert metrics.fleet_host_path_rows.value() == 0
    assert "# fleet host path" not in capfd.readouterr().err
