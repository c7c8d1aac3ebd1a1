"""The cell ``fed-100c-cl2load.size-drift``: the generator's ClusterLoader2
groups and scale ring (at the rehearsal size and, arrays only, at the
configuration's own), every wide row's previous result past the fleet
table's 32 sites, the program against the reference at the rehearsal size
(wide rows and scale-downs included), the control coming out not correct,
and the three readers the cell brings over hand-written spans and a
recorded-trace stub (``None`` where the program records nothing for them)."""

import json

import numpy as np
import pytest

from benchmark import cl2load, control, gen, run
from benchmark.metrics import host_assign_device_s, host_solve_s, host_wide_rows

CELL = "fed-100c-cl2load.size-drift"


def _cell(rehearse: bool):
    _, _, cfg, traffic = run.load_cell(CELL, rehearse)
    return cfg, traffic


def _arrays(rehearse: bool, seed: int):
    cfg, traffic = _cell(rehearse)
    fleet = gen.fleet(cfg, seed)
    bd = cl2load.bindings(cfg, seed, fleet, gen.request_profiles(cfg))
    return cfg, traffic, bd, cl2load.scale_ring(cfg, traffic, bd, seed)


# -- the generator ------------------------------------------------------------


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_groups_and_the_scale_ring_are_what_the_files_say(rehearse):
    cfg, traffic, bd, ring = _arrays(rehearse, 7)
    pods, counts = cl2load.group_sizes(cfg)
    assert pods.tolist() == [5, 30, 250]
    assert np.bincount(bd["group"]).tolist() == counts.tolist()
    assert counts.sum() == cfg["deployments"]
    if not rehearse:
        assert counts.tolist() == [91463, 7622, 915]
        # the pods split 1/2, 1/4, 1/4
        assert (pods * counts).tolist() == [457315, 228660, 228750]
    assert np.array_equal(bd["replicas"], pods[bd["group"]])
    assert [cl2load.scale_range(cfg, s) for s in pods.tolist()] == [
        (2, 7), (15, 45), (125, 375)]
    share = float(traffic["scale_share"])
    assert len(ring) == int(traffic["ring"])
    for step in ring:
        rows, reps = step["rows"], step["replicas"]
        assert np.array_equal(rows, np.unique(rows))
        grp = bd["group"][rows]
        assert np.bincount(grp, minlength=3).tolist() == [
            int(np.floor(share * n + 0.5)) for n in counts.tolist()]
        for g, s in enumerate(pods.tolist()):
            lo, hi = cl2load.scale_range(cfg, s)
            assert lo <= reps[grp == g].min() and reps[grp == g].max() <= hi
    if not rehearse:
        assert np.bincount(bd["group"][ring[0]["rows"]]).tolist() == [
            4573, 381, 46]
    # consecutive steps differ at no more than twice the share
    for a, b in zip(ring, ring[1:] + ring[:1]):
        moved = len(np.union1d(a["rows"], b["rows"]))
        assert moved <= 2 * share * counts.sum() + 3


def test_every_seed_holds_the_same_content_dealt_otherwise():
    cfg, _, a, ra = _arrays(True, 7)
    _, _, b, rb = _arrays(True, 8)
    assert not np.array_equal(a["group"], b["group"])
    for key in ("group", "prof_idx", "fresh"):
        assert np.array_equal(np.sort(a[key]), np.sort(b[key])), key
    assert sorted(ra[0]["replicas"].tolist()) == sorted(
        rb[0]["replicas"].tolist())


def test_every_wide_row_holds_more_than_32_sites_at_full_width():
    cfg, traffic, bd, ring = _arrays(False, 2147483777)
    c = int(cfg["clusters"])
    big = bd["group"] == 2
    held = big & (bd["wide_of"] >= 0)
    assert 0.66 < held.sum() / big.sum() < 0.74
    dense = cl2load.prev_dense(bd, np.flatnonzero(held), c)
    # the reference's division of 250 replicas over the 100 members
    assert (dense.sum(axis=1) == 250).all()
    assert ((dense > 0).sum(axis=1) > int(cfg["row_bounds"]["prev_sites"])).all()
    assert np.array_equal((dense > 0).sum(axis=1), bd["n_prev"][held])
    # the small and medium groups draw theirs as the sibling does
    assert bd["n_prev"][~big].max() <= 8
    # every wave: every big Deployment but a rescaled one left with no
    # previous result and at most 128 replicas is past a bound
    for step in ring:
        reps = cl2load.step_replicas(bd, step)
        wide = cl2load.wide_rows(cfg, bd, reps)
        assert not wide[~big].any()
        narrow = big & ~wide
        assert (reps[narrow] <= 128).all() and (bd["n_prev"][narrow] == 0).all()
        assert 900 <= wide.sum() <= 915


# -- the cell at its rehearsal size ------------------------------------------


def _run(capsys, seed=2147483777, trace=0, seconds="2"):
    res = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    seconds, "--trace", str(trace)], rehearse=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    return res


def test_sound_run_is_correct_over_the_wide_rows_and_the_scale_downs(capsys):
    res = _run(capsys, trace=1, seconds="3")
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"] == {} and res["rehearsal"] is True
    checks = res["checks"]
    assert checks["mismatched_rows"]["value"] == 0
    for floor in ("rows_compared", "ring_steps_compared", "wide_decided_rows",
                  "scaled_rows", "scale_down_wide_rows"):
        assert checks[floor]["value"] >= checks[floor]["limit"] > 0, floor
    assert {"host_solve_s", "host_wide_rows", "prologue_self_s",
            "spans_dropped"} <= set(res["per_layer_read"])


def test_a_wide_row_answered_from_a_cut_previous_result_is_not_correct(
        capsys, monkeypatch):
    """The program handed a wide row with its previous result cut to 32
    sites: what a move onto the fleet row state without room for them
    would answer. The comparison of every wide row sees it."""
    from benchmark.drivers import cl2load as driver

    real = driver.Deployment.problem

    def cut(self, i, replicas, prev):
        if len(prev) > 32:
            prev = dict(sorted(prev.items(), key=lambda kv: -kv[1])[:32])
        return real(self, i, replicas, prev)

    monkeypatch.setattr(driver.Deployment, "problem", cut)
    res = _run(capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2147483777])
def test_the_control_is_not_correct(seed):
    checks = control.control_checks(CELL, seed, 40, rehearse=True)
    checks.pop("_failed")
    assert run.verdict(checks) is False
    # every wide row compared differs
    waves = checks["ring_steps_compared"]["value"]
    assert (checks["mismatched_rows"]["value"]
            >= checks["wide_decided_rows"]["value"] * waves)


# -- the readers --------------------------------------------------------------

WAVES = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]


def span(name, start, dur, span_id=0, parent_id=None, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": start, "duration_s": dur, "attrs": attrs}


def wave(t, sid, host_s, wide):
    out = [span("scheduler.eligible", t, 0.01, span_id=sid, rows=100000,
                fleet_rows=100000 - wide, wide_rows=wide),
           span("scheduler.host", t + 0.3, host_s, span_id=sid + 1,
                rows=wide, replicas=250 * wide, prev_max=100, chunks=1)]
    out += [span(f"scheduler.host.{n}", t + 0.3, host_s / 5,
                 span_id=sid + 2 + k, parent_id=sid + 1, rows=wide)
            for k, n in enumerate(("pack", "estimate", "select", "assign",
                                   "unpack"))]
    return out


def ctx_of(spans, op_s=None, waves=4):
    return {"spans": spans, "waves": WAVES, "rest_wall": 3.0,
            "trace": {"op_s": op_s or {}, "waves": waves}}


def test_the_readers_read_the_recorded_spans():
    spans = (wave(10.1, 10, 0.05, 915) + wave(11.1, 20, 0.07, 914)
             + wave(12.1, 30, 0.09, 913))
    ctx = ctx_of(spans, {"jit_divide_replicas": 0.02, "jit__fleet_pass": 0.1})
    assert host_solve_s.read(ctx) == pytest.approx(0.07)
    assert host_wide_rows.read(ctx) == 914
    assert host_assign_device_s.read(ctx) == pytest.approx(0.005)


def test_the_readers_read_nothing_where_the_program_has_nothing():
    # a batch that rode whole: no host span, no wide_rows, no division
    other = [span("scheduler.eligible", 10.1, 0.01, rows=100000,
                  fleet_rows=100000),
             span("scheduler.solve", 10.2, 0.05, rows=100000)]
    ctx = ctx_of(other, {"jit__fleet_pass": 0.3})
    for reader in (host_solve_s, host_wide_rows, host_assign_device_s):
        assert reader.read(ctx) is None, reader.__name__
    # a program older than the wide_rows attribute still has its host span
    older = [s for s in wave(10.1, 10, 0.05, 915)
             if s["name"] == "scheduler.host"]
    for s in older:
        s["attrs"] = {"rows": 915}
    ctx = ctx_of(older + [span("scheduler.eligible", 10.1, 0.01, rows=100000,
                               fleet_rows=99085)])
    assert host_solve_s.read(ctx) == pytest.approx(0.05)
    assert host_wide_rows.read(ctx) is None
