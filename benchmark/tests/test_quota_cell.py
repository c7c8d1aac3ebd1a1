"""The cell ``fed-100c-quota.usage-churn``: the reference against hand-made
waves (the FIFO cut, a binding that asks nothing behind it, an unquota'd
namespace, a static assignment, a raise), the generator's ring, the five readers the cell
brings (each over hand-written spans and a reduced recorded-trace stub, and
``None`` where the program records nothing for them to read), the control
coming out not correct, and the cell at its rehearsal size on whatever
device is there (the CPU): a sound run is correct and prints no time, rate or
device metric; with admission broken underneath (a denial that never reaches
the answer; a binding that asks nothing denied behind the cut; the order of
the table's slots taken for the presented one),
``correct`` comes out false; a program that hands its fleet table an admitted
sub-list ends the set-up."""

import json

import numpy as np
import pytest

from benchmark import control, gen, quota, run
from benchmark.metrics import (
    quota_admit_device_s,
    quota_admit_roofline,
    quota_caps_roofline,
    quota_host_rows,
    quota_self_s,
)
from benchmark.reference import divide, quota as reference
from benchmark.roofline_quota import (
    cell_counts,
    least_seconds,
    quota_admit_count,
    quota_caps_count,
)

CELL = "fed-100c-quota.usage-churn"
U = reference.UNLIMITED


def _run(capsys, seed=2147483777, trace=0, seconds="2"):
    res = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    seconds, "--trace", str(trace)], rehearse=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    return res


# -- the reference ------------------------------------------------------------


def test_admission_is_fifo_over_the_whole_wave():
    # namespace 0 leaves 10 cpu / unlimited memory, namespace 1 leaves 4
    remaining = np.asarray([[10, U], [4, U]], np.int64)
    ns = np.asarray([0, 1, 0, -1, 0, 0, 1, 0])
    demand = np.asarray(
        [[4, 1], [3, 1], [5, 1], [99, 99], [3, 1], [1, 1], [1, 1], [0, 0]],
        np.int64)
    got = reference.admit(ns, demand, remaining).tolist()
    # row 4 (4 + 5 + 3 = 12 > 10) is cut and HOLDS ITS PLACE: row 5's one
    # more does not pass it; row 7, which asks nothing, is not the quota's
    # to deny, behind the cut or not; row 3 has no quota
    assert got == [True, True, True, True, False, False, True, True]
    # the same bindings in another order are cut elsewhere
    order = [4, 5, 7, 0, 2, 1, 6, 3]
    got = reference.admit(ns[order], demand[order], remaining).tolist()
    assert got == [True, True, True, True, False, True, True, True]
    # every tracked resource has to fit
    tight = np.asarray([[10, 1], [4, U]], np.int64)  # row 2's cpu fits
    assert reference.admit(ns, demand, tight).tolist() == [
        True, True, False, True, False, False, True, True]
    # nor where the namespace has nothing left at all
    none = np.asarray([[0, U], [0, U]], np.int64)
    assert reference.admit(ns, demand, none).tolist() == [
        False, False, False, True, False, False, False, True]


def test_a_raise_clears_the_rows_behind_the_old_cut():
    ns = np.zeros(6, np.int64)
    demand = np.asarray([[3], [3], [3], [3], [3], [3]], np.int64)
    before = reference.admit(ns, demand, np.asarray([[9]], np.int64))
    after = reference.admit(ns, demand, np.asarray([[15]], np.int64))
    assert before.tolist() == [True] * 3 + [False] * 3
    assert after.tolist() == [True] * 5 + [False]
    assert (~before & after).sum() == 2


def test_a_static_assignment_bounds_the_members_it_names():
    caps = np.full((1, 4, 3), U, np.int64)
    caps[0, 1, :2] = (2000, 8 << 30)  # member 1: 2 cpu, 8 GiB
    caps[0, 2, 0] = 500               # member 2: half a cpu
    requests = np.asarray([[500, 1 << 30, 1], [1000, 16 << 30, 1]], np.int64)
    top = reference.ceiling(caps, np.asarray([0, 0, -1]),
                            requests[[0, 1, 0]])
    none = divide.MAX_INT32
    assert top.tolist() == [
        [none, 4, 1, none],   # min(2000 // 500, 8 GiB // 1 GiB), 500 // 500
        [none, 0, 0, none],   # 16 GiB a replica does not fit 8 GiB
        [none] * 4]           # no assignment in this binding's namespace
    # the division: 8 replicas over 4 equal members, the second capped at 1
    cap = np.asarray([[64000, 1 << 40, 1000]] * 4, np.int64)
    caps[0, 1, :2] = (500, 8 << 30)
    args = (np.asarray([True, True, False]), np.asarray([8, 8, 8]),
            requests, np.asarray([0, 0, 0]), np.zeros((3, 4), np.int64),
            np.zeros(3, bool), cap)
    out, errors = reference.place(*args, np.asarray([0, -1, 0]), caps)
    assert errors == ["", "", reference.QUOTA]
    assert out[1].tolist() == [2, 2, 2, 2]
    assert out[0, 1] == 0 and out[0, 2] == 0 and out[0].sum() == 8
    assert not out[2].any()  # denied: the answer carries no placement
    free, _ = reference.place(*args)  # no assignment applied
    assert free[0].tolist() == [2, 2, 2, 2]


# -- the generator ------------------------------------------------------------


@pytest.fixture(scope="module")
def full():
    _, _, cfg, traffic = run.load_cell(CELL, False)
    return cfg, traffic


@pytest.mark.parametrize("seed", [7, 2147483777])
def test_the_deployment_is_what_the_file_says(full, seed):
    cfg, traffic = full
    tn = quota.tenants(cfg, seed)
    counts = np.bincount(tn["ns"], minlength=512)
    assert counts.sum() == 100000 and counts.argmax() == 0
    assert 0.14 < counts[0] / 1e5 < 0.155  # Zipf s = 1 over 512
    assert 0.48 < counts[:16].sum() / 1e5 < 0.51
    assert (tn["quota_row"] >= 0).sum() == 384
    assert (tn["quota_row"][:64] >= 0).all()
    assert (tn["cap_row"] >= 0).sum() == 128
    assert ((tn["cap_row"] >= 0) <= (tn["quota_row"] >= 0)).all()
    assert tn["cap_members"].shape == (128, 20)
    assert all(len(set(m)) == 20 for m in tn["cap_members"].tolist())
    caps = quota.caps(cfg, tn)
    assert caps.shape == (128, 100, 3)
    assert ((caps[:, :, 0] < U).sum(axis=1) == 20).all()
    assert (caps[:, :, 2] == U).all()
    # every seed holds the same content, dealt otherwise
    other = quota.tenants(cfg, seed + 1)
    assert np.array_equal(np.bincount(other["ns"], minlength=512), counts)
    assert not np.array_equal(other["ns"], tn["ns"])
    assert quota.steps(traffic) == "uuRuuuLu"


def test_the_ring_closes_and_the_raise_lands_where_it_says(full):
    cfg, traffic = full
    seed = 7
    bd, prof = gen.bindings(cfg, seed), gen.request_profiles(cfg)
    tn = quota.tenants(cfg, seed)
    dem, used = quota.demand(bd, prof, 100)
    assert 0.60 < (dem[:, 0] > 0).mean() < 0.63
    st = quota.ring(cfg, traffic, tn, dem, used)
    content, overall = st["content"], st["overall"]
    assert overall.shape == (8, 384, 3) and (overall[:, :, 2] == U).all()
    # every move of the ring, the wrap included, is one usage step
    for k in range(8):
        move = (st["used"][k] - st["used"][k - 1])[:, 0]
        assert (np.abs(move) <= 3 * content[:, 0] // 100 + 1).all(), k
    hot = tn["quota_row"][:16]
    raised = overall[2][:, :2] - overall[1][:, :2]
    assert np.array_equal(raised[hot], content[hot][:, :2] // 10)
    assert not np.delete(raised, hot, axis=0).any()
    assert np.array_equal(overall[6], overall[1])
    assert np.array_equal(overall[5], overall[2])
    qrow = tn["quota_row"][tn["ns"]]
    adm = [reference.admit(qrow, dem, rem) for rem in st["remaining"][:3]]
    denied = [int((~a).sum()) for a in adm]
    # what the limits were sized for: 6,000-15,000 rows denied a wave
    assert all(6000 < d < 15000 for d in denied), denied
    assert int((~adm[1] & adm[2]).sum()) > 3000  # the raise clears rows
    assert adm[0][qrow < 0].all()
    assert all(a[~dem.any(axis=1)].all() for a in adm)  # asks nothing


# -- the readers --------------------------------------------------------------

WAVES = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]
PEAK = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def span(name, start, dur, span_id=0, parent_id=None, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": start, "duration_s": dur, "attrs": attrs}


def wave(t, sid, scale=1.0, host_rows=0):
    return [
        span("scheduler.solve", t, 0.5, span_id=sid, rows=100000),
        span("scheduler.quota", t + 0.2, 0.001 * scale, span_id=sid + 1,
             parent_id=sid, rows=100000, quota_rows=91479, denied=13000,
             host_rows=host_rows, dispatched=1, generation=sid),
    ]


def ctx_of(spans, cfg, op_s=None, waves=4):
    return {"spans": spans, "waves": WAVES, "rest_wall": 3.0, "cfg": cfg,
            "peak": PEAK, "trace": {"op_s": op_s or {}, "waves": waves}}


def test_span_readers_take_the_median_wave(full):
    cfg, _ = full
    ctx = ctx_of(wave(10.1, 10) + wave(11.1, 20, 2.0) + wave(12.1, 30, 3.0,
                 host_rows=100000), cfg)
    assert quota_self_s.read(ctx) == pytest.approx(0.002)
    assert quota_host_rows.read(ctx) == 0
    ctx = ctx_of(wave(10.1, 10, host_rows=100000)
                 + wave(11.1, 20, host_rows=100000) + wave(12.1, 30), cfg)
    assert quota_host_rows.read(ctx) == 100000


def test_device_readers_read_the_quota_kernels(full):
    cfg, _ = full
    # a recorded-trace stub: device seconds by module over 4 traced waves
    ctx = ctx_of([], cfg, {"jit_quota_admit": 0.020, "jit__fleet_quota": 0.004,
                           "jit_quota_cluster_caps": 0.0002,
                           "jit__fleet_pass": 0.1})
    assert quota_admit_device_s.read(ctx) == pytest.approx(0.006)
    admit, caps = cell_counts(cfg)
    assert admit == quota_admit_count(512, 3)
    assert admit["bytes"] == 131072 * (4 + 24 + 1) + 2 * 512 * 24
    assert caps == quota_caps_count(8 * 129, 100, 3)
    assert caps["bytes"] == 1032 * 100 * (24 + 4)
    least, bound = least_seconds(admit, PEAK)
    assert bound == "bytes" and 4.6e-6 < least < 4.8e-6
    share = quota_admit_roofline.read(ctx)
    assert share == pytest.approx(100 * least / 0.006) and 0 < share < 100
    least, bound = least_seconds(caps, PEAK)
    assert bound == "bytes" and 3.4e-6 < least < 3.6e-6
    share = quota_caps_roofline.read(ctx)
    assert share == pytest.approx(100 * least / 0.00005) and 0 < share < 100
    assert any("quota_admit_roofline bound=bytes" in n for n in ctx["notes"])
    # a program that admits on the host still runs quota_admit on the device
    ctx = ctx_of([], cfg, {"jit_quota_admit": 0.020})
    assert quota_admit_device_s.read(ctx) == pytest.approx(0.005)


def test_readers_read_nothing_where_the_program_has_nothing(full):
    cfg, _ = full
    other = [span("scheduler.solve", 10.6, 0.1, rows=100000)]
    ctx = ctx_of(other, cfg, {"jit__fleet_pass": 0.3})
    for reader in (quota_self_s, quota_host_rows, quota_admit_device_s,
                   quota_admit_roofline, quota_caps_roofline):
        assert reader.read(ctx) is None, reader.__name__
    # another cell's configuration: no tenants to count a roofline from
    ctx = ctx_of([], {"bindings": 1}, {"jit_quota_admit": 0.02,
                                       "jit_quota_cluster_caps": 0.001})
    assert quota_admit_roofline.read(ctx) is None
    assert quota_caps_roofline.read(ctx) is None


# -- the cell at its rehearsal size ------------------------------------------


def test_sound_run_is_correct_and_prints_no_device_metric(capsys):
    res = _run(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"] == {} and res["rehearsal"] is True
    checks = res["checks"]
    assert checks["mismatched_rows"]["value"] == 0
    assert checks["undivided_rows"]["value"] == 0
    for floor in ("quota_decided_rows", "fifo_cut_namespaces",
                  "cap_decided_rows", "raise_cleared_rows", "rows_compared",
                  "step_kinds_compared"):
        assert checks[floor]["value"] >= checks[floor]["limit"] > 0, floor
    assert list(res)[-1] == "checks"


def test_traced_rehearsal_reads_the_quota_layers(capsys):
    res = _run(capsys, trace=1, seconds="5")
    assert res["correct"] is True and res["metrics"] == {}
    assert {"quota_self_s", "quota_host_rows", "prologue_self_s",
            "fleet_host_self_s", "compiles_in_window", "wave_unspanned_s",
            "spans_dropped"} <= set(res["per_layer_read"])
    assert "busy_s" not in res["device"] and "breakdown" not in res


@pytest.mark.parametrize("seed", [1, 2, 2147483777])
def test_the_control_is_not_correct(seed):
    checks = control.control_checks(CELL, seed, 40, rehearse=True)
    checks.pop("_failed")
    assert run.verdict(checks) is False
    # every denied row of the sample differs, and the rows an assignment
    # decides beside them
    assert (checks["mismatched_rows"]["value"]
            >= checks["quota_decided_rows"]["value"] * 4 > 0)


def test_a_denial_that_never_reaches_the_answer_is_not_correct(
        capsys, monkeypatch):
    from karmada_tpu.scheduler import fleet

    monkeypatch.setattr(
        fleet._QuotaVerdict, "denied",
        lambda self: np.zeros(self.n, bool))
    with pytest.raises(RuntimeError, match="the reference denies the first"):
        _run(capsys)


def test_a_row_that_asks_nothing_denied_behind_the_cut_is_not_correct(
        capsys, monkeypatch):
    """The kernel's inclusive running sum, left to meet a zero demand: a
    binding that asks nothing is answered 'quota exceeded' once its
    namespace's line has passed the limit. The reference admits it."""
    import jax.numpy as jnp

    from karmada_tpu.scheduler import fleet

    real = fleet._fleet_quota

    def every_row_in_line(prof_reqs, rows, ns_idx, *state):
        _, demand, quota_rows = real(prof_reqs, rows, ns_idx, *state)
        ns = jnp.where(rows >= 0, ns_idx[jnp.maximum(rows, 0)], -1)
        return ns, demand, quota_rows

    monkeypatch.setattr(fleet, "_fleet_quota", every_row_in_line)
    res = _run(capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


def test_slot_order_taken_for_presented_order_is_not_correct(
        capsys, monkeypatch):
    """Admission in the order of the table's rows, not of the batch: the
    bindings reach the table in a permuted order here, so the two differ."""
    import jax.numpy as jnp

    from benchmark.drivers.quota import Deployment
    from karmada_tpu.scheduler import fleet

    real_first = Deployment.first_passes

    def shuffled_slots(self, state):
        # under the quota: a move of its static assignments drops the table
        order = np.random.default_rng(3).permutation(len(self.problems))
        self.engine.set_quota(self.quota_snapshot(state, -100))
        self.engine.schedule([self.problems[i] for i in order.tolist()])
        real_first(self, state)

    real = fleet.FleetTable._dispatch_quota

    def by_slot(self, quota, rows_dev):
        rows = np.array(rows_dev)
        live = rows >= 0
        rows[live] = np.sort(rows[live])
        return real(self, quota, jnp.asarray(rows))

    monkeypatch.setattr(Deployment, "first_passes", shuffled_slots)
    monkeypatch.setattr(fleet.FleetTable, "_dispatch_quota", by_slot)
    try:
        res = _run(capsys)
    except RuntimeError as exc:  # the wave's own read saw it first
        assert "the reference denies the first" in str(exc)
    else:
        assert res["correct"] is False
        assert res["checks"]["mismatched_rows"]["value"] > 0


def test_a_partitioned_batch_ends_the_set_up(monkeypatch):
    from karmada_tpu.scheduler import TensorScheduler

    monkeypatch.setattr(
        TensorScheduler, "_quota_rides_table", lambda self, problems, q: False)
    with pytest.raises(SystemExit, match="partitions a quota'd batch"):
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.3",
                  "--trace", "0"], rehearse=True)


def test_a_warm_up_that_does_not_settle_ends(monkeypatch):
    from benchmark.traffic import quotachurn

    _, _, cfg, traffic = run.load_cell(CELL, True)
    traffic["warmup_wall_limit_s"] = -1.0
    dep, mix = run.build(cfg, traffic, 5, lambda m: None)
    mix.packed = [None] * mix.ring
    dep.quota_snapshot = lambda state, generation: None
    with pytest.raises(SystemExit, match="the ring does not settle"):
        mix.prepare(0)
    mix.prepare(8 * mix.ring)  # past the warm-up: the guard stands down
    assert quotachurn.DRIVER == "quota"
