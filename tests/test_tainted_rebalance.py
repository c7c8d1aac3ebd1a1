"""BASELINE config 5's mix through the engine's normal path, at a small size:
members a slice of which carry a ``NoSchedule`` taint, two placements (one
tolerating it), previous results on tainted members kept by the filter's
leniency, answers past ``K_PREV`` sites. Every row of two drift steps equals
the plain reference (benchmark/reference/divide.py), row by row."""

import contextlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import gen, run, tainted  # noqa: E402
from benchmark.drivers import tainted as driver  # noqa: E402
from benchmark.traffic import taintdrift  # noqa: E402

CELL = "rebalance-100kx5k.drift"
SIZE = {"bindings": 2048, "clusters": 600, "chunk_size": 512}


@pytest.fixture(scope="module")
def storm():
    _, _, cfg, traffic = run.load_cell(CELL, rehearse=True)
    cfg.update(SIZE)
    dep = driver.Deployment(cfg, 2147483777, lambda msg: None)
    dep.setup()
    mix = taintdrift.Traffic(dep, traffic, lambda msg: None)
    mix.build()
    answers = []
    for g in range(2):  # two drift steps: update_snapshot + schedule
        mix.wave(g, lambda name: contextlib.nullcontext())
        answers.append([(r.success, dict(r.clusters)) for r in mix.last])
    return dep, mix, answers


def test_the_mix_holds_what_config_5_names(storm):
    dep = storm[0]
    fl, bd = dep.fleet, dep.bind
    assert 0.05 < fl["tainted"].mean() < 0.11
    assert 0.25 < bd["tolerates"].mean() < 0.35
    assert (bd["replicas"].min(), bd["replicas"].max()) == (1, 99)
    on_taint = tainted.tainted_prev(bd, fl["tainted"])
    assert (on_taint & ~bd["tolerates"]).sum() > 100
    # the members' own shapes: 2-49 nodes of 16..128 cores
    cores = fl["allocatable"][:, 0] // 1000
    assert cores.min() >= 32 and cores.max() <= 49 * 128
    assert (fl["allocated"] <= fl["allocatable"]).all()


@pytest.mark.parametrize("step", [0, 1])
def test_every_row_equals_the_reference(storm, step):
    dep, mix, answers = storm
    got = answers[step]
    rows = np.arange(SIZE["bindings"])
    out, uns = mix._reference(step, rows, True)
    want = mix._answers(out, uns)
    bad = [i for i in rows.tolist() if got[i] != want[i]]
    assert not bad, (len(bad), bad[:5], got[bad[0]], want[bad[0]])
    # the rows the taint decides, the lenient and the wide ones are there
    free, _ = mix._reference(step, rows, False)
    held = out > 0
    prev = gen.prev_dense(dep.bind, rows, SIZE["clusters"])
    assert (free != out).any(axis=1).sum() > 100
    lenient = ~dep.bind["tolerates"] & (
        held & (prev > 0) & dep.fleet["tainted"][None, :]).any(axis=1)
    assert lenient.sum() > 100
    assert (held.sum(axis=1) > 32).sum() > 100
    assert not uns.any()
