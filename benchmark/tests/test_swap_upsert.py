"""``swap_upsert_s`` (PR 34) over a small hand-written span list: one ``h``
pass (the identity path, nothing packed) and the ``L`` / ``r`` passes of a
swapped batch; ``None`` where no pass packed a row or the program stamps no
such attribute."""

import pytest

from benchmark.metrics import swap_upsert_s

WAVES = [(10.0, 10.2), (11.0, 11.6), (12.0, 12.7)]


def span(name, span_id, parent, start, dur, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent,
            "start": start, "duration_s": dur, "attrs": attrs}


H_PASS = [
    span("scheduler.solve", 1, None, 10.0, 0.07, rows_packed=0),
    span("kernel.host", 2, 1, 10.0, 0.0001, phase="upsert"),
    span("kernel.host", 3, 1, 10.0001, 0.004, phase="sync"),
]
L_PASS = [
    span("scheduler.solve", 4, None, 11.1, 0.40, rows_packed=17213),
    span("kernel.host", 5, 4, 11.1, 0.25, phase="upsert"),
    span("kernel.host", 6, 4, 11.35, 0.02, phase="sync"),
]


def test_the_median_is_over_the_passes_that_packed():
    r_pass = [
        span("scheduler.solve", 7, None, 12.1, 0.50, rows_packed=17213),
        span("kernel.host", 8, 7, 12.1, 0.35, phase="upsert"),
    ]
    before = [  # set-up's first pass: before the waves, not read
        span("scheduler.solve", 9, None, 2.0, 0.9, rows_packed=100000),
        span("kernel.host", 10, 9, 2.0, 0.8, phase="upsert"),
    ]
    ctx = {"spans": H_PASS + L_PASS + r_pass + before, "waves": WAVES}
    assert swap_upsert_s.read(ctx) == pytest.approx(0.30)
    ctx["spans"] = H_PASS + L_PASS
    assert swap_upsert_s.read(ctx) == pytest.approx(0.25)


def test_a_phase_span_without_a_parent_is_found_by_its_interval():
    orphans = [dict(s, parent_id=None) for s in H_PASS + L_PASS]
    assert swap_upsert_s.read({"spans": orphans, "waves": WAVES}) == (
        pytest.approx(0.25))


def test_nothing_to_read():
    assert swap_upsert_s.read({"spans": H_PASS, "waves": WAVES}) is None
    unstamped = [span("scheduler.solve", 1, None, 10.0, 0.07, rows=100000),
                 span("kernel.host", 2, 1, 10.0, 0.03)]
    assert swap_upsert_s.read({"spans": unstamped, "waves": WAVES}) is None
    plane_only = [span("controller.binding", 1, None, 10.1, 0.3, items=4)]
    assert swap_upsert_s.read({"spans": plane_only, "waves": WAVES}) is None
