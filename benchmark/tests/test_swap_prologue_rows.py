"""``swap_prologue_rows`` over hand-written span lists (``tracer.dump()``
dicts): the positions a swap wave's ``scheduler.pack`` says it visited, read
over the passes ``swap_wave_s`` takes; the batch's length on a program that
walks a swapped batch, the moved positions on one that diffs it; None where
there is nothing to read."""

import pytest

from benchmark.metrics import swap_prologue_rows, swap_wave_s

WAVES = [(10.0, 10.1), (11.0, 11.3), (12.0, 12.1), (13.0, 13.3)]


def span(name, span_id, parent, start, dur, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent,
            "start": start, "duration_s": dur, "attrs": attrs}


def identity_pass(t, first_id):
    return [
        span("scheduler.schedule", first_id, None, t, 0.09,
             rows=100000, path="identity"),
        span("scheduler.identity", first_id + 1, first_id, t, 0.007,
             rows=100000, hit=1, moved=0),
        span("scheduler.solve", first_id + 2, first_id, t + 0.007, 0.08),
    ]


def walked_pass(t, first_id):
    """A swap wave of a program that walks the batch."""
    r = first_id
    return [
        span("scheduler.schedule", r, None, t, 0.25, rows=100000,
             path="full"),
        span("scheduler.pack", r + 1, r, t, 0.07, rows=100000),
        span("scheduler.compile", r + 2, r + 1, t, 0.03, rows=100000),
        span("scheduler.handoff", r + 3, r, t + 0.07, 0.006, rows=100000),
        span("scheduler.solve", r + 4, r, t + 0.08, 0.16),
        span("scheduler.rearm", r + 5, r, t + 0.24, 0.007, rows=100000),
    ]


def diffed_pass(t, first_id, moved):
    """A swap wave of a program that diffs the batch: the sweep inside
    pack, the stages' ``rows`` the moved positions."""
    r = first_id
    return [
        span("scheduler.schedule", r, None, t, 0.19, rows=100000,
             path="full"),
        span("scheduler.pack", r + 1, r, t, 0.02, rows=moved,
             kept=100000 - moved),
        span("scheduler.identity", r + 2, r + 1, t, 0.007, rows=100000,
             hit=0, moved=moved),
        span("scheduler.compile", r + 3, r + 1, t + 0.007, 0.002,
             rows=moved),
        span("scheduler.eligible", r + 4, r + 1, t + 0.009, 0.011,
             rows=moved, fleet_rows=100000),
        span("scheduler.handoff", r + 5, r, t + 0.02, 0.0001, rows=100000),
        span("scheduler.solve", r + 6, r, t + 0.021, 0.16),
        span("scheduler.rearm", r + 7, r, t + 0.185, 0.0001, rows=100000),
    ]


def test_a_walked_swap_wave_reads_the_batchs_length():
    spans = (identity_pass(10.0, 1) + walked_pass(11.0, 11)
             + identity_pass(12.0, 31) + walked_pass(13.0, 41))
    ctx = {"spans": spans, "waves": WAVES}
    assert swap_wave_s.read(ctx) == pytest.approx(0.25)
    assert swap_prologue_rows.read(ctx) == 100000


def test_a_diffed_swap_wave_reads_the_moved_positions():
    spans = (identity_pass(10.0, 1) + diffed_pass(11.0, 11, 17260)
             + identity_pass(12.0, 31) + diffed_pass(13.0, 41, 17262)
             + diffed_pass(2.0, 61, 99))  # set-up's: outside the waves
    ctx = {"spans": spans, "waves": WAVES}
    assert swap_prologue_rows.read(ctx) == 17261
    # the stages' rows under pack are not counted beside pack's own
    ctx["waves"] = WAVES[:2]
    assert swap_prologue_rows.read(ctx) == 17260


def test_two_packs_of_one_pass_are_summed():
    """A delta that tried one moved row and found it off the fleet, then the
    walk: both under the one root."""
    r = 11
    spans = identity_pass(10.0, 1) + [
        span("scheduler.schedule", r, None, 11.0, 0.25, rows=600,
             path="full"),
        span("scheduler.identity", r + 1, r, 11.0, 0.0001, rows=600, hit=0,
             moved=1),
        span("scheduler.pack", r + 2, r, 11.0001, 0.0001, rows=1),
        span("scheduler.pack", r + 3, r, 11.0002, 0.003, rows=600, kept=0),
        span("scheduler.solve", r + 4, r, 11.004, 0.2),
    ]
    ctx = {"spans": spans, "waves": WAVES}
    assert swap_prologue_rows.read(ctx) == 601


def test_nothing_to_read_reads_none():
    ctx = {"spans": identity_pass(10.0, 1) + identity_pass(11.0, 11),
           "waves": WAVES}
    assert swap_prologue_rows.read(ctx) is None
    # a program without the root span or its path: pack under no root
    parent = [
        span("scheduler.pack", 1, None, 11.0, 0.06, rows=100000),
        span("scheduler.solve", 2, None, 11.07, 0.17, rows_packed=17),
    ]
    assert swap_prologue_rows.read({"spans": parent, "waves": WAVES}) is None
    # a full pass whose pack carries no rows
    bare = [
        span("scheduler.schedule", 1, None, 11.0, 0.25, path="full"),
        span("scheduler.pack", 2, 1, 11.0, 0.06),
    ]
    assert swap_wave_s.read({"spans": bare, "waves": WAVES}) == 0.25
    assert swap_prologue_rows.read({"spans": bare, "waves": WAVES}) is None
