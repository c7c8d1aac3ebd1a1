"""The four readers of PR 35 (``wave_unspanned_s``, ``identity_check_s``,
``swap_wave_s``, ``swap_prologue_s``) over small hand-written span lists
(``tracer.dump()`` dicts): the union of overlapping and nested spans, a wave
under no span, the passes that ran the full prologue against those that took
the identity path, and None on a span list without the new names or
attributes, as a program that predates them records."""

import pytest

from benchmark.metrics import (
    identity_check_s,
    swap_prologue_s,
    swap_wave_s,
    wave_unspanned_s,
)

WAVES = [(10.0, 10.1), (11.0, 11.3), (12.0, 12.1)]


def span(name, span_id, parent, start, dur, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent,
            "start": start, "duration_s": dur, "attrs": attrs}


def identity_pass(t, first_id):
    """An ``h`` wave: the root, the id() sweep, the table's pass."""
    return [
        span("scheduler.schedule", first_id, None, t, 0.09,
             rows=1000, path="identity"),
        span("scheduler.identity", first_id + 1, first_id, t, 0.007,
             rows=1000, hit=1, moved=0),
        span("scheduler.solve", first_id + 2, first_id, t + 0.007, 0.08),
    ]


def full_pass(t, first_id, scale=1.0):
    """An ``L`` / ``r`` wave: pack and its stages, hand-off, solve, re-arm."""
    r = first_id
    return [
        span("scheduler.schedule", r, None, t, 0.25 * scale,
             rows=1000, path="full"),
        span("scheduler.pack", r + 1, r, t, 0.06 * scale, rows=1000),
        span("scheduler.compile", r + 2, r + 1, t, 0.03 * scale),
        span("scheduler.eligible", r + 3, r + 1, t + 0.03 * scale,
             0.03 * scale),
        span("scheduler.handoff", r + 4, r, t + 0.06 * scale, 0.01 * scale),
        span("scheduler.solve", r + 5, r, t + 0.07 * scale, 0.17 * scale),
        span("kernel.host", r + 6, r + 5, t + 0.07 * scale, 0.08 * scale,
             phase="upsert"),
        span("scheduler.rearm", r + 7, r, t + 0.24 * scale, 0.008 * scale),
    ]


def test_unspanned_takes_the_union_of_nested_and_overlapping_spans():
    spans = [
        # wave 1: a root with a child inside it (counted once), then a span
        # that overlaps the root's end and runs past the wave's (clipped)
        span("scheduler.schedule", 1, None, 10.01, 0.05),
        span("scheduler.solve", 2, 1, 10.02, 0.03),
        span("kernel.bits", 3, None, 10.05, 0.10),
        # wave 2: a span that began before the wave (clipped), a hole, two
        # disjoint spans
        span("controller.binding", 4, None, 10.9, 0.15),
        span("scheduler.schedule", 5, None, 11.10, 0.05),
        span("runtime.gc", 6, None, 11.20, 0.05),
        # wave 3: one span over the whole wave and more
        span("settle", 7, None, 11.9, 0.5),
    ]
    # dark: wave 1 [10.0, 10.01) = 0.01; wave 2 0.30 - (0.05 + 0.05 + 0.05)
    # = 0.15; wave 3 0.0
    ctx = {"spans": spans, "waves": WAVES}
    assert wave_unspanned_s.read(ctx) == pytest.approx(0.01)
    ctx["waves"] = WAVES[:2]
    assert wave_unspanned_s.read(ctx) == pytest.approx(0.08)
    ctx["waves"] = WAVES[1:2]
    assert wave_unspanned_s.read(ctx) == pytest.approx(0.15)


def test_a_wave_with_no_span_reads_its_whole_wall():
    spans = [span("scheduler.schedule", 1, None, 5.0, 0.2)]  # before them
    assert wave_unspanned_s.read({"spans": spans, "waves": WAVES}) == (
        pytest.approx(0.1))
    assert wave_unspanned_s.read({"spans": [], "waves": WAVES}) is None


def test_identity_check_is_summed_a_wave_and_the_median_taken():
    spans = (identity_pass(10.0, 1) + identity_pass(11.0, 11)
             + identity_pass(11.1, 21) + identity_pass(12.0, 31)
             + identity_pass(3.0, 41))  # before the waves: not read
    ctx = {"spans": spans, "waves": WAVES}
    assert identity_check_s.read(ctx) == pytest.approx(0.007)
    ctx["waves"] = WAVES[1:]
    assert identity_check_s.read(ctx) == pytest.approx(0.0105)


def test_swap_readers_take_the_full_passes_alone():
    spans = (identity_pass(10.0, 1) + full_pass(11.0, 11)
             + identity_pass(12.0, 31) + full_pass(2.0, 41, 4.0))  # set-up's
    ctx = {"spans": spans, "waves": WAVES}
    assert swap_wave_s.read(ctx) == pytest.approx(0.25)
    # pack + hand-off + re-arm; the stages under pack are not counted twice
    assert swap_prologue_s.read(ctx) == pytest.approx(0.06 + 0.01 + 0.008)
    waves = WAVES + [(13.0, 13.6)]
    ctx = {"spans": spans + full_pass(13.0, 61, 2.0), "waves": waves}
    assert swap_wave_s.read(ctx) == pytest.approx(0.375)
    assert swap_prologue_s.read(ctx) == pytest.approx(0.078 * 1.5)


def test_swap_readers_ignore_identity_waves():
    spans = identity_pass(10.0, 1) + identity_pass(11.0, 11)
    ctx = {"spans": spans, "waves": WAVES}
    assert swap_wave_s.read(ctx) is None
    assert swap_prologue_s.read(ctx) is None
    assert identity_check_s.read(ctx) == pytest.approx(0.007)


def test_a_program_without_the_spans_reads_none():
    """The parent's span list: pack and solve under no root, no ``path``."""
    parent = [
        span("scheduler.pack", 1, None, 11.0, 0.06, rows=1000),
        span("scheduler.solve", 2, None, 11.07, 0.17, rows_packed=17),
        span("kernel.host", 3, 2, 11.07, 0.08, phase="upsert"),
        span("scheduler.solve", 4, None, 10.0, 0.08, rows_packed=0),
    ]
    ctx = {"spans": parent, "waves": WAVES}
    assert identity_check_s.read(ctx) is None
    assert swap_wave_s.read(ctx) is None
    assert swap_prologue_s.read(ctx) is None
    # what it spans it spans: the reader of the union reads the rest
    assert wave_unspanned_s.read(ctx) == pytest.approx(0.07)
    # a root without the attribute is no swap wave either
    rootless = [span("scheduler.schedule", 1, None, 11.0, 0.25, rows=1000)]
    assert swap_wave_s.read({"spans": rootless, "waves": WAVES}) is None
    assert swap_prologue_s.read({"spans": rootless, "waves": WAVES}) is None
