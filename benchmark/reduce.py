"""Reduction of a profiler trace to numbers: pure functions over plain event
lists ``(name, start_ns, duration_ns)``, so a test can feed them a small
recorded trace (benchmark/tests/recorded_trace.json). ``trace.py`` extracts
such lists from the ``.xplane.pb``."""

from __future__ import annotations

import re

_HASH = re.compile(r"\(\d+\)$")


def op_name(raw: str) -> str:
    """``jit__fleet_pass(1234567)`` -> ``jit__fleet_pass``."""
    return _HASH.sub("", raw)


def clip(events: list, lo: int, hi: int) -> list:
    """Events cut to the stretch [lo, hi)."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_union(events: list) -> tuple:
    """(busy ns, merged [start, end) intervals) of overlapping events."""
    merged = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def op_sums(events: list) -> dict:
    """name -> summed device ns."""
    out: dict = {}
    for name, _, d in events:
        n = op_name(name)
        out[n] = out.get(n, 0) + d
    return out


def gaps(merged: list, lo: int, hi: int) -> list:
    """Idle [start, end) intervals of the stretch [lo, hi)."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def attribute_gaps(idle: list, spans: list) -> dict:
    """name -> idle ns, each gap charged to the innermost span open at its
    midpoint. ``spans`` are (name, start_ns, duration_ns); innermost = the
    one that started last among those that contain the midpoint."""
    out: dict = {}
    ordered = sorted(spans, key=lambda t: t[1])
    for a, b in idle:
        mid = (a + b) // 2
        name = "_no_span_open_"
        for n, s, d in ordered:
            if s > mid:
                break
            if s + d >= mid:
                name = n
        out[name] = out.get(name, 0) + (b - a)
    return out


def top(d: dict, n: int = 10) -> list:
    """[[name, seconds], ...] largest first."""
    return [[k, v / 1e9] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]
