"""Self times from the program's own spans (``tracer.dump()`` dicts: name,
span_id, parent_id, start on perf_counter, duration_s). A span's self time
is its duration minus its direct children's."""

from __future__ import annotations

import statistics


def self_times(spans: list) -> list:
    """[(name, start, self seconds)]"""
    child = {}
    for s in spans:
        p = s.get("parent_id")
        if p is not None:
            child[p] = child.get(p, 0.0) + s["duration_s"]
    return [
        (s["name"], s["start"],
         max(0.0, s["duration_s"] - child.get(s["span_id"], 0.0)))
        for s in spans
    ]


def per_wave_median(spans: list, waves: list, match) -> float | None:
    """Median over the waves of the summed self time of the spans whose name
    ``match`` accepts and that start inside the wave. None where no wave
    holds such a span (nothing to read)."""
    selfs = sorted((st, v) for n, st, v in self_times(spans) if match(n))
    if not selfs:
        return None
    sums, i = [], 0
    for a, b in waves:
        while i < len(selfs) and selfs[i][0] < a:
            i += 1
        tot, j = 0.0, i
        while j < len(selfs) and selfs[j][0] <= b:
            tot += selfs[j][1]
            j += 1
        if j > i:
            sums.append(tot)
        i = j
    return statistics.median(sums) if sums else None
