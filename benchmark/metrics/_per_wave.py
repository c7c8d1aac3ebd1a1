"""Shared by the span readers that sum a value over each wave: the median
over the waves of the sum, taken over the spans that start inside the wave
(as benchmark/spans.py places a span in a wave)."""

from __future__ import annotations

import bisect
import statistics


def median_of_sums(spans: list, waves: list, value) -> float | None:
    """``value(span)`` answers a number, or None for a span that does not
    count. None where no wave holds a span that counts."""
    picked = sorted(
        (s["start"], v) for s in spans if (v := value(s)) is not None
    )
    starts = [st for st, _ in picked]
    sums = []
    for a, b in waves:
        i, j = bisect.bisect_left(starts, a), bisect.bisect_right(starts, b)
        if j > i:
            sums.append(sum(v for _, v in picked[i:j]))
    return statistics.median(sums) if sums else None


def host_phases_named(spans: list) -> bool:
    """Whether the program records its ``kernel.host`` stretches one by one,
    each with its ``phase`` (one that lumps them into a single span a pass
    gives the readers of a stretch nothing to read)."""
    return any(s["name"] == "kernel.host" and "phase" in s["attrs"]
               for s in spans)


def in_waves(spans: list, waves: list) -> list:
    """The spans that start between the first wave's start and the last
    wave's end."""
    lo, hi = waves[0][0], waves[-1][1]
    return [s for s in spans if lo <= s["start"] <= hi]
