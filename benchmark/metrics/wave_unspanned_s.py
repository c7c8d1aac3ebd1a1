"""Tracer: what a wave spends under no span of the program's. Per wave, its
wall minus the length of the UNION of the program's spans (every name of
``ctx["spans"]``; the harness's own annotations are not among them) clipped
to the wave; the median over the waves. Nested and overlapping spans count
once; a wave that holds no span reads its whole wall. None where the program
recorded no span at all."""

import statistics


def read(ctx):
    spans = ctx["spans"]
    if not spans:
        return None
    ivals = sorted((s["start"], s["start"] + s["duration_s"]) for s in spans)
    dark, i = [], 0
    for a, b in ctx["waves"]:
        # spans that ended before this wave opened end before the next too
        while i < len(ivals) and ivals[i][1] <= a:
            i += 1
        covered, cur, j = 0.0, a, i
        while j < len(ivals) and ivals[j][0] < b:
            lo, hi = max(ivals[j][0], cur), min(ivals[j][1], b)
            if hi > lo:
                covered += hi - lo
                cur = hi
            j += 1
        dark.append((b - a) - covered)
    return statistics.median(dark) if dark else None
