"""Engine host prologue, the positions a swap wave's prologue visited: over
the same ``scheduler.schedule`` spans as ``swap_wave_s`` (``path`` =
``full``), the MEDIAN of the ``rows`` their children ``scheduler.pack``
carry (by ``parent_id``; summed where a pass holds two: a delta that tried
one row, then the walk). A program that walks every position of a swapped
batch stamps the batch's length there; one that diffs the batch against the
armed one stamps the positions that hold another object. Beside
``swap_prologue_s``: the seconds follow this count. None where
``swap_wave_s`` reads None, or no such pass has a ``scheduler.pack`` child
that carries ``rows``."""

import statistics

from .swap_wave_s import full_passes


def read(ctx):
    spans, roots = full_passes(ctx)
    visited: dict = {}
    for s in spans:
        rows = s["attrs"].get("rows")
        if (s["name"] == "scheduler.pack" and rows is not None
                and s.get("parent_id") is not None):
            visited[s["parent_id"]] = visited.get(s["parent_id"], 0) + rows
    per_pass = [visited[r["span_id"]] for r in roots
                if r["span_id"] in visited]
    return statistics.median(per_pass) if per_pass else None
