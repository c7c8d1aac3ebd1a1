"""Fleet table, the upsert phase of a pass that repacks rows: over the
``scheduler.solve`` spans of the waves that carry ``rows_packed`` > 0 (a
swapped batch: the region-loss ring's ``L`` and ``r`` waves; the identity
path packs none), the MEDIAN duration of their ``kernel.host`` stretch with
``phase`` upsert: the child by ``parent_id``, else the one whose interval
lies inside the solve span. The per-wave medians of the other readers land
on the ``h`` / ``d`` waves and see none of this. None where no pass of the
waves packed a row, or the program stamps no such attribute or phase."""

import statistics

from ._per_wave import in_waves

# a phase span is recorded from the same stamps as its pass: equal up to
# the float's last digits
_EDGE_S = 1e-6


def read(ctx):
    spans = in_waves(ctx["spans"], ctx["waves"])
    upserts = [s for s in spans if s["name"] == "kernel.host"
               and s["attrs"].get("phase") == "upsert"]
    by_parent = {s["parent_id"]: s for s in upserts
                 if s.get("parent_id") is not None}
    took = []
    for solve in spans:
        if solve["name"] != "scheduler.solve" or not (
                solve["attrs"].get("rows_packed") or 0) > 0:
            continue
        child = by_parent.get(solve["span_id"])
        if child is None:
            a = solve["start"] - _EDGE_S
            b = solve["start"] + solve["duration_s"] + _EDGE_S
            child = next((s for s in upserts if a <= s["start"]
                          and s["start"] + s["duration_s"] <= b), None)
        if child is not None:
            took.append(child["duration_s"])
    return statistics.median(took) if took else None
