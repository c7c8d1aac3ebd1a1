"""Engine host prologue, the engine's own share of a swap wave: over the
same ``scheduler.schedule`` spans as ``swap_wave_s`` (``path`` = ``full``),
the MEDIAN of the summed durations of their children ``scheduler.pack`` +
``scheduler.handoff`` + ``scheduler.rearm`` (by ``parent_id``): what
``TensorScheduler`` spends around the fleet table's pass, beside
``swap_upsert_s`` for the table's. None where ``swap_wave_s`` reads None."""

import statistics

from .swap_wave_s import full_passes

OWN = ("scheduler.pack", "scheduler.handoff", "scheduler.rearm")


def read(ctx):
    spans, roots = full_passes(ctx)
    if not roots:
        return None
    own: dict = {}
    for s in spans:
        if s["name"] in OWN and s.get("parent_id") is not None:
            own[s["parent_id"]] = own.get(s["parent_id"], 0.0) + s["duration_s"]
    return statistics.median(own.get(r["span_id"], 0.0) for r in roots)
