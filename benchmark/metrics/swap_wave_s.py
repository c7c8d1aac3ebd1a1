"""Engine host prologue, a swap wave's wall inside the engine: over the
waves' ``scheduler.schedule`` spans with ``path`` = ``full`` (the whole
prologue ran: the region-loss ring's ``L`` and ``r`` waves; an ``h`` / ``d``
wave takes the identity path), the MEDIAN duration. The per-wave medians of
the other readers land on the ``h`` / ``d`` waves and see none of this. None
where no pass of the waves ran the full prologue, or the program stamps no
such span or attribute."""

import statistics

from ._per_wave import in_waves


def full_passes(ctx) -> tuple:
    """(the waves' spans, the ``scheduler.schedule`` spans among them whose
    ``path`` is ``full``)."""
    spans = in_waves(ctx["spans"], ctx["waves"])
    return spans, [s for s in spans if s["name"] == "scheduler.schedule"
                   and s["attrs"].get("path") == "full"]


def read(ctx):
    _, roots = full_passes(ctx)
    return statistics.median(s["duration_s"] for s in roots) if roots else None
