"""Seconds in FULL collections of the heap, as the program's own
``runtime.gc`` spans give them, over the wall time after the profiler
stopped, %. (``heap_gc_share`` is the harness's reading from outside, over
all generations.) None where the program has no such span; 0 where it has
and no full collection fell into the stretch."""

from ._per_wave import in_waves


def read(ctx):
    try:
        from karmada_tpu.utils.tracing import SPAN_NAMES
    except ImportError:
        return None
    if "runtime.gc" not in SPAN_NAMES:
        return None
    gc_s = sum(s["duration_s"] for s in in_waves(ctx["spans"], ctx["waves"])
               if s["name"] == "runtime.gc")
    return 100.0 * gc_s / ctx["rest_wall"]
