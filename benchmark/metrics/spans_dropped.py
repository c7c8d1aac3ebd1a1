"""Spans the program's tracer evicted off its ring since the window opened
(``clear()`` zeroes the count there). Has to read 0: above it, every metric
read from spans is short of the spans that went."""


def read(ctx):
    try:
        from karmada_tpu.utils.tracing import tracer
    except ImportError:
        return None
    dropped = getattr(tracer, "dropped_total", None)
    return None if dropped is None else float(dropped)
