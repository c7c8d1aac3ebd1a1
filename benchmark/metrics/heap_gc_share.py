"""Seconds the garbage collector ran (gc.callbacks) after the profiler
stopped, over the wall time of those waves, %."""


def read(ctx):
    return 100.0 * ctx["win"]["gc_rest_s"] / ctx["rest_wall"]
