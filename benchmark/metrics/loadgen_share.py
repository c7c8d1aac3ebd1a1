"""Share of the wall time spent outside the waves: the harness's own work
between two calls of the entry point. Read over the waves after the
profiler stopped."""


def read(ctx):
    inside = sum(b - a for a, b in ctx["waves"])
    return 100.0 * (ctx["rest_wall"] - inside) / ctx["rest_wall"]
