"""Fresh XLA traces inside the window: the program's compile counter's
delta plus the passes that reported a new trace. Has to read 0."""


def read(ctx):
    return float(ctx["win"]["compiles"] + ctx["win"]["fresh_hits"])
