"""Keys whose reconcile did something, of the keys reconciled: 100 x
sum(keys - noop) / sum(keys) over the ``controller.*`` drains, after the
profiler stopped, that carry both counts. A drain that could not tell its
no-ops carries no ``noop`` and is left out, never guessed."""

from ._per_wave import in_waves


def read(ctx):
    keys = noop = 0
    for s in in_waves(ctx["spans"], ctx["waves"]):
        a = s["attrs"]
        if s["name"].startswith("controller.") and "keys" in a and "noop" in a:
            keys += a["keys"]
            noop += a["noop"]
    return 100.0 * (keys - noop) / keys if keys else None
