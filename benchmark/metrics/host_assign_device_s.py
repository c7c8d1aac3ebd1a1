"""Device seconds of the general host path's division per traced wave, from
the trace: the jitted ``divide_replicas`` that ``_schedule_chunk``'s
``_assign`` dispatches over a chunk's dense (B x C) inputs. Nothing where no
such kernel ran (a batch that rides the fleet table whole, or a chunk small
enough for the host's numpy divider)."""

KERNEL = "jit_divide_replicas"


def read(ctx):
    t = ctx["trace"]
    total = t["op_s"].get(KERNEL, 0.0)
    return total / t["waves"] if total > 0 else None
