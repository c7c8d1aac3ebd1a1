"""GL001 bad fixture: host control flow + host sync inside a jitted
kernel. Parsed by graftlint only — never imported or executed."""

import os
import time
from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("flag",))
def kernel(x, n, flag: bool):
    if n > 0:  # BAD: Python `if` on a traced value
        x = x + 1
    while x.sum() > 0:  # BAD: Python `while` on a traced value
        x = x - 1
    scale = float(x[0])  # BAD: host conversion of a traced value
    print("tracing", flag)  # BAD: trace-time print
    t0 = time.time()  # BAD: clock read baked into the trace
    plat = os.environ.get("KARMADA_TPU_MESH_DEVICES", "")  # BAD: env in trace
    y = x.item()  # BAD: host sync
    return jnp.asarray([scale, t0, float(len(plat)), y])
