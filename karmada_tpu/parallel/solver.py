"""The fused scheduling step and its device-mesh sharding.

``schedule_step`` is the flagship jitted program: estimator availability +
min-merge + unified division in one XLA computation (the whole
Algorithm.Schedule subtree of SURVEY.md section 3.1 minus host-side group
search). Bindings are independent, so the batch axis shards like data
parallelism; the cluster axis can shard like model parallelism when
num_clusters x resource-dims outgrows a core (SURVEY.md section 5
"long-context" analogue: the per-row sorts over a sharded cluster axis are
where XLA inserts collectives).

``make_sharded_step`` places inputs with NamedSharding over a
``Mesh(axis_names=("b", "c"))`` and lets GSPMD partition: elementwise work
stays local; the lexicographic sorts along the cluster axis induce
all-gathers on the ``c`` axis only — exactly the collective structure the
scaling-book recipe predicts for sort-limited kernels. With ``c`` unsharded
(the default for <=5k clusters) the step runs with zero communication.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.divide import DivideResult, _divide_batch
from ..ops.estimate import (
    general_estimate,
    general_estimate_interned,
    merge_estimates,
)


def _merge_and_divide(
    general, has_summary, strategy, replicas, candidates, static_w, prev,
    fresh, has_aggregated, wide, fast,
) -> DivideResult:
    """Shared tail of both step variants: sentinel masking, estimator
    min-merge, unified division."""
    general = jnp.where(has_summary[None, :], general, jnp.int32(-1))
    avail = merge_estimates(replicas, (general,))
    out, unsched = _divide_batch(
        strategy, replicas, candidates, static_w, avail, prev, fresh,
        has_aggregated, wide, fast,
    )
    return DivideResult(assignment=out, unschedulable=unsched)


def _schedule_step(
    available_cap: jnp.ndarray,  # int64[C, R] cluster capacity
    has_summary: jnp.ndarray,  # bool[C]
    requests: jnp.ndarray,  # int64[B, R]
    strategy: jnp.ndarray,  # int32[B]
    replicas: jnp.ndarray,  # int32[B]
    candidates: jnp.ndarray,  # bool[B, C]
    static_w: jnp.ndarray,  # int32[B, C]
    prev: jnp.ndarray,  # int32[B, C]
    fresh: jnp.ndarray,  # bool[B]
    has_aggregated: bool = True,
    wide: bool = True,
    fast: tuple | None = None,
) -> DivideResult:
    general = general_estimate(available_cap, requests)
    return _merge_and_divide(
        general, has_summary, strategy, replicas, candidates, static_w,
        prev, fresh, has_aggregated, wide, fast,
    )


schedule_step = jax.jit(
    _schedule_step, static_argnames=("has_aggregated", "wide", "fast")
)


def _schedule_step_interned(
    available_cap: jnp.ndarray,  # int64[C, R] cluster capacity
    has_summary: jnp.ndarray,  # bool[C]
    profiles: jnp.ndarray,  # int64[U, R] unique request rows
    prof_idx: jnp.ndarray,  # int32[B]
    strategy: jnp.ndarray,  # int32[B]
    replicas: jnp.ndarray,  # int32[B]
    candidates: jnp.ndarray,  # bool[B, C]
    static_w: jnp.ndarray,  # int32[B, C]
    prev: jnp.ndarray,  # int32[B, C]
    fresh: jnp.ndarray,  # bool[B]
    has_aggregated: bool = True,
    wide: bool = True,
    fast: tuple | None = None,
) -> DivideResult:
    """``schedule_step`` with request-profile interning: the estimator runs
    per unique profile ([U, C] divisions) and the per-binding matrix is a
    one-hot-matmul gather — see ``ops.estimate.general_estimate_interned``."""
    general = general_estimate_interned(available_cap, profiles, prof_idx)
    return _merge_and_divide(
        general, has_summary, strategy, replicas, candidates, static_w,
        prev, fresh, has_aggregated, wide, fast,
    )


schedule_step_interned = jax.jit(
    _schedule_step_interned, static_argnames=("has_aggregated", "wide", "fast")
)


def make_sharded_step(mesh: Mesh, *, shard_clusters: bool = False):
    """jit ``schedule_step`` with bindings sharded over mesh axis ``b`` (and
    optionally clusters over ``c``). Inputs may be numpy; placement happens
    via in_shardings."""
    c_ax = "c" if shard_clusters and "c" in mesh.axis_names else None
    bc = P("b", c_ax)
    row_b = P("b")
    row_c = P(c_ax)
    in_shardings = tuple(
        NamedSharding(mesh, s)
        for s in (
            P(c_ax, None),  # available_cap[C, R]
            row_c,  # has_summary[C]
            P("b", None),  # requests[B, R]
            row_b,  # strategy
            row_b,  # replicas
            bc,  # candidates
            bc,  # static_w
            bc,  # prev
            row_b,  # fresh
        )
    )
    out_shardings = DivideResult(
        assignment=NamedSharding(mesh, bc),
        unschedulable=NamedSharding(mesh, row_b),
    )
    return jax.jit(
        _schedule_step,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        static_argnames=("has_aggregated", "wide", "fast"),
    )


def default_mesh(
    n_devices: int | None = None, *, cluster_axis: int = 1
) -> Mesh:
    """Mesh over the first n devices of the default backend: ("b", "c")
    with the cluster axis sized ``cluster_axis`` (1 = pure
    binding-parallel). A backend that shows fewer devices than asked
    raises — it never substitutes another platform's devices."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ValueError(
            f"default_mesh: {n} devices requested but only {len(devs)} visible "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N and "
            "JAX_PLATFORMS=cpu before the first jax import to dry-run "
            "multi-chip on CPU)"
        )
    if n % cluster_axis:
        raise ValueError(
            f"default_mesh: {n} devices not divisible by cluster_axis={cluster_axis}"
        )
    devs = devs[:n]
    b = n // cluster_axis
    import numpy as np

    grid = np.array(devs).reshape(b, cluster_axis)
    return Mesh(grid, axis_names=("b", "c"))
