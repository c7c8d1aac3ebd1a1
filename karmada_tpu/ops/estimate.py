"""Capacity estimation kernels: MaxAvailableReplicas as batched integer math.

General estimator (ref: pkg/estimator/client/general.go:96-196): per cluster,
available = allocatable - allocated - allocating; max replicas = min over
requested resource dims of floor(available / request), min'ed with the
allowed-pod headroom. Each replica occupies one pod, so the pods dimension
carries an implicit request of 1 — which reproduces getAllowedPodNumber
(general.go:96-114) as just another dimension.

The node/model-grade variants live in karmada_tpu.estimator; they produce the
same ``[B, C]`` availability matrix and are min-merged by
``merge_estimates`` (ref: pkg/scheduler/core/util.go:54-104).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MAX_INT32 = jnp.int32(2**31 - 1)
UNAUTHENTIC = jnp.int32(-1)  # estimator "no answer" (client/interface.go:30)


@jax.jit
def general_estimate(
    available_cap: jnp.ndarray,  # int64[C, R]: allocatable-allocated-allocating
    requests: jnp.ndarray,  # int64[B, R]: per-replica requests (0 = not requested)
) -> jnp.ndarray:
    """int32[B, C] max available replicas (>= 0); MAX_INT32 when the binding
    requests nothing at all (best-effort) — callers clamp the sentinel."""
    cap = jnp.maximum(available_cap, 0)  # negative available -> 0 replicas
    r_dims = requests.shape[-1]
    best = jnp.full(requests.shape[:-1] + (cap.shape[0],), jnp.int64(2**31 - 1))
    for r in range(r_dims):  # R is small and static; unrolled under jit
        req_r = requests[..., r][..., None]  # [B, 1]
        ratio = cap[None, :, r] // jnp.maximum(req_r, 1)
        best = jnp.where(req_r > 0, jnp.minimum(best, ratio), best)
    return jnp.minimum(best, jnp.int64(2**31 - 1)).astype(jnp.int32)


# row_coupled: the graftlint-dep delta-safety declarations — request row
# b reads only requests[b] (the cap table is replicated state), so the
# estimator family is certified delta_safe (IR006-proven against the
# jaxpr; see tools/graftlint/dep.py)
general_estimate.row_coupled = False


def gather_profile_rows(
    table: jnp.ndarray,  # int32[U, C]
    idx: jnp.ndarray,  # int32[B]
) -> jnp.ndarray:
    """int32[B, C] = table[idx], expressed as a one-hot matmul.

    HISTORY: on the round-1/2 toolchain a direct row
    gather with a [B]-sized index vector hung XLA compilation inside
    lax.scan; this MXU formulation was the workaround. Round-3 re-probes
    (inside lax.scan, chunk=4096, U=2..3500) show plain gathers now
    compile cleanly and run ~2.4x faster at large U, so the fleet solve
    (scheduler/fleet.py) uses plain gathers. This helper is retained for
    callers that want the matmul form (and as the fallback should a future
    toolchain regress); the 16-bit split keeps the selection exact for
    EVERY int32 value (sentinels included) — each half fits f32's mantissa
    and a one-hot row selects a single entry, so there is no accumulation
    error."""
    u = table.shape[0]
    onehot = jax.nn.one_hot(idx, u, dtype=jnp.float32)  # [B, U]
    # 16-bit split keeps every int32 exact in f32 (each half < 2^16 and the
    # one-hot rows select a single entry, so no accumulation error); the
    # arithmetic shift keeps negative sentinels (-1 no-answer) intact
    lo = (table & 0xFFFF).astype(jnp.float32)
    hi = (table >> 16).astype(jnp.float32)
    # HIGHEST precision: the TPU MXU's default bf16 passes would round the
    # 16-bit halves (8-bit mantissa); full-f32 passes keep them exact
    lo_g = jnp.einsum(
        "bu,uc->bc", onehot, lo, precision=jax.lax.Precision.HIGHEST
    ).astype(jnp.int32)
    hi_g = jnp.einsum(
        "bu,uc->bc", onehot, hi, precision=jax.lax.Precision.HIGHEST
    ).astype(jnp.int32)
    return (hi_g << 16) | lo_g


gather_profile_rows.row_coupled = False  # row b reads table[idx[b]] only


@jax.jit
def general_estimate_interned(
    available_cap: jnp.ndarray,  # int64[C, R]
    profiles: jnp.ndarray,  # int64[U, R]: unique request rows
    prof_idx: jnp.ndarray,  # int32[B]: row i uses profiles[prof_idx[i]]
) -> jnp.ndarray:
    """int32[B, C] — ``general_estimate`` with request-profile interning.

    Real fleets carry few unique ReplicaRequirements (a handful of resource
    T-shirt sizes), so the [B, C, R] integer divisions collapse to [U, C]
    followed by a row gather: the estimator cost becomes O(U x C) instead of
    O(B x C), the single biggest win for the 100k-binding hot path. The
    packing layer produces (profiles, prof_idx) via np.unique over request
    rows — exact, no semantic change (general.go:156-196 per-row math is
    unchanged)."""
    per_profile = general_estimate(available_cap, profiles)  # [U, C]
    return gather_profile_rows(per_profile, prof_idx)


general_estimate_interned.row_coupled = False  # per-row profile lookup


@jax.jit
def merge_estimates(
    replicas: jnp.ndarray,  # int32[B]
    estimates: tuple[jnp.ndarray, ...],  # each int32[B, C]; -1 = no answer
) -> jnp.ndarray:
    """core/util.go:54-104: min across estimators ignoring UNAUTHENTIC,
    then clamp an untouched MAX_INT32 sentinel to spec.Replicas, and
    short-circuit zero-replica (non-workload) bindings to the sentinel path."""
    b = replicas.shape[0]
    c = estimates[0].shape[1]
    out = jnp.full((b, c), MAX_INT32)
    for est in estimates:
        out = jnp.where(est == UNAUTHENTIC, out, jnp.minimum(out, est))
    out = jnp.where(replicas[:, None] == 0, MAX_INT32, out)
    return jnp.where(out == MAX_INT32, replicas[:, None], out)


merge_estimates.row_coupled = False  # element-wise min across estimators
