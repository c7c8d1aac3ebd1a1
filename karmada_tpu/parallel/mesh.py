"""Scheduling-grid device mesh: env-resolved construction + shardings.

The fleet kernels (scheduler/fleet.py) take a ``jax.sharding.Mesh`` as a
static argument and partition the bucket-grid solve along the bindings
axis with ``with_sharding_constraint`` (and, opt-in, the cluster axis) —
SNIPPETS [2]'s naive-sharding pattern applied to the scheduling grid.
This module is everything AROUND that mesh:

- **Construction** (``scheduling_mesh``/``resolve_mesh``): a 1-D (or
  B×C) mesh over the first N visible devices, resolved once per engine
  from ``KARMADA_TPU_MESH_DEVICES`` / ``KARMADA_TPU_MESH_CLUSTER_AXIS``
  (the trace-manifest resolution pattern: an explicit Mesh passes
  through, ``False`` forces single-device even with the env set, None
  falls back to the env default). CPU CI dry-runs set
  ``JAX_PLATFORMS=cpu`` and ``--xla_force_host_platform_device_count``
  before the first jax import.
- **Identity** (``mesh_shape``/``mesh_from_shape``): the canonical,
  JSON-serializable shape of a mesh — ``(("b", nb), ("c", nc))`` — used
  by the fleet trace keys, the prewarm manifest records, the solver
  sidecar's reporting line, and ``/debug/traces``. A Mesh object is not
  serializable; its shape is, and two processes whose meshes share a
  shape compile the same partitioned executables, so the shape IS the
  compile-identity component (a manifest recorded at mesh=1 can never
  seed a mesh=8 boot's ledger — the keys differ).
- **Kernel-family shardings** (``FAMILY_SPECS``/``family_shardings``):
  the documented in/out ``PartitionSpec`` layout of every fleet kernel
  family (divide / dispense / estimate / masks / quota) plus the fleet
  residents. The production paths place data via ``shard_rows`` (engine
  quota admission) and the fleet kernels' in-body constraints /
  ``FleetTable._alloc_resident`` — FAMILY_SPECS is the REFERENCE those
  layouts are written against (asserted well-formed in
  tests/test_mesh_sharding.py), and the construction surface for
  explicit placers a new sharded entry point may add (see
  DEVELOPMENT.md "Adding a sharded kernel entry point").

Padding contract: the fleet pads batches to a multiple of the effective
chunk (itself pow2 ≥ 256), and supported mesh extents are powers of two
≤ 8 axes-product — so every padded batch divides the mesh evenly and
padding rows (``rows == -1``) are masked out exactly like the existing
bucket padding. ``divisible`` is the predicate the dispatch site guards
on; a non-dividing mesh falls back to single-device semantics rather
than mis-sharding.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

log = logging.getLogger("karmada_tpu")

#: device count of the scheduling mesh: "" / "0" / "1" = single-device
#: (mesh off), an integer N = first N visible devices, "auto" = every
#: visible device. Declared in utils.flags.ENV_FLAGS.
MESH_ENV = "KARMADA_TPU_MESH_DEVICES"

#: cluster-axis extent of the mesh (the "c" axis): 1 (default) = pure
#: binding-parallel; >1 additionally shards the cluster axis (the
#: dispense sorts ride c-axis collectives). Must divide the device count.
CLUSTER_AXIS_ENV = "KARMADA_TPU_MESH_CLUSTER_AXIS"


def scheduling_mesh(
    n_devices: Optional[int] = None, *, cluster_axis: int = 1
):
    """A ("b", "c") mesh over the first n visible devices (the
    binding-parallel axis carries n // cluster_axis). Thin delegate to
    ``solver.default_mesh`` so the two construction paths cannot drift."""
    from .solver import default_mesh

    return default_mesh(n_devices, cluster_axis=cluster_axis)


def resolve_mesh(spec=None):
    """Normalize an engine's ``mesh`` argument.

    A Mesh passes through; ``False`` forces single-device even with the
    env set (the explicit opt-out, mirroring ``trace_manifest=""``);
    None falls back to the env default: ``KARMADA_TPU_MESH_DEVICES``
    unset/empty/"0"/"1" resolves to None (single-device), ``"auto"`` to
    every visible device, an integer N to the first N. A set env that
    cannot build (fewer devices than asked, bad integer, cluster axis
    not dividing) raises — the operator asked for a mesh; silently
    benchmarking single-device would mask a misconfigured rig."""
    if spec is False:
        return None
    if spec is not None:
        return spec  # an already-built Mesh (duck-typed: jax stays lazy)
    raw = os.environ.get(MESH_ENV, "").strip().lower()
    if raw in ("", "0", "1"):
        return None
    c_raw = os.environ.get(CLUSTER_AXIS_ENV, "1").strip() or "1"
    try:
        cluster_axis = int(c_raw)
    except ValueError:
        raise ValueError(
            f"{CLUSTER_AXIS_ENV}={c_raw!r} is not an integer"
        ) from None
    if raw == "auto":
        n = None
    else:
        try:
            n = int(raw)
        except ValueError:
            raise ValueError(
                f"{MESH_ENV}={raw!r}: expected an integer device count, "
                "'auto', or empty/0/1 for single-device"
            ) from None
    mesh = scheduling_mesh(n, cluster_axis=cluster_axis)
    record_active_mesh(mesh)
    return mesh


def mesh_shape(mesh) -> Optional[tuple]:
    """Canonical (JSON-round-trippable) identity of a mesh:
    ``(("b", nb), ("c", nc))``; None for single-device. This tuple is
    what fleet trace keys and manifest records carry — equal shapes
    compile equal partitioned executables."""
    if mesh is None:
        return None
    return tuple(
        (str(name), int(size))
        for name, size in zip(mesh.axis_names, mesh.devices.shape)
    )


def mesh_from_shape(shape):
    """Rebuild a mesh matching a recorded ``mesh_shape`` over THIS
    process's devices (prewarm replay of a meshed trace record). Raises
    when the current backend cannot host it — the caller (replay) counts
    that record failed, so it can never seed the new-trace ledger."""
    if shape is None:
        return None
    axes = {str(name): int(size) for name, size in shape}
    unknown = set(axes) - {"b", "c"}
    if unknown:
        raise ValueError(f"unknown mesh axes {sorted(unknown)} in {shape!r}")
    total = axes.get("b", 1) * axes.get("c", 1)
    return scheduling_mesh(total, cluster_axis=axes.get("c", 1))


def materialize_mesh_statics(statics: dict) -> dict:
    """Replace a serialized ``mesh`` shape entry (tuple/list form, as
    stored by the trace manifest and the IR spec grid) with a live Mesh
    built over this process's devices. Entries already holding a Mesh —
    or None — pass through untouched."""
    mesh = statics.get("mesh")
    if mesh is None or not isinstance(mesh, (tuple, list)):
        return statics
    out = dict(statics)
    out["mesh"] = mesh_from_shape(mesh)
    return out


def divisible(n: int, mesh, axis: str = "b") -> bool:
    """True when an ``n``-extent axis divides the mesh axis evenly — the
    dispatch-site guard before sharding that axis (padding has already
    rounded batch rows to the chunk quantum, so in practice only exotic
    non-pow2 meshes fail this)."""
    if mesh is None:
        return True
    size = dict(
        zip(mesh.axis_names, (int(s) for s in mesh.devices.shape))
    ).get(axis, 1)
    return size <= 1 or n % size == 0


def pad_to_mesh(n: int, mesh, axis: str = "b") -> int:
    """Round ``n`` up to the next multiple of the mesh axis extent (the
    mesh-divisible bucket; padding rows are masked out downstream)."""
    if mesh is None:
        return n
    size = dict(
        zip(mesh.axis_names, (int(s) for s in mesh.devices.shape))
    ).get(axis, 1)
    return n if size <= 1 else -(-n // size) * size


# -- kernel-family in/out layouts -------------------------------------------
#
# PartitionSpec element grammar: "b" = bindings axis, "c" = clusters axis,
# None = replicated dimension. One entry per positional kernel input, in
# dispatch order; "out" mirrors the kernel's outputs. Table-shaped inputs
# (interned slot tables, cap tensors, remaining) replicate — they are
# gathered per row on device and orders of magnitude smaller than the
# grid. These are the REFERENCE layouts: the fleet kernels realize them
# as in-body constraints and the engine's quota path via shard_rows;
# family_shardings turns an entry into concrete NamedShardings for
# explicit device_put placement.

FAMILY_SPECS: dict = {
    # divide_replicas(strategy[B], replicas[B], candidates[B,C],
    #                 static_w[B,C], avail[B,C], prev[B,C], fresh[B])
    "divide": {
        "in": (("b",), ("b",), ("b", "c"), ("b", "c"), ("b", "c"),
               ("b", "c"), ("b",)),
        "out": (("b", "c"), ("b",)),
    },
    # take_by_weight_batch(n[B], weights[B,C], limits[B,C], prev[B,C])
    "dispense": {
        "in": (("b",), ("b", "c"), ("b", "c"), ("b", "c")),
        "out": (("b", "c"),),
    },
    # general_estimate(available_cap[C,R], requests[B,R])
    "estimate": {
        "in": (("c", None), ("b", None)),
        "out": (("b", "c"),),
    },
    # contains_all/intersects(table[C,W], query[W])
    "masks": {
        "in": (("c", None), (None,)),
        "out": (("c",),),
    },
    # quota_admit(ns_ids[B], demand[B,R], remaining[N,R])
    "quota": {
        "in": (("b",), ("b", None), (None, None)),
        "out": (("b",), (None, None)),
    },
    # the fleet residents (donated, persistent): dense[cap,C], meta[cap],
    # entries[cap,k] — sharded over table rows so pass-to-pass donation
    # aliases shard-local buffers and no gather precedes the solve
    "fleet_resident": {
        "in": (("b", "c"), ("b",), ("b", None)),
        "out": (("b", "c"), ("b",), ("b", None)),
    },
}


def family_shardings(mesh, family: str, direction: str = "in") -> tuple:
    """NamedShardings for one kernel family's flat signature (see
    FAMILY_SPECS). The "c" element only engages when the mesh carries a
    >1 cluster axis — otherwise those dimensions replicate, matching the
    fleet kernels' ``shard_c`` gating."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = FAMILY_SPECS[family][direction]
    sizes = dict(zip(mesh.axis_names, (int(s) for s in mesh.devices.shape)))
    c_on = sizes.get("c", 1) > 1

    def el(e):
        if e == "c" and not c_on:
            return None
        return e

    return tuple(
        NamedSharding(mesh, P(*(el(e) for e in spec))) for spec in specs
    )


def shard_rows(mesh, *arrays):
    """Place arrays with their LEADING axis sharded over the mesh "b"
    axis (trailing dims replicated) — the one-liner for batch-axis
    inputs like the quota admission wave. Arrays whose leading extent
    does not divide the mesh pass through unplaced (single-device
    semantics, the same fallback the fleet dispatch applies)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = []
    for a in arrays:
        if mesh is None or not divisible(int(a.shape[0]), mesh):
            out.append(a)
        else:
            spec = P("b", *([None] * (a.ndim - 1)))
            out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


# -- process-level mesh identity (reporting surfaces) -----------------------

#: last mesh this process resolved/adopted (shape form): the solver
#: sidecar line, /debug/traces, `karmadactl-tpu trace dump` and the
#: warmup stats all read THIS so an operator can tell a single-chip from
#: an 8-chip plane without poking jax
_ACTIVE_SHAPE: list = [None]


def record_active_mesh(mesh) -> None:
    """Adopt a mesh as this process's reported scheduling mesh (engines
    call it on construction; resolve_mesh on env resolution)."""
    if mesh is not None:
        _ACTIVE_SHAPE[0] = mesh_shape(mesh)


def active_mesh_shape() -> Optional[list]:
    """JSON form of the process's scheduling-mesh shape ([["b", nb],
    ["c", nc]]), or None when every engine runs single-device."""
    shape = _ACTIVE_SHAPE[0]
    if shape is None:
        return None
    return [[name, size] for name, size in shape]
