"""graftlint-IR: jaxpr-level kernel auditor.

The AST tier (core.py/rules.py) guards Python-source invariants; the class
of bugs that actually burns TPU time — silent float64/weak-type promotion,
host transfers hidden inside a kernel, large arrays closed over into a
trace so every snapshot recompiles, prewarm-manifest entries drifting from
what the kernels really trace to — only exists in the lowered IR,
invisible to any AST pass. This tier discovers every exported kernel entry
point (the ops/ dispense/divide/estimate/masks families and the scheduler
fleet kernels), abstractly traces each via ``jax.make_jaxpr`` under
``JAX_PLATFORMS=cpu`` across a representative bucket grid (the same
cap/row buckets the prewarm trace manifest records), and machine-checks
the IR001-IR005 invariants (irrules.py) over the resulting jaxprs.

Run it:

    python -m tools.graftlint --ir                    # full registry
    python -m tools.graftlint --ir divide_replicas    # one family
    python -m tools.graftlint --ir --manifest PATH    # + manifest audit
    karmadactl-tpu lint --ir                          # same, CLI verb

Tracing is ABSTRACT: ``make_jaxpr`` over ``ShapeDtypeStruct``s never
compiles or executes anything, so the whole grid audits in seconds on any
backend. Findings share the AST tier's machinery end to end — inline
``# graftlint: disable=IR00X`` pragmas on the kernel's ``def`` line,
justified entries in ``graftlint_baseline.json``, ``--format json``.

This module imports jax ONLY inside the tracing functions: importing it
(for the registry listing, the docs drift gate, ``--list-rules``) stays
dependency-free like the rest of the package.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional

from . import irrules  # noqa: F401 — registers the IR00x analyzers
from .core import (
    IR_RULES,
    Config,
    Finding,
    LintResult,
    ModuleInfo,
    apply_baseline,
    default_config,
)

# --------------------------------------------------------------------------
# entry-point registry
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class KernelSpec:
    """One abstract trace of one entry point: positional input
    shapes/dtypes (manifest ``in_shapes`` form: dtype as string) plus the
    static kwargs. ``group`` optionally regroups the flat struct list
    into the kernel's pytree signature (tuple-valued args)."""

    variant: str
    in_shapes: tuple  # ((shape tuple, dtype str), ...)
    statics: dict = field(default_factory=dict)
    group: Optional[Callable] = None


@dataclass(frozen=True)
class KernelEntry:
    """One exported kernel family: where it lives, how prewarm knows it,
    and how to build its representative spec grid. ``make_specs`` is a
    thunk so the registry itself imports nothing heavy — bucket constants
    (K_PREV, cap rounding) are read LIVE from the engine at trace time,
    never mirrored."""

    name: str
    family: str  # "ops" | "masks" | "scheduler"
    module: str
    attr: str
    path: str  # repo-relative source file (findings anchor here)
    make_specs: Callable[[], list]
    manifest_kernel: Optional[str] = None  # name in the prewarm manifest
    #: delta-safety declaration: do the kernel's outputs couple batch
    #: rows? Mandatory (IR006 fails a missing one) and PROVEN against
    #: the jaxpr by the dep tier — see tools/graftlint/dep.py
    row_coupled: Optional[bool] = None
    #: flat in_shapes positions whose leading axis is the batch-row axis
    row_args: tuple = ()
    #: positions carrying plane-wide state (cross-row by construction —
    #: the first_fit_group avail channel); a declared-coupled kernel may
    #: verify via proven dependence on these instead of a row coupler
    plane_args: tuple = ()
    #: repo-relative modules (beyond ``path``) whose change must
    #: re-trace this entry under ``--changed-only`` — the spec builders'
    #: and kernel bodies' import graph, kept explicit
    spec_deps: tuple = ()


# -- spec builders: the representative bucket grid --------------------------
#
# Dimensions are deliberately SMALL (abstract tracing cost is shape-
# independent, so nothing is gained by production extents) but bucket-
# SHAPED: pow2 caps, the engine's floor quanta, both wide/narrow and
# fast/sorted divide variants, byte and word wires — the statics axes are
# what mint distinct traces in production, so they are what the grid must
# cover.

_B, _C, _R, _U, _G, _P = 8, 16, 3, 4, 2, 3


def _fast_tuples(c: int) -> tuple:
    """(with_idx, no_idx) packed-dispense static tuples valid for ``c``
    clusters — the same (w_bits, l_bits, k_top, div_f32, with_idx) shape
    scheduler.core.kernel_variant emits."""
    i_bits = max(1, (c - 1).bit_length())
    l_bits = 8
    return (
        (31 - l_bits - i_bits, l_bits, 8, True, True),
        (31 - l_bits, l_bits, 8, False, False),
    )


def _specs_divide() -> list:
    fast_idx, fast_noidx = _fast_tuples(_C)
    row = (
        ((_B,), "int32"), ((_B,), "int32"), ((_B, _C), "bool"),
        ((_B, _C), "int32"), ((_B, _C), "int32"), ((_B, _C), "int32"),
        ((_B,), "bool"),
    )
    return [
        KernelSpec("wide-sorted", row,
                   {"has_aggregated": True, "wide": True, "fast": None}),
        KernelSpec("narrow-fast", row,
                   {"has_aggregated": True, "wide": False,
                    "fast": fast_idx}),
        KernelSpec("narrow-fast-noidx", row,
                   {"has_aggregated": False, "wide": False,
                    "fast": fast_noidx}),
    ]


def _specs_take_by_weight() -> list:
    vec = (((), "int32"), ((_C,), "int32"), ((_C,), "int32"),
           ((_C,), "int32"))
    return [
        KernelSpec("wide", vec, {"wide": True}),
        KernelSpec("narrow", vec, {"wide": False}),
    ]


def _specs_take_by_weight_fast() -> list:
    fast_idx, fast_noidx = _fast_tuples(_C)
    vec = (((), "int32"), ((_C,), "int32"), ((_C,), "int32"),
           ((_C,), "int32"))

    def statics(fast, sites):
        w_bits, l_bits, k_top, div_f32, with_idx = fast
        return {"w_bits": w_bits, "l_bits": l_bits, "k_top": k_top,
                "div_f32": div_f32, "with_idx": with_idx,
                "return_sites": sites}

    return [
        KernelSpec("packed-idx", vec, statics(fast_idx, False)),
        KernelSpec("packed-idx-sites", vec, statics(fast_idx, True)),
        KernelSpec("packed-noidx", vec, statics(fast_noidx, False)),
    ]


def _specs_take_by_weight_batch() -> list:
    batch = (((_B,), "int32"), ((_B, _C), "int32"), ((_B, _C), "int32"),
             ((_B, _C), "int32"))
    return [
        KernelSpec("wide", batch, {"wide": True}),
        KernelSpec("narrow", batch, {"wide": False}),
    ]


def _specs_general_estimate() -> list:
    return [KernelSpec(
        "base", (((_C, _R), "int64"), ((_B, _R), "int64")),
    )]


def _specs_general_estimate_interned() -> list:
    return [KernelSpec(
        "base",
        (((_C, _R), "int64"), ((_U, _R), "int64"), ((_B,), "int32")),
    )]


def _specs_gather_profile_rows() -> list:
    return [KernelSpec("base", (((_U, _C), "int32"), ((_B,), "int32")))]


def _group_merge(structs):
    return structs[0], tuple(structs[1:])


def _specs_merge_estimates() -> list:
    return [KernelSpec(
        "two-estimators",
        (((_B,), "int32"), ((_B, _C), "int32"), ((_B, _C), "int32")),
        group=_group_merge,
    )]


def _specs_quota_admit() -> list:
    # B-pow2 wave rows x pow2 namespace rows — the engine's admission
    # padding shape (scheduler.core._quota_admission)
    return [
        KernelSpec(
            "base",
            (((_B,), "int32"), ((_B, _R), "int64"), ((_U, _R), "int64")),
        ),
        KernelSpec(
            "wide-wave",
            (
                ((4 * _B,), "int32"),
                ((4 * _B, _R), "int64"),
                ((2 * _U, _R), "int64"),
            ),
        ),
    ]


def _specs_quota_cluster_caps() -> list:
    return [
        KernelSpec(
            "base",
            (
                ((_U, _C, _R), "int64"),
                ((_B,), "int32"),
                ((_B, _R), "int64"),
            ),
        ),
    ]


def _specs_explain_pass() -> list:
    # the engine's capture padding shape: pow2 binding rows x the
    # snapshot's cluster columns, k clamped to C (ops.explain.topk_width)
    row = (
        ((_B, _C), "bool"), ((_B, _C), "bool"), ((_B, _C), "bool"),
        ((_B, _C), "bool"), ((_B, _C), "int32"), ((_B, _C), "int32"),
        ((_B,), "bool"), ((_B,), "bool"), ((_B,), "int32"),
        ((_B, _C), "int32"), ((_B, _C), "int32"), ((_B, _C), "bool"),
    )
    return [
        KernelSpec("base", row, {"k": 4, "mesh": None, "shard_c": False}),
        KernelSpec("wide-wave", tuple(
            ((4 * _B,) + s[0][1:], s[1]) for s in row
        ), {"k": 8, "mesh": None, "shard_c": False}),
        # sharded grid: the provenance dispatch under a 2-device ("b")
        # mesh — IR001-IR005 run over the PARTITIONED jaxpr, the fleet
        # kernels' contract (ISSUE 9 / test_sharded_specs_cover_*)
        KernelSpec("sharded-b2", row,
                   {"k": 4, "mesh": _MESH2, "shard_c": False}),
    ]


def _specs_preempt_select() -> list:
    # the engine's preemption padding shape: pow2 combined demander+
    # victim rows x cluster columns x resource dims
    # (scheduler.core._preempt_pass)
    row = (
        ((_B,), "int32"), ((_B, _R), "int64"), ((_B, _R), "int64"),
        ((_B,), "bool"), ((_B,), "int32"), ((_B, _C), "int32"),
        ((_B, _R), "int64"),
    )
    return [
        KernelSpec("base", row, {"mesh": None}),
        KernelSpec("wide-wave", tuple(
            ((4 * _B,) + s[0][1:], s[1]) for s in row
        ), {"mesh": None}),
        # sharded grid: the victim selection under a 2-device ("b")
        # mesh — IR001-IR005 run over the PARTITIONED jaxpr (the global
        # sort/cumsum replication guard is audited, not assumed)
        KernelSpec("sharded-b2", row, {"mesh": _MESH2}),
    ]


def _specs_masks_contains_all() -> list:
    return [KernelSpec(
        "base", (((_C, 2), "uint32"), ((2,), "uint32")),
    )]


def _specs_masks_intersects() -> list:
    return [KernelSpec(
        "base", (((_C, 2), "uint32"), ((2,), "uint32")),
    )]


# -- fleet kernels: shapes mirror FleetTable's device layout ----------------


def _fleet_dims() -> dict:
    from karmada_tpu.scheduler.fleet import K_EVICT, K_PREV, T_CAP

    c = _C
    return {
        "c": c, "w8": (c + 7) // 8, "cap": 256, "chunk": 256,
        "n_pad": 256, "k_prev": K_PREV, "k_evict": K_EVICT, "t_cap": T_CAP,
    }


def _fleet_tables(d: dict) -> list:
    return [
        ((_U, 2 * d["w8"]), "uint8"),  # cp_bits
        ((_U, d["c"]), "int32"),  # cp_static
        ((_G, d["w8"]), "uint8"),  # gvk_bits
        ((_P, d["c"]), "int32"),  # prof_table
        ((d["c"],), "bool"),  # incomplete_en
    ]


def _fleet_state(d: dict) -> list:
    cap = d["cap"]
    return (
        [((cap,), "int32")] * 5  # cp_idx gvk_idx prof_idx replicas strategy
        + [((cap,), "bool")]  # fresh
        + [((cap, d["k_prev"]), "int32")] * 2  # prev_sites prev_counts
        + [((cap, d["k_evict"]), "int32")]  # evict_sites
        + [((cap, d["w8"]), "uint8")]  # sel_bits
    )


#: canonical 2-device mesh shape for the sharded spec variants (the
#: serialized form the trace manifest also records; trace_spec builds the
#: live mesh over the forced host devices at trace time)
_MESH2 = (("b", 2), ("c", 1))


def _specs_fleet_pass() -> list:
    from karmada_tpu.scheduler.fleet import D_FLOOR

    d = _fleet_dims()
    fast_idx, _ = _fast_tuples(d["c"])

    def spec(variant, **statics):
        base = dict(
            chunk=d["chunk"], n_chunks=1, wide=True, fast=None,
            has_aggregated=True, all_rows=True, m_cap=d["n_pad"],
            d_cap=0, mesh=None, shard_c=False,
        )
        base.update(statics)
        shapes = tuple(
            _fleet_tables(d) + [((d["n_pad"],), "int32")] + _fleet_state(d)
            + [((d["cap"], d["c"]), "uint8"), ((d["cap"],), "int32")]
        )
        return KernelSpec(variant, shapes, base)

    return [
        spec("wide-allrows"),
        spec("narrow-fast-delta", wide=False, fast=fast_idx,
             d_cap=D_FLOOR, all_rows=False),
        # sharded grid: the same program under a 2-device ("b") mesh —
        # trace_spec materializes the shape into a live Mesh, so IR001-
        # IR005 run over the PARTITIONED executable's jaxpr, and the
        # donation audit proves the dense residents still alias when
        # row-sharded
        spec("sharded-b2", mesh=_MESH2),
    ]


def _specs_fleet_entries() -> list:
    from karmada_tpu.scheduler.fleet import _cap_round

    d = _fleet_dims()
    shapes = (
        ((d["cap"], d["c"]), "uint8"), ((2048,), "int32"),
    )
    base = dict(chunk=256, n_chunks=8, k_out=8, e_cap=_cap_round(1))
    return [
        KernelSpec("byte-pack21", shapes,
                   {**base, "byte_wire": True, "pack21": True}),
        KernelSpec("word-wire", shapes,
                   {**base, "byte_wire": False, "pack21": False}),
        # sharded grid: phase B over a row-sharded dense resident (the
        # mesh engines' form — gathers cross shards, scans replicate)
        KernelSpec("sharded-b2", shapes,
                   {**base, "byte_wire": True, "pack21": True,
                    "mesh": _MESH2}),
    ]


def _specs_fleet_bits() -> list:
    d = _fleet_dims()
    shapes = tuple(
        _fleet_tables(d) + [((d["n_pad"],), "int32")] + _fleet_state(d)
    )
    return [KernelSpec("base", shapes, {"chunk": d["chunk"], "n_chunks": 1})]


def _specs_fleet_select() -> list:
    from karmada_tpu.scheduler.select import N_PARAMS, R_CAP

    d = _fleet_dims()
    state = _fleet_state(d)
    subsets = 1 << R_CAP
    shapes = tuple(
        _fleet_tables(d)
        + [((_U, N_PARAMS), "int32"), ((d["c"],), "int32"),  # sp_params, region_of
           ((subsets,), "int32"), ((subsets, subsets), "float32")]
        + [((d["n_pad"],), "int32")]
        # the state it reads: cp_idx gvk_idx prof_idx replicas, prev_sites
        # prev_counts, evict_sites, sel_bits (neither strategy nor fresh)
        + state[:4] + state[6:]
    )
    return [KernelSpec("base", shapes, {"chunk": d["chunk"], "n_chunks": 1})]


def _specs_fleet_terms() -> list:
    d = _fleet_dims()
    shapes = tuple(
        _fleet_tables(d)
        + [((d["n_pad"],), "int32"),  # the multi-term rows
           ((d["cap"], d["t_cap"]), "int32"),  # term_slots
           ((d["cap"],), "uint8")]  # term_sel
        + _fleet_state(d)[:-1]  # the state it reads: all but sel_bits
    )
    return [KernelSpec("base", shapes, {"chunk": d["chunk"], "n_chunks": 1})]


def _specs_fleet_quota() -> list:
    d = _fleet_dims()
    cap = d["cap"]
    shapes = (
        ((_P, _R), "int64"),  # prof_reqs
        ((d["n_pad"],), "int32"),  # the batch's rows, presented order
        ((cap,), "int32"), ((cap,), "int32"),  # ns_idx prev_rest
        ((cap,), "int32"), ((cap,), "int32"),  # prof_idx replicas
        ((cap, d["k_prev"]), "int32"),  # prev_counts
    )
    return [KernelSpec("base", shapes)]


def _specs_gather_meta() -> list:
    d = _fleet_dims()
    return [KernelSpec(
        "base", (((d["cap"],), "int32"), ((d["n_pad"],), "int32")),
    )]


def _group_scatter(structs):
    # the state fields and term_slots, the rows, a value for each
    n = (len(structs) - 1) // 2
    return tuple(structs[:n]), structs[n], tuple(structs[n + 1:])


def _specs_scatter_rows() -> list:
    d = _fleet_dims()
    state = _fleet_state(d) + [((d["cap"], d["t_cap"]), "int32")]
    rows = 16
    vals = [((rows,) + tuple(s[0][1:]), s[1]) for s in state]
    return [KernelSpec(
        "base",
        tuple(state + [((rows,), "int64")] + vals),
        group=_group_scatter,
    )]


def _specs_first_fit_group() -> list:
    t = 3
    return [KernelSpec(
        "base",
        (
            ((_B, t, _C), "bool"), ((_B,), "int32"), ((_B, _C), "int64"),
            ((_B,), "int64"), ((_B, _C), "int64"), ((_B,), "bool"),
            ((_B,), "bool"),
        ),
    )]


#: fleet.py's full ops-module import surface (divide pulls dispense;
#: fleet composes every family) — the --changed-only re-trace closure
_FLEET_DEPS = (
    "karmada_tpu/ops/divide.py", "karmada_tpu/ops/dispense.py",
    "karmada_tpu/ops/estimate.py", "karmada_tpu/ops/explain.py",
    "karmada_tpu/ops/preempt.py", "karmada_tpu/ops/quota.py",
)


def _entry(name, family, module, attr, path, make_specs, manifest=None,
           row_coupled=None, row_args=(), plane_args=(), spec_deps=()):
    return KernelEntry(
        name=name, family=family, module=module, attr=attr, path=path,
        make_specs=make_specs, manifest_kernel=manifest,
        row_coupled=row_coupled, row_args=tuple(row_args),
        plane_args=tuple(plane_args), spec_deps=tuple(spec_deps),
    )


#: THE registry: every exported kernel entry point, AST-light (spec
#: builders import the engine lazily). The docs drift gate
#: (tools/docs_from_bench.py check_ir_registry) fails loudly when an
#: ops/ export is missing here; IR004 fails when a fleet kernel is
#: missing from any of FLEET_KERNELS / prewarm._KERNELS / this table.
ENTRY_POINTS: dict = {
    e.name: e
    for e in (
        # ops/ — the dispense/divide/estimate/masks families. Every
        # entry declares ``row_coupled`` (the delta-safety contract,
        # IR006-checked) and which flat input positions carry the batch
        # row axis; the unbatched dispense kernels have no row axis at
        # all, so their independence is trivial (row_args=()).
        _entry("divide_replicas", "ops", "karmada_tpu.ops.divide",
               "divide_replicas", "karmada_tpu/ops/divide.py",
               _specs_divide, row_coupled=False,
               row_args=(0, 1, 2, 3, 4, 5, 6),
               spec_deps=("karmada_tpu/ops/dispense.py",)),
        _entry("take_by_weight", "ops", "karmada_tpu.ops.dispense",
               "take_by_weight", "karmada_tpu/ops/dispense.py",
               _specs_take_by_weight, row_coupled=False),
        _entry("take_by_weight_fast", "ops", "karmada_tpu.ops.dispense",
               "take_by_weight_fast", "karmada_tpu/ops/dispense.py",
               _specs_take_by_weight_fast, row_coupled=False),
        _entry("take_by_weight_batch", "ops", "karmada_tpu.ops.dispense",
               "take_by_weight_batch", "karmada_tpu/ops/dispense.py",
               _specs_take_by_weight_batch, row_coupled=False,
               row_args=(0, 1, 2, 3)),
        _entry("general_estimate", "ops", "karmada_tpu.ops.estimate",
               "general_estimate", "karmada_tpu/ops/estimate.py",
               _specs_general_estimate, row_coupled=False,
               row_args=(1,)),
        _entry("general_estimate_interned", "ops",
               "karmada_tpu.ops.estimate", "general_estimate_interned",
               "karmada_tpu/ops/estimate.py",
               _specs_general_estimate_interned, row_coupled=False,
               row_args=(2,)),
        _entry("gather_profile_rows", "ops", "karmada_tpu.ops.estimate",
               "gather_profile_rows", "karmada_tpu/ops/estimate.py",
               _specs_gather_profile_rows, row_coupled=False,
               row_args=(1,)),
        _entry("merge_estimates", "ops", "karmada_tpu.ops.estimate",
               "merge_estimates", "karmada_tpu/ops/estimate.py",
               _specs_merge_estimates, row_coupled=False,
               row_args=(0, 1, 2)),
        # quota family: dispatched engine-side (TensorScheduler) but
        # manifest-recorded like the fleet solve family, so prewarm can
        # replay admission traces at boot (IR004 keeps the three
        # registries — FLEET_KERNELS / prewarm._KERNELS / here — equal)
        _entry("quota_admit", "ops", "karmada_tpu.ops.quota",
               "quota_admit", "karmada_tpu/ops/quota.py",
               _specs_quota_admit, manifest="quota_admit",
               row_coupled=True, row_args=(0, 1), plane_args=(2,)),
        _entry("quota_cluster_caps", "ops", "karmada_tpu.ops.quota",
               "quota_cluster_caps", "karmada_tpu/ops/quota.py",
               _specs_quota_cluster_caps, manifest="quota_cluster_caps",
               row_coupled=False, row_args=(1, 2)),
        # provenance family: the armed-only per-pass explain dispatch
        # (engine-side like the quota kernels, manifest-recorded, with a
        # sharded-b2 variant so the partitioned form is audited too)
        _entry("explain_pass", "ops", "karmada_tpu.ops.explain",
               "explain_pass", "karmada_tpu/ops/explain.py",
               _specs_explain_pass, manifest="explain_pass",
               row_coupled=False,
               row_args=(0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11)),
        # scarcity family: the armed-only plane-wide victim selection
        # (engine-side like quota/explain, manifest-recorded, with a
        # sharded-b2 variant auditing the partitioned jaxpr)
        _entry("preempt_select", "ops", "karmada_tpu.ops.preempt",
               "preempt_select", "karmada_tpu/ops/preempt.py",
               _specs_preempt_select, manifest="preempt_select",
               row_coupled=True, row_args=(0, 1, 2, 3, 4, 5, 6),
               spec_deps=("karmada_tpu/ops/quota.py",)),
        _entry("masks.contains_all", "masks", "karmada_tpu.ops.masks",
               "contains_all", "karmada_tpu/ops/masks.py",
               _specs_masks_contains_all, row_coupled=False,
               row_args=(0,)),
        _entry("masks.intersects", "masks", "karmada_tpu.ops.masks",
               "intersects", "karmada_tpu/ops/masks.py",
               _specs_masks_intersects, row_coupled=False,
               row_args=(0,)),
        # cohort selection: row-wise over B but coupled THROUGH the
        # plane-merged availability input (plane_args) — a declared-
        # coupled kernel IR006 verifies via the plane channel
        _entry("masks.first_fit_group", "masks", "karmada_tpu.ops.masks",
               "first_fit_group", "karmada_tpu/ops/masks.py",
               _specs_first_fit_group, row_coupled=True,
               row_args=(0, 1, 3, 4, 5, 6), plane_args=(2,)),
        # scheduler fleet kernels (manifest-recorded solve family + the
        # ledger-only utility kernels). The row space is the resident
        # cap axis; the pass/entries kernels compact globally
        # (declared coupled), bits/meta are per-row but scan-windowed,
        # so the analyzer returns 'unproven' — declared honestly, not
        # delta_safe (see DEVELOPMENT.md, delta-safe kernel contract).
        _entry("fleet_pass", "scheduler", "karmada_tpu.scheduler.fleet",
               "_fleet_pass", "karmada_tpu/scheduler/fleet.py",
               _specs_fleet_pass, manifest="fleet_pass",
               row_coupled=True,
               row_args=(6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16),
               spec_deps=_FLEET_DEPS),
        _entry("fleet_entries", "scheduler", "karmada_tpu.scheduler.fleet",
               "_fleet_entries", "karmada_tpu/scheduler/fleet.py",
               _specs_fleet_entries, manifest="fleet_entries",
               row_coupled=True, row_args=(0,), spec_deps=_FLEET_DEPS),
        _entry("fleet_bits", "scheduler", "karmada_tpu.scheduler.fleet",
               "_fleet_bits", "karmada_tpu/scheduler/fleet.py",
               _specs_fleet_bits, manifest="fleet_bits",
               row_coupled=False,
               row_args=(6, 7, 8, 9, 10, 11, 12, 13, 14),
               spec_deps=_FLEET_DEPS),
        # the Select stage: per-row math, but its writes land at ``rows``
        # (a scatter into the resident sel_bits) and its two counts sum
        # over every row: declared coupled
        _entry("fleet_select", "scheduler", "karmada_tpu.scheduler.fleet",
               "_fleet_select", "karmada_tpu/scheduler/fleet.py",
               _specs_fleet_select, manifest="fleet_select",
               row_coupled=True,
               row_args=(10, 11, 12, 13, 14, 15, 16, 17),
               spec_deps=_FLEET_DEPS + ("karmada_tpu/scheduler/select.py",)),
        # the term kernel: per-row math too, and like the Select stage its
        # writes land at ``rows`` (scatters into the resident cp_idx and
        # term_sel) and its two counts sum over every row
        _entry("fleet_terms", "scheduler", "karmada_tpu.scheduler.fleet",
               "_fleet_terms", "karmada_tpu/scheduler/fleet.py",
               _specs_fleet_terms, manifest="fleet_terms",
               row_coupled=True,
               row_args=(6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16),
               spec_deps=_FLEET_DEPS + ("karmada_tpu/ops/masks.py",)),
        # what quota_admit takes of a batch, from the row state: rows
        # gathered into presented order and one count over them all
        # (declared coupled)
        _entry("fleet_quota", "scheduler", "karmada_tpu.scheduler.fleet",
               "_fleet_quota", "karmada_tpu/scheduler/fleet.py",
               _specs_fleet_quota, manifest="fleet_quota",
               row_coupled=True, row_args=(2, 3, 4, 5, 6),
               spec_deps=_FLEET_DEPS),
        _entry("gather_meta", "scheduler", "karmada_tpu.scheduler.fleet",
               "_gather_meta", "karmada_tpu/scheduler/fleet.py",
               _specs_gather_meta, row_coupled=False, row_args=(0,),
               spec_deps=_FLEET_DEPS),
        _entry("scatter_rows", "scheduler", "karmada_tpu.scheduler.fleet",
               "_scatter_rows", "karmada_tpu/scheduler/fleet.py",
               _specs_scatter_rows, row_coupled=True,
               row_args=tuple(range(17)), spec_deps=_FLEET_DEPS),
    )
}


def entries_for_changed(paths, registry: Optional[dict] = None) -> dict:
    """The ``--changed-only`` scope for the IR/dep tiers: entries whose
    source file or declared ``spec_deps`` intersect the changed set.
    Like GL003's precedent, full-scope-only negatives (registry
    coverage, manifest presence) stay off scoped runs — run_ir/run_dep
    see ``entries is not None`` and drop them."""
    changed = {str(p).replace("\\", "/") for p in paths}
    registry = ENTRY_POINTS if registry is None else registry
    return {
        name: e
        for name, e in registry.items()
        if e.path in changed or set(e.spec_deps) & changed
    }


def exported_ops_kernels(root: Path) -> set:
    """Kernel function names ``karmada_tpu/ops/__init__.py`` re-exports
    (pure AST: lowercase ``from .submodule import name`` bindings —
    constants are UPPER and result types CamelCase by repo convention).
    The docs drift gate compares this against the registry."""
    tree = ast.parse(
        (Path(root) / "karmada_tpu" / "ops" / "__init__.py").read_text()
    )
    out: set = set()
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.ImportFrom)
            and node.level == 1
            and node.module
        ):
            continue
        for a in node.names:
            name = a.asname or a.name
            if name.islower() and not name.startswith("_"):
                out.add(name)
    return out


def ops_registry_drift(root: Optional[Path] = None) -> tuple:
    """(exported-but-unregistered, registered-but-unexported) kernel
    names — both must be empty; tools/docs_from_bench.py fails loudly on
    either (the same drift-guard pattern as the env-flag table)."""
    config = default_config(root)
    exported = exported_ops_kernels(config.root)
    registered = {
        e.name for e in ENTRY_POINTS.values() if e.family == "ops"
    }
    return sorted(exported - registered), sorted(registered - exported)


# --------------------------------------------------------------------------
# tracing
# --------------------------------------------------------------------------


def _import_jax():
    # the auditor must never grab a TPU: default to CPU before the first
    # jax import (a caller that already imported jax keeps its platform).
    # The sharded entry-point specs trace under a >=2-device mesh, so the
    # forced-host-device flag is ensured BEFORE the first backend init —
    # a caller that already initialized a 1-device backend surfaces the
    # mesh-build failure as an IR004 trace failure (loud, not skipped).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # inline (NOT parallel.mesh.ensure_host_devices): importing any
    # karmada_tpu module pulls jax, and XLA_FLAGS is captured at jax
    # IMPORT — the flag must be in the env before that first import
    import re as _re

    flags = os.environ.get("XLA_FLAGS", "")
    m = _re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if not m or int(m.group(1)) < 2:
        opt = "--xla_force_host_platform_device_count=2"
        flags = flags.replace(m.group(0), opt) if m else f"{flags} {opt}"
        os.environ["XLA_FLAGS"] = flags.strip()
    import jax

    return jax


@dataclass
class TracedKernel:
    """One abstract trace: the jaxpr plus the finding anchor."""

    entry: KernelEntry
    spec: KernelSpec
    closed_jaxpr: object
    line: int = 1

    @property
    def label(self) -> str:
        return f"{self.entry.name}[{self.spec.variant}]"

    def finding(self, rule_id: str, message: str, detail: str) -> Finding:
        return Finding(
            rule=rule_id, path=self.entry.path, line=self.line, col=1,
            message=message, anchor=self.entry.attr, detail=detail,
            anchor_line=self.line,
        )


def resolve_kernel(entry: KernelEntry):
    import importlib

    return getattr(importlib.import_module(entry.module), entry.attr)


def trace_spec(entry: KernelEntry, spec: KernelSpec, line: int = 1):
    """Abstractly trace one spec: no compile, no execution, no data."""
    jax = _import_jax()
    import numpy as np

    fn = resolve_kernel(entry)
    structs = [
        jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))
        for shape, dtype in spec.in_shapes
    ]
    args = spec.group(structs) if spec.group else tuple(structs)
    statics = dict(spec.statics)
    # a sharded spec (registry variant or meshed manifest record) carries
    # its mesh as the canonical SHAPE — build the live Mesh over this
    # process's devices the same way prewarm replay does, so the audited
    # jaxpr is the partitioned program the serving path dispatches
    from karmada_tpu.parallel.mesh import materialize_mesh_statics

    statics = materialize_mesh_statics(statics)
    closed = jax.make_jaxpr(lambda *a: fn(*a, **statics))(*args)
    return TracedKernel(
        entry=entry, spec=spec, closed_jaxpr=closed, line=line,
    )


# --------------------------------------------------------------------------
# manifest fidelity (IR004 inputs)
# --------------------------------------------------------------------------


@dataclass
class ManifestResult:
    index: int
    kernel: str
    error: Optional[str] = None
    reason: str = "ok"
    traced: Optional[TracedKernel] = None


def spec_from_record(record: dict, variant: str) -> KernelSpec:
    """A manifest record IS a kernel spec: same in_shapes form, statics
    through prewarm's own JSON inverse (so tuple restoration cannot
    diverge from what replay() would execute)."""
    from karmada_tpu.scheduler.prewarm import _statics_from_json

    return KernelSpec(
        variant=variant,
        in_shapes=tuple(
            (tuple(int(d) for d in shape), dtype)
            for shape, dtype in record["in_shapes"]
        ),
        statics=_statics_from_json(record["statics"]),
    )


def record_canon(record: dict, spec: KernelSpec) -> tuple:
    """(original canon, canon of the spec re-serialized through prewarm's
    own writers) — byte-identical means the save/load/replay cycle is
    lossless for this record."""
    import numpy as np

    from karmada_tpu.scheduler.prewarm import _canon, _listify

    rebuilt = {
        "kernel": record["kernel"],
        "in_shapes": [
            [list(shape), str(np.dtype(dtype))]
            for shape, dtype in spec.in_shapes
        ],
        "statics": {k: _listify(v) for k, v in spec.statics.items()},
    }
    return _canon(record), _canon(rebuilt)


def check_manifest(path: str, ctx: "IRContext") -> None:
    """Audit one trace manifest: every record must resolve to a known
    kernel family, re-trace under its recorded shapes/statics, and
    round-trip to a byte-identical content signature. Successfully traced
    records join the IR001/2/3/5 audit set.

    The file is parsed RAW, not through ``prewarm.TraceManifest`` — the
    loader silently drops unreadable files and records whose kernel is
    missing from ``_KERNELS``, which is exactly the drift this audit
    exists to catch (a renamed fleet kernel would make every old record
    vanish and the audit report clean). An explicitly-audited manifest
    that is unreadable or empty is itself a finding: the operator asked
    to prove coverage, and there is none."""
    import json

    by_manifest = {
        e.manifest_kernel: e
        for e in ctx.entries.values()
        if e.manifest_kernel
    }
    try:
        rel = Path(path).resolve().relative_to(
            ctx.config.root.resolve()
        ).as_posix()
    except ValueError:
        rel = Path(path).as_posix()
    ctx.manifest_rel = rel
    try:
        data = json.loads(Path(path).read_text())
        records = data.get("records", [])
        if not isinstance(records, list):
            raise ValueError("'records' is not a list")
    except (OSError, ValueError) as exc:
        ctx.manifest_results.append(ManifestResult(
            index=-1, kernel="<manifest>",
            error=f"manifest unreadable ({exc})", reason="unreadable",
        ))
        return
    if not records:
        ctx.manifest_results.append(ManifestResult(
            index=-1, kernel="<manifest>",
            error=("manifest holds zero records — prewarm would cover "
                   "nothing; re-record it (run a warm pass with recording "
                   "on) or drop --manifest"),
            reason="empty",
        ))
        return
    for i, record in enumerate(records):
        kernel = (
            record.get("kernel", "?") if isinstance(record, dict) else "?"
        )
        res = ManifestResult(index=i, kernel=str(kernel))
        ctx.manifest_results.append(res)
        if not isinstance(record, dict) or not all(
            k in record for k in ("kernel", "in_shapes", "statics")
        ):
            res.error = (
                "malformed record (kernel/in_shapes/statics required)"
            )
            res.reason = "malformed"
            continue
        entry = by_manifest.get(kernel)
        if entry is None:
            res.error = (
                "unknown kernel family (not in the IR entry-point registry)"
            )
            res.reason = "unknown-kernel"
            continue
        try:
            spec = spec_from_record(record, f"manifest[{i}]")
            res.traced = trace_spec(entry, spec, ctx.entry_line(entry))
        except Exception as exc:  # noqa: BLE001 — each record is audited
            # independently; one stale record must not mask the rest
            res.error = f"re-trace failed ({exc!r})"
            res.reason = "trace-failed"
            continue
        original, rebuilt = record_canon(record, spec)
        if original != rebuilt:
            res.error = (
                "recorded signature does not round-trip byte-identically "
                f"({original} != {rebuilt})"
            )
            res.reason = "canon-drift"
            continue
        ctx.traced.append(res.traced)


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------


class IRContext:
    """Cross-rule state of one IR run (the IR analogue of LintContext)."""

    def __init__(self, config: Config, entries: dict):
        self.config = config
        self.entries = entries
        self.traced: list = []
        self.trace_failures: list = []  # (entry, spec, err-str)
        self.registry_coverage: Optional[dict] = None
        self.manifest_rel: str = ""
        self.manifest_results: list = []
        self.const_bytes_threshold = irrules.CONST_BYTES_THRESHOLD
        self._def_lines: dict = {}  # path -> {funcname: lineno}
        self._modinfos: dict = {}  # path -> Optional[ModuleInfo]

    def entry_line(self, entry: KernelEntry) -> int:
        lines = self._def_lines.get(entry.path)
        if lines is None:
            lines = {}
            source = self.config.root / entry.path
            if source.exists():
                for node in ast.walk(ast.parse(source.read_text())):
                    if isinstance(
                        node, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        lines.setdefault(node.name, node.lineno)
            self._def_lines[entry.path] = lines
        return lines.get(entry.attr, 1)

    def modinfo(self, rel: str) -> Optional[ModuleInfo]:
        """Parsed module for suppression lookup (None for paths outside
        the tree, e.g. a manifest file)."""
        if rel not in self._modinfos:
            source = self.config.root / rel
            info = None
            if source.exists() and source.suffix == ".py":
                info = ModuleInfo.parse(source, rel, set())
            self._modinfos[rel] = info
        return self._modinfos[rel]


def _registry_coverage(entries: dict) -> dict:
    """The three surfaces a fleet kernel must be registered on (IR004)."""
    from karmada_tpu.scheduler import fleet, prewarm

    return {
        "fleet": set(fleet.FLEET_KERNELS),
        "prewarm": set(prewarm._KERNELS),
        "ir": {
            e.manifest_kernel
            for e in entries.values()
            if e.manifest_kernel
        },
    }


def run_ir(
    families=None,
    *,
    root=None,
    baseline="auto",
    manifest: Optional[str] = None,
    entries: Optional[dict] = None,
    const_bytes_threshold: Optional[int] = None,
) -> LintResult:
    """One-call API behind ``--ir`` and the tier-1 gate. ``families``
    filters the registry by entry name (None = everything); ``entries``
    substitutes the registry wholesale (the seeded-mutant fixtures);
    ``manifest`` additionally audits a trace-manifest file (IR004)."""
    config = default_config(root)
    registry = dict(entries) if entries is not None else dict(ENTRY_POINTS)
    full_run = entries is None and not families
    if families:
        unknown = sorted(set(families) - set(registry))
        if unknown:
            raise KeyError(
                f"unknown kernel families {unknown}; known: "
                f"{sorted(registry)}"
            )
        registry = {name: registry[name] for name in families}

    ctx = IRContext(config, registry)
    if const_bytes_threshold is not None:
        ctx.const_bytes_threshold = const_bytes_threshold
    for entry in registry.values():
        line = ctx.entry_line(entry)
        for spec in entry.make_specs():
            try:
                ctx.traced.append(trace_spec(entry, spec, line))
            except Exception as exc:  # noqa: BLE001 — a spec that fails
                # to trace is ITSELF the IR004 finding, never an abort
                ctx.trace_failures.append((entry, spec, repr(exc)))
    if full_run:
        ctx.registry_coverage = _registry_coverage(registry)
    if manifest:
        check_manifest(manifest, ctx)

    raw: list = []
    suppressed = 0
    seen: set = set()
    for r in IR_RULES.values():
        found: list = []
        for t in ctx.traced:
            found.extend(r.check(t, ctx))
        found.extend(r.finalize(ctx))
        for f in found:
            key = (f.identity, f.line)
            if key in seen:  # variants of one entry repeat one defect
                continue
            seen.add(key)
            mod = ctx.modinfo(f.path)
            if mod is not None and mod.suppressed(
                f.rule, f.line, f.anchor_line
            ):
                suppressed += 1
            else:
                raw.append(f)

    baseline_path = None
    if baseline == "auto":
        baseline_path = config.root / config.baseline_path
    elif baseline:
        baseline_path = config.root / baseline
    checked = len(ctx.traced) + len(ctx.trace_failures)
    return apply_baseline(
        raw, baseline=baseline_path, checked_files=checked,
        suppressed=suppressed,
    )
