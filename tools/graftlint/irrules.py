"""The IR00x analyzers: jaxpr-level invariants over traced kernels.

| id    | invariant                                                        |
|-------|------------------------------------------------------------------|
| IR001 | no float64 / weak-float promotion anywhere in a kernel jaxpr     |
| IR002 | no host round-trip primitives (callbacks) inside a kernel        |
| IR003 | no large closed-over constants (captured arrays bake snapshot    |
|       | data into the trace -> per-snapshot recompiles)                  |
| IR004 | trace-manifest fidelity: records re-trace to their recorded      |
|       | signature; the fleet-kernel registries cannot drift apart        |
| IR005 | donation audit: buffers declared donated are actually consumed   |

Each rule walks a ``TracedKernel`` (see ir.py) — an entry point abstractly
traced via ``jax.make_jaxpr`` over one bucket of the representative grid.
The walk is duck-typed over jaxpr objects (``.eqns``, ``.aval``,
``.primitive.name``) so this module never imports jax: like the AST tier,
listing rules and computing registries must stay dependency-free; only the
TRACING step (ir.py) needs a live jax.
"""

from __future__ import annotations

import math
from typing import Iterator

from .core import Finding, Rule, rule

# -- jaxpr walking (duck-typed; no jax import) ------------------------------


def _subjaxprs(params: dict):
    """Jaxpr objects nested in an eqn's params (scan/cond/jit bodies)."""
    for value in params.values():
        items = value if isinstance(value, (list, tuple)) else (value,)
        for item in items:
            inner = getattr(item, "jaxpr", None)  # ClosedJaxpr
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(item, "eqns"):  # raw Jaxpr
                yield item


def walk_eqns(jaxpr, _depth: int = 0):
    """Every eqn of ``jaxpr`` and its nested sub-jaxprs (scan bodies,
    cond branches, inner jit calls), depth-first."""
    if _depth > 32:  # defensive: malformed self-referential params
        return
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from walk_eqns(sub, _depth + 1)


def walk_consts(closed_jaxpr):
    """Every captured constant of ``closed_jaxpr`` and of the closed
    sub-jaxprs nested anywhere in its eqns. jax keeps a jitted
    function's captured constants on the INNER jaxpr of its ``jit`` eqn —
    the outer trace's ``consts`` is empty for every registered kernel."""
    yield from closed_jaxpr.consts
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        for value in eqn.params.values():
            items = value if isinstance(value, (list, tuple)) else (value,)
            for item in items:
                if hasattr(item, "consts") and hasattr(item, "jaxpr"):
                    yield from item.consts


def _aval(var):
    av = getattr(var, "aval", None)
    return av if av is not None and hasattr(av, "dtype") else None


class IRRule(Rule):
    kind = "ir"
    id = "IR000"

    def check(self, traced, ctx) -> Iterator[Finding]:  # type: ignore[override]
        return iter(())

    def finalize(self, ctx) -> Iterator[Finding]:  # type: ignore[override]
        return iter(())


# -- IR001 — dtype discipline -----------------------------------------------

#: dtypes that must never appear in a kernel trace: x64 is enabled
#: process-wide for the INTEGER math (ops/__init__.py), so any float64 is
#: an accidental promotion paying doubled VPU/memory cost — every float
#: the kernels legitimately use is a pinned float32
_BANNED_DTYPES = ("float64", "complex128", "complex64")


@rule
class DtypeDiscipline(IRRule):
    id = "IR001"
    title = "no float64 / weak-float promotion in kernel jaxprs"

    def check(self, traced, ctx) -> Iterator[Finding]:
        seen: set = set()
        jaxpr = traced.closed_jaxpr.jaxpr

        def probe(av, where: str):
            if av is None:
                return None
            d = str(av.dtype)
            if d in _BANNED_DTYPES:
                return f"{d}:{where}"
            # a weak float intermediate is a promotion waiting for a
            # partner operand (and flips with jax.config drift) — every
            # float in a kernel must be pinned via .astype/dtype=
            if getattr(av, "weak_type", False) and d.startswith("float"):
                return f"weak-{d}:{where}"
            return None

        hits = [probe(_aval(v), "input") for v in jaxpr.invars]
        hits += [probe(_aval(v), "const") for v in jaxpr.constvars]
        for eqn in walk_eqns(jaxpr):
            hits += [
                probe(_aval(v), eqn.primitive.name) for v in eqn.outvars
            ]
        for detail in filter(None, hits):
            if detail in seen:
                continue
            seen.add(detail)
            yield traced.finding(
                self.id,
                f"{traced.label}: {detail.rsplit(':', 1)[0]} value produced "
                f"by `{detail.rsplit(':', 1)[1]}` in the traced jaxpr — pin "
                "the dtype explicitly (ops/dispense.py ACC_WIDE/ACC_NARROW "
                "for accumulators, .astype(jnp.float32) for float math); "
                "unpinned dtypes flip with jax.config drift and double "
                "VPU/memory cost on TPU",
                detail,
            )


# -- IR002 — host round-trips -----------------------------------------------

#: primitives that leave the device mid-kernel: any callback flavor plus
#: the infeed/outfeed escape hatches; `device_get` never appears as a
#: primitive (it is an eager host fetch) but is listed for completeness
_HOST_PRIMS = {"infeed", "outfeed", "device_get"}


def _is_host_primitive(name: str) -> bool:
    return name in _HOST_PRIMS or "callback" in name


@rule
class HostRoundTrip(IRRule):
    id = "IR002"
    title = "no host round-trip primitives inside kernel jaxprs"

    def check(self, traced, ctx) -> Iterator[Finding]:
        seen: set = set()
        for eqn in walk_eqns(traced.closed_jaxpr.jaxpr):
            name = eqn.primitive.name
            if not _is_host_primitive(name) or name in seen:
                continue
            seen.add(name)
            yield traced.finding(
                self.id,
                f"{traced.label}: host round-trip primitive `{name}` inside "
                "the kernel jaxpr — every dispatch blocks on a device->host"
                "->device transfer on the serving path; hoist the host work "
                "out of the kernel or precompute it into an input",
                name,
            )


# -- IR003 — closed-over constants ------------------------------------------

#: bytes above which a captured constant is flagged: big captures are
#: snapshot-state arrays baked into the executable — every new snapshot
#: re-traces AND re-transfers them (the inputs-not-captures contract the
#: fleet kernels are built on)
CONST_BYTES_THRESHOLD = 4096


@rule
class ConstCapture(IRRule):
    id = "IR003"
    title = "no large closed-over constants in kernel jaxprs"

    def check(self, traced, ctx) -> Iterator[Finding]:
        threshold = getattr(
            ctx, "const_bytes_threshold", CONST_BYTES_THRESHOLD
        )
        for i, const in enumerate(walk_consts(traced.closed_jaxpr)):
            # sized from shape x itemsize: a captured numpy array arrives
            # as jax's TypedNdArray literal, which carries no ``nbytes``
            shape = tuple(getattr(const, "shape", ()))
            dtype = getattr(const, "dtype", type(const).__name__)
            nbytes = math.prod(shape) * getattr(dtype, "itemsize", 0)
            if nbytes <= threshold:
                continue
            yield traced.finding(
                self.id,
                f"{traced.label}: closed-over constant #{i} "
                f"({shape} {dtype}, {nbytes} bytes) captured into the "
                "trace — captured arrays are baked into the executable, so "
                "every rebuilt snapshot/table mints a fresh compile AND "
                "re-uploads the data; pass it as a kernel input instead",
                f"const:{shape}:{dtype}",
            )


# -- IR004 — trace-manifest fidelity ----------------------------------------


@rule
class ManifestFidelity(IRRule):
    id = "IR004"
    title = ("trace-manifest records re-trace to their recorded signature; "
             "kernel registries stay in lockstep")

    def finalize(self, ctx) -> Iterator[Finding]:
        # (a) every registry spec must trace: a spec that no longer traces
        # means the entry-point registry drifted from the kernel signature
        # — exactly the drift that would make prewarm replay a stale
        # manifest record into a failed compile at boot
        for entry, spec, err in ctx.trace_failures:
            yield Finding(
                rule=self.id, path=entry.path, line=ctx.entry_line(entry),
                col=1,
                message=(
                    f"{entry.name}[{spec.variant}]: entry-point spec failed "
                    f"to trace ({err}) — the IR registry "
                    "(tools/graftlint/ir.py) drifted from the kernel "
                    "signature; update the spec builder or the kernel"
                ),
                anchor=entry.attr, detail=f"trace:{spec.variant}",
                anchor_line=ctx.entry_line(entry),
            )
        # (b) the three fleet-kernel registries must agree: FLEET_KERNELS
        # (dispatch), prewarm._KERNELS (manifest load filter + replay),
        # and the IR entry points (audit). A kernel present in one but not
        # the others is a serving-path dispatch prewarm can never cover.
        cov = ctx.registry_coverage
        if cov is not None:
            surfaces = {
                "fleet": ("karmada_tpu/scheduler/fleet.py", "FLEET_KERNELS"),
                "prewarm": ("karmada_tpu/scheduler/prewarm.py", "_KERNELS"),
                "ir": ("tools/graftlint/ir.py", "ENTRY_POINTS"),
            }
            union = set().union(*cov.values())
            for kernel in sorted(union):
                missing = [s for s, names in cov.items() if kernel not in names]
                if not missing:
                    continue
                for s in missing:
                    path, anchor = surfaces[s]
                    yield Finding(
                        rule=self.id, path=path, line=1, col=1,
                        message=(
                            f"fleet kernel family {kernel!r} is missing "
                            f"from {anchor} ({path}) but present in "
                            f"{sorted(set(cov) - set(missing))} — prewarm "
                            "would silently cover less than the serving "
                            "path dispatches; register it everywhere"
                        ),
                        anchor=anchor, detail=f"coverage:{kernel}",
                    )
        # (c) manifest records: each must resolve to a known kernel,
        # re-trace under the recorded shapes/statics, and round-trip to a
        # byte-identical content signature
        for res in ctx.manifest_results:
            if res.error is None:
                continue
            if res.index < 0:  # manifest-level: unreadable/empty file
                yield Finding(
                    rule=self.id, path=ctx.manifest_rel, line=1, col=1,
                    message=(
                        f"{ctx.manifest_rel}: {res.error} — the audited "
                        "manifest proves NO prewarm coverage; a warmup "
                        "against it would be a silent no-op"
                    ),
                    anchor="<manifest>", detail=f"manifest:{res.reason}",
                )
                continue
            yield Finding(
                rule=self.id, path=ctx.manifest_rel, line=1, col=1,
                message=(
                    f"manifest record #{res.index} ({res.kernel}): "
                    f"{res.error} — prewarm replay of this manifest would "
                    "fail or compile something the serving path never "
                    "dispatches; re-record the manifest "
                    "(delete it and run a warm pass) or fix the kernel"
                ),
                anchor=res.kernel, detail=f"record[{res.index}]:{res.reason}",
            )


# -- IR005 — donation audit --------------------------------------------------


@rule
class DonationAudit(IRRule):
    id = "IR005"
    title = "buffers declared donated are actually consumed by an output"

    def check(self, traced, ctx) -> Iterator[Finding]:
        # donation is declared on the jit wrapper, so it surfaces on the
        # top-level jit eqn of the outer trace; XLA can only alias a
        # donated input into an output of IDENTICAL shape+dtype — a
        # donated buffer with no such output is silently copied, doubling
        # its HBM footprint (the dense resident is the largest tenant)
        for eqn in traced.closed_jaxpr.jaxpr.eqns:
            if eqn.primitive.name != "jit":
                continue
            donated = eqn.params.get("donated_invars") or ()
            if not any(donated):
                continue
            pool = [
                (tuple(av.shape), str(av.dtype))
                for av in (_aval(v) for v in eqn.outvars)
                if av is not None
            ]
            for pos, (var, don) in enumerate(zip(eqn.invars, donated)):
                if not don:
                    continue
                av = _aval(var)
                if av is None:
                    continue
                sig = (tuple(av.shape), str(av.dtype))
                if sig in pool:
                    pool.remove(sig)  # one output consumes one donation
                    continue
                yield traced.finding(
                    self.id,
                    f"{traced.label}: donated argument #{pos} "
                    f"({sig[0]} {sig[1]}) has no output of identical "
                    "shape/dtype to alias into — XLA silently drops the "
                    "donation and keeps BOTH buffers live; return the "
                    "updated buffer or stop donating it",
                    f"donated[{pos}]:{sig[0]}:{sig[1]}",
                )
