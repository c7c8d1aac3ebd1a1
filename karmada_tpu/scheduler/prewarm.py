"""Trace-signature manifest + AOT prewarming: kill the cold start.

The fleet engine already ledgers every XLA trace signature it dispatches
(``FleetTable._mark_trace`` — the ``new_trace_last_pass`` warm-loop
contract). This module makes that ledger DURABLE and REPLAYABLE:

- ``TraceManifest`` persists, for every fresh trace, the kernel name, the
  ledger key, the exact input shapes/dtypes, and the static-argument
  tuple — everything needed to re-lower and re-compile that trace in a
  process that has never scheduled anything.
- ``replay()`` walks the manifest and runs each record ONCE on
  zero-filled dummy inputs — no engine, no real data — which traces,
  compiles (a persistent-cache hit when a prior process seeded it), and
  leaves the jit DISPATCH cache hot, so the first real dispatch is a
  straight cache hit. AOT ``lower().compile()`` alone is not enough: it
  populates the compile caches but the first dispatch still re-traces
  and re-loads on the serving path (measured at ~1.5× a steady wave). A
  record whose kernel rejects zeros falls back to exactly that AOT
  compile. Everything happens OFF the serving path.
- ``warmup()`` is the boot-phase entry (the ``karmadactl-tpu warmup``
  verb, the localup/solver ``--warmup-manifest`` boot stage, and the
  opt-in fleet-rebuild background thread all land here).

Shape-bucket canonicalization: the engine's static caps are already
quantized (pow2 chunk/slot caps, quarter-octave entry caps, M/D-quantum
wire caps), so a fleet of a given size maps to a small, stable signature
set. ``replay(expand=True)`` additionally compiles the NEXT bucket of
each tuned cap (entry/meta/delta), so a churn burst that grows a cap
mid-storm lands on an already-compiled bucket instead of minting a fresh
compile on the critical path. Grown specs carry no ledger key — the
signature genuinely was not observed, so ``new_trace_last_pass`` still
reports it honestly; only the compile is prepaid.

Shrink buckets (the compaction/rebucket family) expand too: a settle
train's demand collapses toward the cap FLOORS (sustained-shrink
policy), so for each observed record the predecessor bucket and the
floor bucket of each tuned cap are synthesized WITH their derived
ledger keys — the key is a pure function of the record's key and the
substituted cap element, so seeding it after a successful compile is
honest (the compile genuinely happened; the first settle dispatch is a
dispatch-cache hit). Without these, a restored 1M-shape engine minted a
fresh multi-second solve trace mid-settle (BENCH_r05 pass 5).

Restore contract: after ``replay()`` ran in this process, an engine
constructed with the same manifest seeds its fleet ledger from the
manifest keys, so its FIRST pass over a covered fleet shape reports
``new_trace=False`` — warm loops (and HA failovers) skip straight to the
timed window. Seeding without replay would be a lie (the compile would
still run at first dispatch), so it is gated on the replay having
actually happened (``TraceManifest.warmed``).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Optional

#: per manifest path, the record canons replay() COMPILED in this
#: process — the honesty gate for ledger seeding (see module docstring).
#: Per-record, not per-path: a partial warm (stale record vs new build,
#: transient backend error) must seed only the keys whose compile
#: actually succeeded, or the first pass claims new_trace=False while a
#: compile still runs on the serving path.
_WARMED: dict[str, set[str]] = {}
#: per manifest path, the ledger keys replay() proved compiled — the
#: observed records' keys plus the DERIVED shrink-bucket keys (which
#: have no manifest record to recover a key from, hence key set rather
#: than canon set).
_WARMED_KEYS: dict[str, set] = {}
_WARM_LOCK = threading.Lock()

_SCHEMA_VERSION = 1

#: kernels worth persisting: the solve-family traces dominate compile
#: cost; tiny utility kernels (row scatter, meta gather) stay ledger-only.
#: This is the jax-free NAME mirror of fleet.FLEET_KERNELS —
#: TraceManifest._load filters on it without importing the engine;
#: _jit_registry asserts the two stay in lockstep (and graftlint IR004
#: machine-checks it in tier-1). Values are the ``row_coupled``
#: delta-safety declarations — the jax-free mirror of each kernel's own
#: ``row_coupled`` attribute, checked for agreement (and proven against
#: the traced jaxprs) by graftlint IR006.
_KERNELS = {
    "fleet_pass": True,
    "fleet_entries": True,
    "fleet_bits": False,
    "fleet_select": True,
    "fleet_terms": True,
    "fleet_quota": True,
    "quota_admit": True,
    "quota_cluster_caps": False,
    "explain_pass": False,
    "preempt_select": True,
}


def _jit_registry() -> dict:
    from . import fleet

    registry = dict(fleet.FLEET_KERNELS)
    assert set(registry) == set(_KERNELS), (sorted(registry), sorted(_KERNELS))
    return registry


def _retuple(v):
    """JSON round-trip inverse: lists back to tuples, recursively (ledger
    keys and the ``fast`` static are tuples; JSON stores them as lists)."""
    if isinstance(v, list):
        return tuple(_retuple(x) for x in v)
    return v


def _canon(record: dict) -> str:
    """Content identity of a record (dedup key): kernel + shapes +
    statics. The ledger key is derived from those, so it is excluded —
    an expanded spec (key=None) must dedup against an observed record
    with the same compile inputs."""
    return json.dumps(
        [record["kernel"], record["in_shapes"], record["statics"]],
        sort_keys=True,
    )


class TraceManifest:
    """File-backed ledger of compile-ready trace records.

    One instance per path; safe to share across engines in a process.
    Recording never raises into the scheduler (best-effort persistence);
    writes are atomic (tmp + rename) so a crashed writer cannot corrupt
    the manifest a future boot restores from."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self.records: list[dict] = []
        self._seen: set[str] = set()
        self._lock = threading.Lock()
        self._load()

    @property
    def warmed(self) -> bool:
        """True when ``replay()`` completed for this path in this
        process (possibly a partial warm — see ``warmed_keys``)."""
        return self.path in _WARMED

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                data = json.load(f)
            records = data.get("records", [])
        except (OSError, ValueError):
            return
        # under the lock like every other records/_seen mutation: _load
        # also runs via restore-time re-instantiation while engine threads
        # may hold the same manifest object (one instance per path)
        with self._lock:
            for r in records:
                if r.get("kernel") in _KERNELS and "in_shapes" in r:
                    c = _canon(r)
                    if c not in self._seen:
                        self._seen.add(c)
                        self.records.append(r)

    # called-with-lock-held helper (the *_locked convention): load() and
    # record() hold self._lock around it, so the self.records read is
    # serialized with every writer  # graftlint: disable=GL011
    def _save(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        payload = {
            "version": _SCHEMA_VERSION,
            "platform": os.environ.get("JAX_PLATFORMS", ""),
            "records": self.records,
        }
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=None, separators=(",", ":"))
        os.replace(tmp, self.path)

    def record(self, kernel: str, key, arrays, statics: dict) -> None:
        """Persist one fresh trace: ``key`` is the fleet ledger tuple (or
        None for synthesized bucket specs), ``arrays`` the positional
        kernel inputs in dispatch order, ``statics`` the static kwargs.
        No-op for already-known records."""
        rec = {
            "kernel": kernel,
            "key": key if key is None else list(_listify(key)),
            "in_shapes": [
                [list(int(d) for d in a.shape), str(a.dtype)]
                for a in arrays
            ],
            "statics": {k: _listify(v) for k, v in statics.items()},
        }
        c = _canon(rec)
        with self._lock:
            if c in self._seen:
                return
            self._seen.add(c)
            self.records.append(rec)
            try:
                self._save()
            except OSError:
                pass  # persistence is best-effort; the ledger still holds

    def annotate_memory(self, rec_canon: str, memory: dict) -> None:
        """Attach a compiled record's XLA ``memory_analysis()`` footprint
        (temp/output/argument/generated-code bytes) to the matching
        manifest record — the durable half of the device-memory ledger
        (ISSUE 12 b): a future boot can read the compile-time memory
        bill without recompiling. ``memory`` excludes itself from record
        identity (``_canon`` keys on kernel/shapes/statics only), so
        annotation never forks a record. Best-effort persistence, like
        ``record``."""
        with self._lock:
            for r in self.records:
                if _canon(r) == rec_canon:
                    if r.get("memory") == memory:
                        return
                    r["memory"] = memory
                    try:
                        self._save()
                    except OSError:
                        pass  # the in-memory annotation still holds
                    return

    def keys(self) -> set:
        """The observed ledger keys, as tuples (seeding form)."""
        with self._lock:
            records = list(self.records)
        return {
            _retuple(r["key"])
            for r in records
            if r.get("key") is not None
        }

    def warmed_keys(self) -> set:
        """The ledger keys ``replay()`` proved compiled in this process —
        the only keys an engine may seed its new-trace ledger from:
        observed records' keys plus derived shrink-bucket keys. Empty
        before replay; excludes records whose compile failed (their
        trace would still run at first dispatch)."""
        ok = _WARMED.get(self.path)
        if not ok:
            return set()
        with self._lock:
            records = list(self.records)
        keys = {
            _retuple(r["key"])
            for r in records
            if r.get("key") is not None and _canon(r) in ok
        }
        keys.update(_WARMED_KEYS.get(self.path, set()))
        return keys


def _listify(v):
    if isinstance(v, tuple):
        return [_listify(x) for x in v]
    return v


def _statics_from_json(statics: dict) -> dict:
    """Inverse of record(): lists back to tuples (``fast``), everything
    else verbatim. A meshed record's ``mesh`` static is its canonical
    SHAPE tuple (parallel.mesh.mesh_shape) — kept in shape form here so
    content signatures round-trip byte-identically (the IR004 canon);
    ``replay()`` materializes a live Mesh over the booting process's
    devices just before compiling (parallel.mesh.materialize_mesh_statics
    — a backend that cannot host the recorded shape fails that record,
    which keeps it out of ``warmed_keys`` and off the seeded ledger)."""
    return {k: _retuple(v) for k, v in statics.items()}


def _cap_prev(cap: int) -> Optional[int]:
    """Largest quantized entry cap strictly below ``cap`` (None at the
    1024 floor) — the bucket a sustained shrink lands on next. Bisects
    against ``_cap_round`` (monotone, rounds up) so the result tracks
    the engine's quantization policy verbatim."""
    from .fleet import _cap_round

    if cap <= 1024:
        return None
    lo, hi = 1, cap - 1
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if _cap_round(mid) < cap:
            lo = mid
        else:
            hi = mid - 1
    return _cap_round(lo)


#: kernel -> {static name: index of that cap in the record's ledger key}
#: (fleet._e_key / a_key layouts). Shrink-bucket derivation
#: substitutes the cap element of an OBSERVED key; the sanity check in
#: expand_records (key[idx] == statics[cap]) keeps a layout drift from
#: ever seeding a wrong key.
_KEY_CAP_INDEX = {
    "fleet_entries": {"e_cap": 6},
    "fleet_pass": {"m_cap": 10, "d_cap": 11},
}


def _derived(r: dict, updates: dict) -> Optional[dict]:
    """A synthesized record: ``r`` with the cap statics in ``updates``
    substituted and the ledger key re-derived by element substitution.
    None when the observed key does not match the declared layout."""
    idx_map = _KEY_CAP_INDEX.get(r["kernel"], {})
    key = list(r["key"]) if r.get("key") is not None else None
    statics = dict(r["statics"])
    for name, cap in updates.items():
        if key is not None:
            i = idx_map.get(name)
            if i is None or i >= len(key) or key[i] != statics.get(name):
                key = None  # layout drift: compile-only, never seed
            else:
                key[i] = cap
        statics[name] = cap
    return {
        "kernel": r["kernel"],
        "key": key,
        "in_shapes": r["in_shapes"],
        "statics": statics,
    }


def expand_records(records: list[dict]) -> list[dict]:
    """Shape-bucket expansion: for each observed record, synthesize the
    NEXT bucket of each tuned wire cap (so mid-storm cap growth lands on
    a prepaid compile) and the PREDECESSOR + FLOOR buckets (so a settle
    train's sustained shrink does too). Grown specs have key=None (the
    signature was never dispatched; the ledger must stay honest); shrink
    specs carry their derived key — see the module docstring."""
    from .fleet import D_FLOOR, D_ROUND, M_ROUND, _cap_round, d_round

    out: list[dict] = []
    seen = {_canon(r) for r in records}

    def _emit(rec: dict) -> None:
        c = _canon(rec)
        if c not in seen:
            seen.add(c)
            out.append(rec)

    for r in records:
        statics = dict(r["statics"])
        grown: list[dict] = []
        shrunk: list[dict] = []
        if r["kernel"] == "fleet_entries":
            e_cap = statics.get("e_cap")
            if isinstance(e_cap, int):
                grown.append({**statics, "e_cap": _cap_round(e_cap + 1)})
                prev = _cap_prev(e_cap)
                if prev is not None:
                    shrunk.append({"e_cap": prev})
                    if prev > 1024:
                        shrunk.append({"e_cap": 1024})
        elif r["kernel"] == "fleet_pass":
            m_cap = statics.get("m_cap")
            d_cap = statics.get("d_cap", 0)
            if isinstance(m_cap, int):
                # the engine's m_round: 4096 floor, then M_ROUND
                # multiples, clamped to the padded row count (the rows
                # input, position 5) — rounding the cap's successor lands
                # on the bucket the engine would actually tune to next
                # (adding a raw quantum to the 4096 floor does not)
                n_pad = r["in_shapes"][5][0][0]
                nxt = (
                    -(-(m_cap + 1) // M_ROUND) * M_ROUND
                    if m_cap + 1 > 4096
                    else 4096
                )
                nxt = min(nxt, n_pad)
                if nxt > m_cap:
                    grown.append({**statics, "m_cap": nxt})
            if isinstance(d_cap, int) and d_cap > 0:
                # same successor-rounding for the delta cap (D_FLOOR,
                # then D_ROUND multiples)
                grown.append({**statics, "d_cap": d_round(d_cap + 1)})
            # shrink: the settle train tunes each cap down its own
            # sustain vote, so cover the single-step predecessors and
            # the joint floor state the train terminates in
            m_floor = (
                min(4096, r["in_shapes"][5][0][0])
                if isinstance(m_cap, int)
                else None
            )
            m_prev = None
            if isinstance(m_cap, int) and m_cap > m_floor:
                q = (m_cap - 1) // M_ROUND * M_ROUND
                m_prev = q if q > m_floor else m_floor
            d_prev = None
            if isinstance(d_cap, int) and d_cap > D_FLOOR:
                q = (d_cap - 1) // D_ROUND * D_ROUND
                d_prev = q if q > D_FLOOR else D_FLOOR
            if m_prev is not None:
                shrunk.append({"m_cap": m_prev})
            if d_prev is not None:
                shrunk.append({"d_cap": d_prev})
            floors = {}
            if m_prev is not None:
                floors["m_cap"] = m_floor
            if d_prev is not None:
                floors["d_cap"] = D_FLOOR
            if floors:
                shrunk.append(floors)
        for st in grown:
            _emit(
                {
                    "kernel": r["kernel"],
                    "key": None,
                    "in_shapes": r["in_shapes"],
                    "statics": st,
                }
            )
        for updates in shrunk:
            d = _derived(r, updates)
            if d is not None:
                _emit(d)
    return out


def replay(manifest: TraceManifest, *, expand: bool = True) -> dict:
    """AOT-compile every manifest record (plus expanded buckets) on the
    current backend. Returns stats; per-record failures are counted, not
    raised — a manifest written by an older build must degrade to a
    partial warm, never block boot."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    registry = _jit_registry()
    records = list(manifest.records)
    specs = records + (expand_records(records) if expand else [])
    # dedup expanded specs against observed ones
    seen: set[str] = set()
    todo = []
    for r in specs:
        c = _canon(r)
        if c not in seen:
            seen.add(c)
            todo.append(r)
    compiled = failed = 0
    ok_canons: set[str] = set()
    ok_keys: set = set()
    errors: list[str] = []
    # kernel -> {temp/output/argument/generated_code bytes}: the MAX
    # footprint across this replay's records per kernel family — what an
    # operator budgets HBM against (karmada_tpu_kernel_memory_bytes)
    memory_by_kernel: dict[str, dict] = {}
    t0 = time.perf_counter()
    for r in todo:
        fn = registry.get(r["kernel"])
        if fn is None:
            failed += 1
            continue
        try:
            shapes = [
                (tuple(shape), np.dtype(dtype))
                for shape, dtype in r["in_shapes"]
            ]
            statics = _statics_from_json(r["statics"])
            # a meshed record carries its mesh as the canonical shape;
            # build the live mesh over THIS process's devices (raises —
            # counting the record failed — when the backend cannot host
            # it, so an 8-chip record can never fake-warm a 1-chip boot)
            from ..parallel.mesh import materialize_mesh_statics

            statics = materialize_mesh_statics(statics)
            aot = None
            try:
                # one dummy-data execution: trace + compile (persistent-
                # cache hit when seeded) + run, leaving the jit dispatch
                # cache hot — the first REAL dispatch then skips tracing
                # and cache-loading entirely
                args = [jnp.zeros(s, d) for s, d in shapes]
                jax.block_until_ready(fn(*args, **statics))
                del args
            except Exception:  # noqa: BLE001 — zeros tripped the kernel
                # fall back to AOT compile: the caches still fill, only
                # the first dispatch re-traces (off the compile cliff).
                # Kept for the memory hook below — never re-lowered.
                aot = fn.lower(
                    *(jax.ShapeDtypeStruct(s, d) for s, d in shapes),
                    **statics,
                ).compile()
            compiled += 1
            ok_canons.add(_canon(r))
            if r.get("key") is not None:
                # proved-compiled ledger key (observed or derived
                # shrink bucket) — the seeding surface of warmed_keys()
                ok_keys.add(_retuple(r["key"]))
            # device-memory footprint (ISSUE 12 b), best-effort: an
            # already-annotated record reuses its stored footprint —
            # zero extra lowerings on every boot after the first; a
            # fresh record pays ONE extra lowering (the compile itself
            # is a cache hit behind the execution above / the persistent
            # cache warmup enables at threshold 0).
            try:
                mem = r.get("memory")
                if mem is None:
                    if aot is None:
                        aot = fn.lower(
                            *(
                                jax.ShapeDtypeStruct(s, d)
                                for s, d in shapes
                            ),
                            **statics,
                        ).compile()
                    ma = aot.memory_analysis()
                    if ma is not None:
                        mem = {
                            "temp_bytes": int(ma.temp_size_in_bytes),
                            "output_bytes": int(ma.output_size_in_bytes),
                            "argument_bytes": int(
                                ma.argument_size_in_bytes
                            ),
                            "generated_code_bytes": int(
                                ma.generated_code_size_in_bytes
                            ),
                        }
                        if r.get("key") is not None:
                            manifest.annotate_memory(_canon(r), mem)
                if mem:
                    slot = memory_by_kernel.setdefault(r["kernel"], {})
                    for kind, v in mem.items():
                        slot[kind] = max(slot.get(kind, 0), int(v))
            except Exception:  # noqa: BLE001 — footprint is telemetry
                pass
        except Exception as e:  # noqa: BLE001 — partial warm beats no boot
            failed += 1
            if len(errors) < 5:
                errors.append(f"{r['kernel']}: {e!r}")
    stats = {
        "records": len(records),
        "specs": len(todo),
        "compiled": compiled,
        "failed": failed,
        "seconds": round(time.perf_counter() - t0, 3),
    }
    if errors:
        stats["errors"] = errors
    if memory_by_kernel:
        stats["memory_bytes"] = {
            k: dict(sorted(v.items()))
            for k, v in sorted(memory_by_kernel.items())
        }
        from ..utils.metrics import kernel_memory_bytes

        for kernel, mem in memory_by_kernel.items():
            for kind, v in mem.items():
                kernel_memory_bytes.set(
                    v, kernel=kernel, kind=kind.removesuffix("_bytes"),
                )
    # compile-lifecycle metric hook (ISSUE 6 b): off-serving-path prewarm
    # compiles show on /metrics beside the serving-path compile counter,
    # so an operator can see a boot's compile bill vs the storm's
    from ..utils.metrics import kernel_prewarmed

    if compiled:
        kernel_prewarmed.inc(compiled, result="compiled")
    if failed:
        kernel_prewarmed.inc(failed, result="failed")
    with _WARM_LOCK:
        _WARMED.setdefault(manifest.path, set()).update(ok_canons)
        _WARMED_KEYS.setdefault(manifest.path, set()).update(ok_keys)
    return stats


def warmup(
    manifest_path: Optional[str] = None, *, expand: bool = True
) -> dict:
    """Boot-phase prewarm: enable the persistent cache with a zero
    persistence threshold (every warmed trace must survive the process),
    load the manifest, and replay it. The entry point behind the
    ``karmadactl-tpu warmup`` verb and the localup/solver
    ``--warmup-manifest`` boot stage."""
    from ..utils import compilecache

    path = manifest_path or compilecache.default_manifest_path()
    if not path:
        return {"records": 0, "specs": 0, "compiled": 0, "failed": 0,
                "seconds": 0.0, "manifest": "", "cache_dir": ""}
    cache_dir = compilecache.enable(min_compile_secs=0.0)
    manifest = TraceManifest(path)
    stats = replay(manifest, expand=expand)
    stats["manifest"] = manifest.path
    stats["cache_dir"] = cache_dir
    # the boot's scheduling-mesh identity rides the warmup stats so the
    # operator (and the orchestrator scraping the JSON line) can tell a
    # single-chip from an 8-chip plane before any engine is built
    from ..parallel.mesh import mesh_shape, resolve_mesh

    try:
        stats["mesh"] = mesh_shape(resolve_mesh(None))
    except Exception as exc:  # noqa: BLE001 — a misconfigured mesh env
        # fails loudly at ENGINE construction; warmup only reports
        stats["mesh"] = f"error: {exc}"
    return stats


def resolve_boot_manifest(flag: Optional[str]) -> str:
    """The ``--warmup-manifest`` resolution rule shared by the solver
    sidecar and the localup serve/replica boot phases: a flag left unset
    (None) falls back to ``$KARMADA_TPU_TRACE_MANIFEST``; an EXPLICIT
    ``""`` opts out even with the env var set. Returns the manifest path
    ("" = disabled)."""
    if flag is not None:
        return flag
    from ..utils.compilecache import MANIFEST_ENV

    return os.environ.get(MANIFEST_ENV, "")


def resolve_manifest(spec) -> Optional[TraceManifest]:
    """Normalize an engine's ``trace_manifest`` argument: a TraceManifest
    passes through, a path string wraps, None falls back to the env
    default (``KARMADA_TPU_TRACE_MANIFEST``; unset/empty = disabled —
    engines never write a manifest the operator didn't ask for)."""
    if isinstance(spec, TraceManifest):
        return spec
    if isinstance(spec, str):
        return TraceManifest(spec) if spec else None
    if spec is None:
        from ..utils.compilecache import MANIFEST_ENV

        path = os.environ.get(MANIFEST_ENV, "")
        return TraceManifest(path) if path else None
    raise TypeError(f"trace_manifest: expected TraceManifest, str or None, "
                    f"got {type(spec).__name__}")


_REBUILD_WARMED: set[str] = set()


def prewarm_on_rebuild(manifest: Optional[TraceManifest]) -> None:
    """Opt-in background prewarm when a fleet table is (re)built: replay
    the manifest on a daemon thread so the rebuilt table's upcoming
    shapes compile OFF the serving path. Enabled by
    ``KARMADA_TPU_PREWARM_ON_REBUILD=1``; once per manifest per
    process."""
    if manifest is None:
        return
    if os.environ.get("KARMADA_TPU_PREWARM_ON_REBUILD") not in ("1", "true"):
        return
    with _WARM_LOCK:
        if manifest.path in _REBUILD_WARMED:
            return
        _REBUILD_WARMED.add(manifest.path)

    def _bg() -> None:
        try:
            replay(manifest)
        except Exception:  # noqa: BLE001 — warmers never take the plane down
            logging.getLogger("karmada_tpu").exception(
                "background prewarm of %s failed; serving path will "
                "compile on first dispatch instead", manifest.path
            )

    threading.Thread(
        target=_bg, name="fleet-prewarm", daemon=True
    ).start()
