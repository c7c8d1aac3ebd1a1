"""Persistent XLA compilation cache policy — one module, every process.

A fresh trace of a fleet kernel costs seconds to minutes of XLA compile
(not measured on this machine; the engine's static specializations —
chunk counts, kernel variants, entry-buffer caps — legitimately produce
several traces per workload shape), and every plane restart, HA failover
and fleet-table rebuild would re-pay all of them on the serving path.

This module is the single resolution point for where that cost is paid
once:

- ``resolve_cache_dir()`` — the on-disk cache directory:
  ``JAX_COMPILATION_CACHE_DIR`` verbatim when set, otherwise
  ``<checkout>/.jax_cache/<platform set>`` beside the package. A
  partition that can hold XLA:CPU executables additionally carries a
  fingerprint of the host CPU: jax keys a cached executable by backend
  version, not by the ISA features it was compiled for, so a checkout
  copied to another machine (with its ignored ``.jax_cache/``) must not
  hand that machine's CPU-pinned processes foreign machine code.
- ``enable()`` — applies the jax.config knobs; called by
  ``karmada_tpu.ops`` at import (every jax-using component passes through
  it) and re-callable to tighten the persistence threshold.
- ``default_manifest_path()`` — where the trace-signature manifest
  (scheduler.prewarm.TraceManifest) lives by default: BESIDE the cache,
  in the same partition, because manifest records replay into exactly
  that cache.

Env knobs (the process-tree plumbing localup/solver/bench ride):

- ``JAX_COMPILATION_CACHE_DIR`` — cache directory, used verbatim (no
  partition — whoever set it pinned an exact path); ``""`` disables.
  No code in this repo points the cache anywhere else while it is set.
- ``KARMADA_TPU_TRACE_MANIFEST`` — manifest path override; ``""``
  disables manifest recording/restoring entirely.
- ``KARMADA_TPU_CACHE_MIN_COMPILE_SECS`` — persistence threshold
  (default 1.0; prewarm drops it to 0.0 so warmed artifacts always
  persist).
"""

from __future__ import annotations

import hashlib
import os
import platform

MIN_COMPILE_SECS_ENV = "KARMADA_TPU_CACHE_MIN_COMPILE_SECS"
MANIFEST_ENV = "KARMADA_TPU_TRACE_MANIFEST"
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def _host_fingerprint() -> str:
    """Short stable id of this host's CPU model + ISA feature flags."""
    try:
        with open("/proc/cpuinfo") as f:
            ident = "".join(sorted({
                ln for ln in f if ln.startswith(("model name", "flags"))
            }))
    except OSError:
        ident = ""
    ident = ident or platform.machine() + platform.processor()
    return hashlib.sha256(ident.encode()).hexdigest()[:8]


def _platform_partition() -> str:
    """Cache partition of this process: the ``JAX_PLATFORMS`` list (the
    whole platform-selection mechanism), plus the host fingerprint unless
    the list excludes the CPU backend."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    name = platforms.replace(",", "_") or "default"
    if not platforms or "cpu" in platforms.split(","):
        name += "-" + _host_fingerprint()
    return name


def resolve_cache_dir() -> str:
    """The effective persistent-cache directory ("" = disabled)."""
    override = os.environ.get(CACHE_DIR_ENV)
    if override is not None:
        return override
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_cache", _platform_partition())


def enable(*, min_compile_secs: float | None = None) -> str:
    """Point jax's persistent compilation cache at ``resolve_cache_dir()``
    — the only place this repo sets it. Returns the active directory (""
    when disabled). Safe to call again to tighten ``min_compile_secs``
    (prewarm sets 0.0 so every warmed trace persists regardless of how
    fast it compiled)."""
    cache_dir = resolve_cache_dir()
    if not cache_dir:
        return ""
    if min_compile_secs is None:
        try:
            min_compile_secs = float(
                os.environ.get(MIN_COMPILE_SECS_ENV, "1.0")
            )
        except ValueError:
            min_compile_secs = 1.0
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    return cache_dir


def default_manifest_path() -> str:
    """Where the trace-signature manifest lives ("" = disabled).

    ``KARMADA_TPU_TRACE_MANIFEST`` overrides (empty string disables);
    otherwise the manifest sits inside the cache directory so cache and
    manifest travel (and invalidate) together."""
    override = os.environ.get(MANIFEST_ENV)
    if override is not None:
        return override
    cache_dir = resolve_cache_dir()
    if not cache_dir:
        return ""
    return os.path.join(cache_dir, "trace_manifest.json")
