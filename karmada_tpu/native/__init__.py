"""On-demand-compiled native host runtime (ctypes, no pip deps).

The TPU compute path is XLA; the HOST side of the wire (byte-widening
the fetched buffers, folding entry runs into the mirror) is plain memory
movement that numpy does in several strided passes — at the 1M-binding
tier that is seconds per churn pass. This package compiles ``fold.c``
with the baked-in g++ on first use (cached under ``_build/`` next to the
sources, keyed by source hash) and exposes the loops via ctypes; every
caller keeps a numpy fallback, so a machine without a toolchain just
runs the slower path.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _build() -> Optional[ctypes.CDLL]:
    src = os.path.join(_DIR, "fold.c")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    build_dir = os.path.join(_DIR, "_build")
    so_path = os.path.join(build_dir, f"fold-{digest}.so")
    if not os.path.exists(so_path):
        os.makedirs(build_dir, exist_ok=True)
        tmp = so_path + f".tmp{os.getpid()}"
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, src],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, so_path)  # atomic under concurrent builders
    lib = ctypes.CDLL(so_path)
    i64 = ctypes.c_int64
    p_u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    p_i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    p_i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.decode3.argtypes = [p_u8, i64, p_i32]
    lib.decode2.argtypes = [p_u8, i64, p_i32]
    lib.decode21.argtypes = [p_u8, i64, p_i32]
    lib.fold_entries.argtypes = [p_i32, i64, p_i32, p_i64, i64, p_i32]
    lib.apply_deltas.argtypes = [
        p_i32, i64, p_i32, p_i64, i64, p_i32, p_i32, i64,
    ]
    return lib


def get() -> Optional[ctypes.CDLL]:
    """The loaded library, or None (no toolchain / build failure /
    KARMADA_TPU_NO_NATIVE=1). Never raises."""
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    with _LOCK:
        if _TRIED:
            return _LIB
        if os.environ.get("KARMADA_TPU_NO_NATIVE") == "1":
            _TRIED = True
            return None
        try:
            _LIB = _build()
        except Exception:  # noqa: BLE001 — fallback path is always valid
            _LIB = None
        _TRIED = True
    return _LIB


def le32(raw: np.ndarray) -> int:
    """First 4 bytes as a little-endian int (the wire's total header)."""
    return (
        int(raw[0]) | (int(raw[1]) << 8)
        | (int(raw[2]) << 16) | (int(raw[3]) << 24)
    )


def decode3(raw: np.ndarray) -> np.ndarray:
    """uint8[3n] little-endian packed entries -> int32[n]."""
    n = len(raw) // 3
    lib = get()
    if lib is None:
        e = raw[: 3 * n].astype(np.int32)
        return e[0::3] | (e[1::3] << 8) | (e[2::3] << 16)
    out = np.empty(n, np.int32)
    lib.decode3(np.ascontiguousarray(raw[: 3 * n]), n, out)
    return out


def decode2(raw: np.ndarray) -> np.ndarray:
    """uint8[2n] little-endian meta words -> int32[n]."""
    n = len(raw) // 2
    lib = get()
    if lib is None:
        m = raw[: 2 * n].astype(np.int32)
        return m[0::2] | (m[1::2] << 8)
    out = np.empty(n, np.int32)
    lib.decode2(np.ascontiguousarray(raw[: 2 * n]), n, out)
    return out


def decode4(raw: np.ndarray) -> np.ndarray:
    """uint8[4n] little-endian words -> int32[n] (the wire of a table with
    two-byte cells: entries and cell deltas take 4 bytes)."""
    n = len(raw) // 4
    return np.ascontiguousarray(raw[: 4 * n]).view("<i4").astype(np.int32)


def decode21(raw: np.ndarray, n: int) -> np.ndarray:
    """21-bit little-endian bitstream -> int32[n]; ``raw`` must extend at
    least 3 bytes past the packed payload (the device wire pads)."""
    lib = get()
    if lib is None:
        bit = np.arange(n, dtype=np.int64) * 21
        byte = bit >> 3
        sh = (bit & 7).astype(np.uint32)
        b = raw.astype(np.uint32)
        u32 = (
            b[byte] | (b[byte + 1] << 8)
            | (b[byte + 2] << 16) | (b[byte + 3] << 24)
        )
        return ((u32 >> sh) & 0x1FFFFF).astype(np.int32)
    out = np.empty(n, np.int32)
    lib.decode21(np.ascontiguousarray(raw), n, out)
    return out


def fold_entries(
    mirror: np.ndarray,  # int32[cap, k_res] C-contiguous
    rows: np.ndarray,  # per changed row (any int dtype)
    counts: np.ndarray,  # entries per row
    stream: np.ndarray,  # int32 concatenated runs, row order
) -> None:
    """Scatter entry runs into the host mirror (zero-filling each row's
    tail). In-place on ``mirror``."""
    lib = get()
    if lib is None or not mirror.flags["C_CONTIGUOUS"]:
        total = int(counts.sum())
        mirror[rows] = 0
        flat_rows = np.repeat(rows, counts)
        starts = np.cumsum(counts) - counts
        cols = np.arange(total) - np.repeat(starts, counts)
        # clamp overlong runs exactly like the C path (which memcpys at
        # most k_res entries per row) so the two paths stay equivalent
        ok = cols < mirror.shape[1]
        mirror[flat_rows[ok], cols[ok]] = stream[:total][ok]
        return
    lib.fold_entries(
        mirror, mirror.shape[1],
        np.ascontiguousarray(rows, np.int32),
        np.ascontiguousarray(counts, np.int64),
        len(rows),
        np.ascontiguousarray(stream, np.int32),
    )


def apply_deltas(
    mirror: np.ndarray,  # int32[cap, k_res] C-contiguous
    rows: np.ndarray,  # per delta row (any int dtype)
    dcounts: np.ndarray,  # deltas per row
    stream: np.ndarray,  # int32 (site<<(cell_bits+1) | newcount+1), row
    # order, site-ascending within each row
    cell_bits: int = 8,  # bits of a count: 8, or 16 for two-byte cells
) -> None:
    """Merge cell deltas into the host mirror's sorted (site << cell_bits |
    count) entry runs (newcount 0 removes the site, otherwise set/insert).
    In-place on ``mirror``; rows are clamped to k_res merged entries like
    fold_entries."""
    k_res = mirror.shape[1]
    lib = get()
    if lib is None or not mirror.flags["C_CONTIGUOUS"]:
        sb = cell_bits
        mask, dmask = (1 << sb) - 1, (2 << sb) - 1
        off = 0
        for r, nd in zip(rows, dcounts):
            nd = int(nd)
            d = stream[off : off + nd]
            off += nd
            if not nd:
                continue
            run = mirror[r]
            sites = {int(v) >> sb: int(v) & mask for v in run if v != 0}
            for v in d:
                v = int(v)
                site, cnt = v >> (sb + 1), (v & dmask) - 1
                if cnt > 0:
                    sites[site] = cnt
                else:
                    sites.pop(site, None)
            merged = [
                (s << sb) | c for s, c in sorted(sites.items())
            ][:k_res]
            mirror[r] = 0
            mirror[r, : len(merged)] = merged
        return
    scratch = np.empty(k_res, np.int32)
    lib.apply_deltas(
        mirror, k_res,
        np.ascontiguousarray(rows, np.int32),
        np.ascontiguousarray(dcounts, np.int64),
        len(rows),
        np.ascontiguousarray(stream, np.int32),
        scratch,
        cell_bits,
    )
