"""Bitset machinery for label/taint/GVK matching at tensor speed.

Label selectors, tolerations, and API enablement are the O(bindings x
clusters) constant factor of the reference's filter loop
(framework/plugins/*). Here every string universe is interned into a bit
vocabulary (label key=value pairs, label keys, taint triples, GVKs) and packed
into uint32 words, so a full selector evaluates as a handful of AND/OR/
popcount ops over ``[C, words]`` arrays — no string work on the hot path.

These helpers are backend-agnostic: they accept numpy or jax arrays (the
snapshot builder uses numpy once per snapshot; kernels can run them on
device).
"""

from __future__ import annotations

from typing import Iterable, Mapping, Sequence

import numpy as np

WORD = 32


class Vocab:
    """String -> bit-id interning table."""

    def __init__(self) -> None:
        self._ids: dict[str, int] = {}

    def intern(self, s: str) -> int:
        i = self._ids.get(s)
        if i is None:
            i = len(self._ids)
            self._ids[s] = i
        return i

    def get(self, s: str) -> int | None:
        return self._ids.get(s)

    def __len__(self) -> int:
        return len(self._ids)

    def __contains__(self, s: str) -> bool:
        return s in self._ids

    @property
    def words(self) -> int:
        return max(1, (len(self._ids) + WORD - 1) // WORD)


def pack_bits(rows: Sequence[Iterable[int]], words: int) -> np.ndarray:
    """Pack per-row bit-id lists into uint32[rows, words]."""
    out = np.zeros((len(rows), words), dtype=np.uint32)
    for r, ids in enumerate(rows):
        for i in ids:
            out[r, i // WORD] |= np.uint32(1) << np.uint32(i % WORD)
    return out


def bits_from_ids(ids: Iterable[int], words: int) -> np.ndarray:
    """Pack one bit-id list into uint32[words]."""
    return pack_bits([list(ids)], words)[0]


def contains_all(bits, require) -> np.ndarray:
    """bool[...]: every bit of ``require`` present in ``bits``.
    bits: uint32[..., W]; require: uint32[W] (broadcast)."""
    return ((bits & require) == require).all(axis=-1)


# row_coupled: the graftlint-dep delta-safety declaration (row i of the
# output reads only row i of ``bits``) — certified against the jaxpr by
# IR006, see tools/graftlint/dep.py
contains_all.row_coupled = False


def intersects(bits, other) -> np.ndarray:
    """bool[...]: any common bit."""
    return ((bits & other) != 0).any(axis=-1)


intersects.row_coupled = False  # per-row word reduce; IR006-certified


def affinity_group_rank(term_masks: np.ndarray) -> np.ndarray:
    """int32[..., C] ordered-failover rank tensor: for each cluster, the
    index of the FIRST affinity term (ClusterAffinities fallback group)
    whose mask contains it, ``T`` where none does (scheduler.go:533-596's
    group order as data instead of control flow). ``term_masks``:
    bool[..., T, C]."""
    t = term_masks.shape[-2]
    idx = np.where(
        term_masks,
        np.arange(t, dtype=np.int32).reshape((t, 1)),
        np.int32(t),
    )
    return idx.min(axis=-2)


def first_fit_group(
    cand_tc: np.ndarray,  # bool[B, T, C] per-term candidate sets
    term_len: np.ndarray,  # int32[B] live terms per row (<= T)
    avail: np.ndarray,  # int64[B, C] merged estimator availability
    replicas: np.ndarray,  # int64[B]
    prev: np.ndarray,  # int64[B, C] previous placements
    dynamic: np.ndarray,  # bool[B] divided dynamic-family strategy
    fresh: np.ndarray,  # bool[B] reschedule-triggered
) -> tuple[np.ndarray, np.ndarray]:
    """Batched ordered-failover group selection: each row's FIRST term
    whose candidate set both exists and passes the divider's
    schedulability predicate — the exact cohort math of
    ``refimpl.divider_np.assign_batch_np`` (fresh credits prev, scale-down
    weighs FULL prev, scale-up targets the shortfall, steady no-ops), so
    selecting group t here and then solving once is placement-identical
    to solving groups 0..t in sequence and keeping the first success.

    Returns ``(rank int32[B], fit bool[B])``; rows where NO group fits get
    their LAST live term (its solve produces the failure the per-round
    loop would have reported). The T axis is a short host loop (T = max
    ClusterAffinities length, almost always <= 4) over fully-batched
    [B, C] reductions — O(B*T*C) adds, no [B, T, C] integer temporaries.
    """
    if isinstance(cand_tc, np.ndarray):
        return _first_fit_group_kernel(
            np, cand_tc, term_len, avail, replicas, prev, dynamic, fresh,
        )
    import jax.numpy as jnp  # device path: lazy so masks stays jax-free

    return _first_fit_group_kernel(
        jnp, cand_tc, term_len, avail, replicas, prev, dynamic, fresh,
    )


# the cohort selection consumes plane-merged availability: per-row over
# B, but changing any binding moves avail for every other row (the
# graftlint-dep plane channel; see tools/graftlint/dep.py)
first_fit_group.row_coupled = True


def _first_fit_group_kernel(
    xp, cand_tc, term_len, avail, replicas, prev, dynamic, fresh
):
    """Backend-generic body of :func:`first_fit_group` (xp is numpy for
    the snapshot path, jax.numpy under a trace). ``cand_tc`` is the stacked
    bool[B, T, C] or a sequence of T bool[B, C] planes.

    The arithmetic keeps the dtype it is given, so the fleet table's term
    kernel runs it in int32: every sum is compared with the row's replicas
    or less, so each element is first cut at replicas + 1. A sum that holds
    a cut element is at least replicas + 1 and answers every comparison as
    the exact sum would; a sum that holds none is exact. In int32 the
    caller keeps twice (replicas + 1) x C of a dynamic row under 2^31
    (scheduler.fleet.replicas_bound: 65,535 below 16,384 members)."""
    planes = (
        cand_tc if isinstance(cand_tc, (list, tuple))
        else [cand_tc[:, ti, :] for ti in range(cand_tc.shape[1])]
    )
    t = len(planes)
    num = replicas
    lim = (num + 1)[:, None]
    avail = xp.minimum(avail, lim)
    prev = xp.minimum(prev, lim)
    # sums keep the dtype (jax.numpy would widen an int32 sum under x64)
    dt = avail.dtype
    prev_full_sum = prev.sum(axis=1, dtype=dt)
    cand_any = xp.stack([p.any(axis=1) for p in planes], axis=1)
    # per-term masked sums as a stack over the short static T axis (the
    # same O(B*T*C) adds as the old in-place fill, but expressible on
    # immutable device arrays)
    avail_sum = xp.stack(
        [xp.where(p, avail, 0).sum(axis=1, dtype=dt) for p in planes], axis=1,
    )
    prev_sum = xp.stack(
        [xp.where(p, prev, 0).sum(axis=1, dtype=dt) for p in planes], axis=1,
    )
    dyn = dynamic[:, None]
    fr = fresh[:, None]
    num_col = num[:, None]
    scale_down = dyn & ~fr & (prev_sum > num_col)
    scale_up = dyn & ~fr & (prev_sum < num_col)
    steady = dyn & ~fr & (prev_sum == num_col)
    target = xp.where(scale_up, num_col - prev_sum, num_col)
    w_sum = xp.where(
        fr,
        avail_sum + prev_sum,
        xp.where(scale_down, prev_full_sum[:, None], avail_sum),
    )
    unsched = dyn & ~steady & (w_sum < target)
    live = xp.arange(t, dtype=xp.int32)[None, :] < term_len[:, None]
    fit_t = cand_any & ~unsched & live
    fit = fit_t.any(axis=1)
    # first-fitting-group extraction: first-true-index over the T axis
    # (affinity_group_rank's primitive, inlined backend-generically)
    term_idx = xp.arange(t, dtype=xp.int32)[None, :]
    rank = xp.where(fit_t, term_idx, xp.int32(t)).min(axis=1)
    last = xp.maximum(term_len - 1, 0).astype(xp.int32)
    return xp.where(fit, rank, last).astype(xp.int32), fit


def label_pair(key: str, value: str) -> str:
    return f"{key}={value}"


def intern_labels(vocab: Vocab, key_vocab: Vocab, labels: Mapping[str, str]) -> tuple[list[int], list[int]]:
    """Intern a label map into (pair_ids, key_ids)."""
    pair_ids = [vocab.intern(label_pair(k, v)) for k, v in labels.items()]
    key_ids = [key_vocab.intern(k) for k in labels]
    return pair_ids, key_ids
