"""Fused match drivers: jnp mirror, chunked gather, device compaction.

Layout mirrors ``kernels/pairs`` + ``kernels/sort``: the Pallas kernel
(match.py) computes, XLA does the gathers/scatters, ref.py holds the
numpy oracle. Three public layers:

- ``pair_jaccard_jnp`` / ``score_lanes_jnp``: the single-source scoring
  math. ``data/matcher.py``'s host path jits the SAME functions, so host
  scores and fused on-device matches are bit-identical by construction
  (not merely by test).
- ``fused_match_pairs``: chunked driver over a device pair list —
  clamped-gather member rows, score+threshold+in-tile-rank per chunk
  (jnp mirror or the Pallas kernel), then ONE cross-chunk prefix-sum
  scatter (``compact_matched``) into the packed matched-pair buffer.
- The packed buffer is the device form of the streaming ledger's
  ``a<<32|b`` uint64 words: x64 stays off (core/u64.py), so it lives as
  the two int32 limbs ``(hi=a, lo=b)``; ``packed_host`` reassembles the
  numpy uint64 ledger words at the host boundary.

Everything device-side is explicit-transfer only: scalars cross as
``jax.device_put(np.int32(...))``, results cross only when the caller
pulls them (repro.analysis R001 / transfer-guard clean).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .match import SUBLANES, _LANES, match_score_pallas

# chunk granularity: multiple of lanes, amortizes dispatch without
# blowing VMEM on the (C, T, chunk) gathered stacks
CHUNK_QUANTUM = 1024
DEFAULT_CHUNK = 1 << 16


def pair_jaccard_jnp(tok: jnp.ndarray, mask: jnp.ndarray, a: jnp.ndarray,
                     b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jaccard of padded token sets for record index pairs (a, b).

    Returns ``(jaccard, present)``: the f32 score and whether both sides
    have at least one valid token (absent columns drop out of the
    weighted norm instead of dragging the score to 0).
    """
    ta, ma = tok[a], mask[a]
    tb, mb = tok[b], mask[b]
    eq = (ta[:, :, None] == tb[:, None, :]) & ma[:, :, None] & mb[:, None, :]
    inter = jnp.sum(jnp.any(eq, axis=2), axis=1)
    na = jnp.sum(ma, axis=1)
    nb = jnp.sum(mb, axis=1)
    union = na + nb - inter
    both = (na > 0) & (nb > 0)
    return jnp.where(both, inter / jnp.maximum(union, 1), 0.0), both


def score_lanes_jnp(tokens, masks, weights, a, b) -> jnp.ndarray:
    """Weighted multi-column score for pair lanes (a, b) — trace-level.

    ``weights`` must be a static tuple of python floats (traced scalars
    would be one implicit upload apiece — repro.analysis R001). The op
    sequence here defines the bit-exact contract shared by the host
    matcher, the jnp mirror, the Pallas kernel, and ref.py.
    """
    total = jnp.zeros(a.shape, jnp.float32)
    norm = jnp.zeros(a.shape, jnp.float32)
    for i in range(len(weights)):
        j, present = pair_jaccard_jnp(tokens[i], masks[i], a, b)
        w = weights[i]
        total = total + w * j
        norm = norm + jnp.where(present, w, 0.0)
    return jnp.where(norm > 0, total / jnp.maximum(norm, 1e-6), 0.0)


def round_up(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


@functools.partial(jax.jit, static_argnames=(
    "chunk", "weights", "threshold", "use_kernel"))
def _match_chunk(tokens, masks, a, b, base, n_real, *, chunk: int,
                 weights: tuple, threshold: float, use_kernel: bool):
    """Score one ``chunk`` of the pair list and emit compaction inputs.

    ``base``/``n_real`` are device int32 scalars so any offset reuses one
    compile per (chunk, schema). Out-of-range lanes replicate a clamped
    in-range pair (the ``_gather_bucket`` idiom) and are force-unmatched
    via ``valid``. Returns per-lane ``(aa, bb, matched, rank)`` plus the
    per-tile matched ``counts`` (chunk/128,).
    """
    offsets = jnp.arange(chunk, dtype=jnp.int32)
    valid = offsets < (n_real - base)
    idx = jnp.clip(base + offsets, 0, a.shape[0] - 1)
    aa = a[idx]
    bb = b[idx]
    if use_kernel:
        t_pad = round_up(max(t.shape[1] for t in tokens), SUBLANES)
        # stack columns as (C, T_pad, chunk): pairs ride the lane axis
        def stacked(cols, rows, cast):
            out = []
            for i in range(len(cols)):
                g = cols[i][rows].astype(cast)              # (chunk, T_c)
                pad = ((0, 0), (0, t_pad - cols[i].shape[1]))
                out.append(jnp.pad(g, pad).T)               # (T_pad, chunk)
            return jnp.stack(out)
        ta = stacked(tokens, aa, jnp.uint32)
        tb = stacked(tokens, bb, jnp.uint32)
        # masks ride as int32 0/1 (bool tiles are backend-fragile)
        ma = stacked(masks, aa, jnp.int32)
        mb = stacked(masks, bb, jnp.int32)
        v = valid.astype(jnp.int32).reshape(-1, 1, _LANES)
        m2, r2, c2 = match_score_pallas(ta, ma, tb, mb, v, weights=weights,
                                        threshold=threshold)
        matched = m2.reshape(-1) != 0
        rank = r2.reshape(-1)
        counts = c2[:, 0, 0]
    else:
        score = score_lanes_jnp(tokens, masks, weights, aa, bb)
        matched = valid & (score >= threshold)
        m2 = matched.astype(jnp.int32).reshape(-1, _LANES)
        rank = (jnp.cumsum(m2, axis=1) - m2).reshape(-1)
        counts = jnp.sum(m2, axis=1)
    return aa, bb, matched, rank, counts


@jax.jit
def compact_matched(aa, bb, matched, rank, counts):
    """Prefix-sum scatter of the matched lanes into a packed pair buffer.

    One exclusive cumsum over the per-tile counts gives each tile its
    base offset; ``base[tile] + rank`` is every matched lane's final
    slot. Unmatched lanes aim at the dump slot ``n`` of an (n+1)-long
    zero buffer that is cropped back to ``n`` — so the single scatter is
    total, and the tail beyond ``count`` stays zero, which downstream
    clustering reads as (0, 0) self-edge no-ops.
    """
    n = aa.shape[0]
    base = jnp.cumsum(counts) - counts
    tile = jnp.arange(n, dtype=jnp.int32) // _LANES
    pos = jnp.where(matched, base[tile] + rank, n)
    ca = jnp.zeros((n + 1,), jnp.int32).at[pos].set(aa)[:n]
    cb = jnp.zeros((n + 1,), jnp.int32).at[pos].set(bb)[:n]
    return ca, cb, jnp.sum(counts)


def fused_match_pairs(tokens, masks, weights, a, b, *, threshold: float,
                      n_real: int, chunk: int = DEFAULT_CHUNK,
                      use_kernel: bool = False):
    """Fused match over a device pair list -> compacted device buffers.

    Returns ``(ca, cb, count)``, all device-resident: the first ``count``
    lanes of ``ca``/``cb`` are the matched pairs in candidate order (the
    scatter is order-preserving), the tail is zeros. ``count`` is a
    device int32 scalar — nothing crosses to the host here.
    """
    assert isinstance(a, jax.Array) and isinstance(b, jax.Array)
    n = int(n_real)
    if n == 0:
        # device_put, not eager jnp.zeros: the latter transfers its fill
        # constant implicitly and trips transfer_guard("disallow")
        z = jax.device_put(np.zeros((0,), np.int32))
        return z, z, jax.device_put(np.int32(0))
    chunk = max(CHUNK_QUANTUM, min(chunk, round_up(n, CHUNK_QUANTUM)))
    assert chunk % _LANES == 0
    n_dev = jax.device_put(np.int32(n))
    parts = []
    for off in range(0, n, chunk):
        parts.append(_match_chunk(
            tokens, masks, a, b, jax.device_put(np.int32(off)), n_dev,
            chunk=chunk, weights=weights, threshold=threshold,
            use_kernel=use_kernel))
    if len(parts) == 1:
        aa, bb, matched, rank, counts = parts[0]
    else:
        aa, bb, matched, rank, counts = (
            jnp.concatenate([p[i] for p in parts]) for i in range(5))
    return compact_matched(aa, bb, matched, rank, counts)


def packed_host(ca, cb, count: int) -> np.ndarray:
    """Host uint64 ledger words ``a<<32|b`` from compacted device limbs."""
    hi = np.asarray(ca)[:count].astype(np.uint64)
    lo = np.asarray(cb)[:count].astype(np.uint64)
    return (hi << np.uint64(32)) | lo
