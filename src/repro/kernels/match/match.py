"""Pallas TPU kernel for fused pair matching: score + threshold + compaction ranks.

Pairwise matching (paper §1 stage 3) consumes the pair engine's candidate
buffers and must emit only the *matched* subset to graph partitioning.
The host path materializes a full per-pair score vector and a boolean
mask on the host — a device->host->device round trip of the whole pair
list per call. This kernel fuses the three steps so the matched pair set
never leaves the device:

1. **score**: per-column weighted Jaccard over the gathered token rows —
   for each candidate lane, ``T x T`` token-equality rounds per column,
   all in-register VPU compares/selects with no cross-lane traffic,
2. **threshold**: ``score >= threshold`` with the weights and threshold
   baked in as compile-time constants (one compile per MatcherConfig),
3. **compaction ranks**: each lane's exclusive prefix-sum rank among the
   matched lanes of its tile plus the per-tile matched count — the same
   histogram/rank split as the radix-sort kernel (``kernels/sort``), so
   the only XLA-side work left is the tiny cross-tile base cumsum and
   ONE scatter into the packed output buffer (memory-bound data
   movement, which stays in XLA by this repo's kernel convention; see
   ``ops.compact_matched``). The in-tile prefix sum is the radix
   kernel's triangular-matmul ``lane_prefix`` (Mosaic has no ``cumsum``).

Member gathers (``tokens[a]``) also stay in XLA — the kernel reads each
pair's already-gathered ``(C, T)`` token stack from HBM exactly once.
Token/mask stacks arrive transposed to ``(C, T, lanes)`` so the lane
dimension is the pair axis; ``T`` is padded to a sublane multiple with
``mask == 0`` rows, which contribute nothing to any Jaccard term.

Per-tile (1, 128) lane vectors (``valid`` in; ``matched``/``rank``/
``count`` out) travel as (tiles, 1, 128) arrays so that every block's
last two dims equal the array's — Mosaic refuses (1, 128) blocks of a
2-D array.

Grid: (pairs / 128,) over (C, T, 128) column blocks per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ... import kernels
from ..sort.sort import lane_prefix

_LANES = 128
# sublane granularity the token axis is padded to (float32/int32 tiling)
SUBLANES = 8


def _match_kernel(ta_ref, ma_ref, tb_ref, mb_ref, valid_ref,
                  matched_ref, rank_ref, count_ref, *,
                  weights: tuple, threshold: float):
    """One 128-pair tile: weighted-Jaccard score -> matched/rank/count.

    The float sequence (int32 true-divide, ``w * j`` accumulation in
    weight order, ``total / max(norm, 1e-6)``) replicates
    ``ops.score_lanes_jnp`` op for op, so kernel and mirror thresholds
    are bit-identical.
    """
    total = jnp.zeros((1, _LANES), jnp.float32)
    norm = jnp.zeros((1, _LANES), jnp.float32)
    for c in range(len(weights)):
        ta = ta_ref[c]              # (T, 128) uint32 tokens of side a
        ma = ma_ref[c] != 0         # (T, 128) token-validity masks
        tb = tb_ref[c]
        mb = mb_ref[c] != 0
        inter = jnp.zeros((1, _LANES), jnp.int32)
        for i in range(ta.shape[0]):        # static unroll over a-tokens
            hit = (tb == ta[i:i + 1, :]) & mb                 # (T, 128)
            anyhit = jnp.any(hit, axis=0, keepdims=True) & ma[i:i + 1, :]
            inter = inter + anyhit.astype(jnp.int32)
        na = jnp.sum(ma.astype(jnp.int32), axis=0, keepdims=True)
        nb = jnp.sum(mb.astype(jnp.int32), axis=0, keepdims=True)
        union = na + nb - inter
        both = (na > 0) & (nb > 0)
        jac = jnp.where(both, inter / jnp.maximum(union, 1), 0.0)
        w = weights[c]              # python float: weak-typed constant
        total = total + w * jac
        norm = norm + jnp.where(both, w, 0.0)
    score = jnp.where(norm > 0, total / jnp.maximum(norm, 1e-6), 0.0)
    matched = (valid_ref[...] != 0) & (score >= threshold)
    mi = matched.astype(jnp.int32)
    # the MXU wants a full sublane tile: prefix 8 copies, keep row 0
    mi8 = jnp.broadcast_to(mi, (SUBLANES, _LANES))
    lane_id = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    tot = lane_prefix(mi8, inclusive_of_all=True)[0:1, :]
    matched_ref[...] = mi
    rank_ref[...] = lane_prefix(mi8)[0:1, :]        # exclusive in-tile rank
    count_ref[...] = jnp.where(lane_id == 0, tot, 0)


def match_score_pallas(ta: jnp.ndarray, ma: jnp.ndarray, tb: jnp.ndarray,
                       mb: jnp.ndarray, valid: jnp.ndarray, *,
                       weights: tuple, threshold: float):
    """(C, T, P) token/mask stacks + (P/128, 1, 128) valid -> fused match.

    ``ta``/``tb`` are uint32 token stacks, ``ma``/``mb``/``valid`` int32
    0/1 masks. P must be a multiple of 128 and T of ``SUBLANES`` (ops.py
    pads). Returns int32 ``(matched, rank, count)`` each shaped
    (P/128, 1, 128); ``count`` carries the tile's matched total in lane 0
    and zeros beyond (the same lane-padding convention as the radix
    kernel's histogram output). Interpreted iff
    ``kernels.use_interpreter()``.
    """
    n_cols, t_pad, n_pairs = ta.shape
    assert n_pairs % _LANES == 0 and t_pad % SUBLANES == 0, ta.shape
    grid = (n_pairs // _LANES,)
    col_spec = pl.BlockSpec((n_cols, t_pad, _LANES), lambda g: (0, 0, g))
    lane_spec = pl.BlockSpec((pl.Squeezed(), 1, _LANES), lambda g: (g, 0, 0))
    out = jax.ShapeDtypeStruct((grid[0], 1, _LANES), jnp.int32)
    return pl.pallas_call(
        functools.partial(_match_kernel, weights=weights,
                          threshold=threshold),
        grid=grid,
        in_specs=[col_spec, col_spec, col_spec, col_spec, lane_spec],
        out_specs=(lane_spec, lane_spec, lane_spec),
        out_shape=(out, out, out),
        interpret=kernels.use_interpreter(),
    )(ta, ma, tb, mb, valid)
