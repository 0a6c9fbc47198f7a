"""Pallas TPU kernel for one LSB radix-sort digit pass.

The pair engine's dedupe is ONE sort of 62-bit packed sort words (uint32
limb pairs, see ``kernels/pairs/ops.py``); on real accelerators it was
XLA's comparator ``lax.sort`` — O(n log^2 n) bitonic rounds of full
cross-lane shuffles. Radix-sorting the word in ``RADIX_BITS``-wide digits
replaces that with O(passes) streaming rounds: per pass, each element
needs only its digit's global rank, which splits into

    rank = global_base[digit]                (exclusive digit prefix sum)
         + tile_base[digit, tile]            (exclusive per-tile prefix)
         + in_tile_rank                      (rank within the tile)

This kernel computes the per-tile histogram and the in-tile rank in one
HBM read of the tile. Mosaic has no ``cumsum`` lowering, so the in-row
prefix sums are one MXU matmul per digit against a (128, 128) triangular
ones matrix (``lane_prefix``: exact, the counts stay far below bf16's
integer range) and the across-row prefix is a static unroll over the
tile's rows. The tiny (digits x tiles) base table and the final position
gather/scatter are memory-bound data movement and stay in XLA (same
split as the pairs tri-decode kernel: compute in Pallas, gathers in XLA).

Digit extraction never straddles a limb because ``RADIX_BITS`` divides
32; the in-tile element order is row-major over the (block_rows, 128)
tile, matching the flattened order the XLA side scatters with.

Grid: (rows / block_rows,) over a (rows, 128) lane layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ... import kernels

# Digit width. 4 bits => RADIX 16: the kernel statically unrolls RADIX
# per-digit rounds (16 is cheap; 256 would not be), and the jnp mirror's
# (n, RADIX) one-hot rank transient stays small.
RADIX_BITS = 4
RADIX = 1 << RADIX_BITS
# Full u64 word coverage (sentinel = all-ones sorts last).
MAX_PASSES = 64 // RADIX_BITS
_LANES = 128


def digit_of(hi: jnp.ndarray, lo: jnp.ndarray, p: int) -> jnp.ndarray:
    """Digit ``p`` (little-endian) of the u64 word ``hi << 32 | lo``.

    ``RADIX_BITS`` divides 32, so a digit never straddles the limbs.
    Shift/mask are python ints (weak-typed): the kernel must not capture
    array constants.
    """
    shift = p * RADIX_BITS
    if shift < 32:
        return (lo >> shift) & (RADIX - 1)
    return (hi >> (shift - 32)) & (RADIX - 1)


def lane_prefix(m: jnp.ndarray, *, inclusive_of_all: bool = False
                ) -> jnp.ndarray:
    """Per-row lane sums of an (R, 128) int32 0/1 tile, on the MXU.

    Returns the exclusive prefix sum along lanes, or with
    ``inclusive_of_all`` each row's total broadcast to every lane. Both
    are one bf16 matmul against a (128, 128) ones pattern built from
    iota; 0/1 inputs and sums <= 128 are exact in bf16 x bf16 -> f32.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    ones = (row >= 0) if inclusive_of_all else (row < col)
    out = jnp.dot(m.astype(jnp.bfloat16), ones.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)


def _radix_pass_kernel(hi_ref, lo_ref, rank_ref, hist_ref, *, p: int):
    d = digit_of(hi_ref[...], lo_ref[...], p)       # (BR, 128) uint32
    n_rows = d.shape[0]
    row_id = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    lane_id = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    rank = jnp.zeros(d.shape, jnp.int32)
    hist = jnp.zeros((1, _LANES), jnp.int32)
    for k in range(RADIX):                          # static unroll
        m = (d == jnp.uint32(k)).astype(jnp.int32)
        within = lane_prefix(m)                     # exclusive, in-row
        row_tot = lane_prefix(m, inclusive_of_all=True)   # (BR, 128)
        rows_before = jnp.zeros(d.shape, jnp.int32)
        for r in range(n_rows - 1):                 # exclusive, across rows
            rows_before = rows_before + jnp.where(
                row_id > r, row_tot[r:r + 1, :], 0)
        rank = jnp.where(m > 0, rows_before + within, rank)
        tile_tot = jnp.sum(row_tot, axis=0, keepdims=True)  # (1, 128)
        hist = jnp.where(lane_id == k, tile_tot, hist)
    rank_ref[...] = rank
    hist_ref[...] = hist


def radix_pass_pallas(hi: jnp.ndarray, lo: jnp.ndarray, *, p: int,
                      block_rows: int = 8):
    """(R, 128) uint32 limb pair -> (in-tile rank, per-tile histogram).

    Returns ``rank`` of shape (R, 128) int32 — each element's rank among
    same-digit elements earlier (row-major) in its tile — and ``hist`` of
    shape (n_tiles, 1, 128) int32 with the tile's per-digit counts in
    lanes [0, RADIX) and zeros beyond (callers slice
    ``hist[:, 0, :RADIX]``). The unit middle axis keeps every block's
    last two dims equal to the array's, as Mosaic requires of blocks
    smaller than (8, 128). Interpreted iff ``kernels.use_interpreter()``.
    """
    rows, lanes = hi.shape
    assert lanes == 128 and rows % block_rows == 0, (rows, lanes)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, 128), lambda r: (r, 0))
    hist_spec = pl.BlockSpec((pl.Squeezed(), 1, 128), lambda r: (r, 0, 0))
    return pl.pallas_call(
        functools.partial(_radix_pass_kernel, p=p),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, hist_spec),
        out_shape=(jax.ShapeDtypeStruct((rows, 128), jnp.int32),
                   jax.ShapeDtypeStruct((grid[0], 1, 128), jnp.int32)),
        interpret=kernels.use_interpreter(),
    )(hi, lo)
