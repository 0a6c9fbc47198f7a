"""Public radix-sort ops over uint32 limb pairs (u64 sort words).

The pair engine's dedupe sorts 62-bit packed sort words held as uint32
``(hi, lo)`` limb pairs with the all-ones u64 as the invalid-lane
sentinel. ``sort_words`` is the one sort abstraction every dedupe call
site routes through:

- ``backend="comparator"``: XLA's 2-key ``lax.sort`` over the limb pair
  (the legacy path — bitonic comparator network on TPU).
- ``backend="radix"``: LSB radix sort, ``RADIX_BITS`` bits per pass.
  Each pass computes per-element stable positions (digit base + rank
  within digit) and applies ONE scatter; ``use_kernel=True`` runs the
  histogram/rank step in the Pallas kernel (``sort.radix_pass_pallas``,
  interpreted on the CPU backend only), otherwise an equivalent
  fused-jnp one-hot cumsum mirror. Both are bit-identical to the comparator path on any
  input (a sorted multiset is unique), which the parity suite asserts.

The pass count is STATIC: callers bound the significant word bits (e.g.
``kernels.pairs.radix_passes_for`` from the max record id in the 62-bit
layout) and pass ``n_passes = ceil(bits / RADIX_BITS)``. Skipping the
all-zero high digits of small keyspaces is where radix wins most.
Sentinel safety under truncated passes: the sentinel's every digit is
the maximum (0xF), and a valid word can never match it across the low 16
size bits (block size >= 2 keeps ``inv_size < 0xFFFF``), so sentinels
sort strictly last whenever ``n_passes >= 4`` — asserted below.

Functions here are NOT jitted (they inherit the caller's tracing, so the
shard-local distributed dedupe can call them inside ``shard_map``);
``radix_sort_words`` is the jitted convenience wrapper.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .sort import (MAX_PASSES, RADIX, RADIX_BITS, digit_of,  # noqa: F401
                   radix_pass_pallas)

SORT_BACKENDS = ("comparator", "radix")
_LANES = 128
_TILE = 8 * _LANES
# below this, sentinels can interleave with valid words (see module doc)
MIN_PASSES = 16 // RADIX_BITS


def _rank_pass_jnp(d: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable in-digit rank + per-digit counts via one-hot cumsum.

    The jnp mirror of the Pallas histogram/rank kernel, whole-array (no
    tiling): rank[i] = #(j < i with d[j] == d[i]). The one-hot is
    digit-major, (RADIX, n): with the RADIX axis minor, TPU layouts pad
    it to 128 lanes (8x the bytes), which at a 20M-slot pair budget no
    longer fits a 16 GB v5e.
    """
    onehot = (d[None, :]
              == jnp.arange(RADIX, dtype=d.dtype)[:, None]).astype(jnp.int32)
    incl = jnp.cumsum(onehot, axis=1)
    rank = jnp.take_along_axis(incl, d.astype(jnp.int32)[None, :],
                               axis=0)[0] - 1
    return rank, incl[:, -1]


def _scatter_pass(hi, lo, pos):
    n = hi.shape[0]
    out_hi = jnp.zeros((n,), hi.dtype).at[pos].set(hi)
    out_lo = jnp.zeros((n,), lo.dtype).at[pos].set(lo)
    return out_hi, out_lo


def _radix_sort_jnp(hi, lo, n_passes: int):
    for p in range(n_passes):
        d = digit_of(hi, lo, p)
        rank, counts = _rank_pass_jnp(d)
        base = jnp.cumsum(counts) - counts          # exclusive digit prefix
        hi, lo = _scatter_pass(hi, lo, base[d.astype(jnp.int32)] + rank)
    return hi, lo


def _radix_sort_kernel(hi, lo, n_passes: int):
    n = hi.shape[0]
    pad = (-n) % _TILE
    sentinel = jnp.uint32(0xFFFFFFFF)
    # pad lanes are sentinels: identical to real invalid-lane words, so
    # the stable sort keeps all sentinels (real + pad) contiguous at the
    # tail and the leading n elements ARE the sorted input
    hi = jnp.pad(hi, (0, pad), constant_values=sentinel)
    lo = jnp.pad(lo, (0, pad), constant_values=sentinel)
    n_tiles = (n + pad) // _TILE
    tile = jnp.arange(n + pad, dtype=jnp.int32) // _TILE
    for p in range(n_passes):
        rank, hist = radix_pass_pallas(hi.reshape(-1, _LANES),
                                       lo.reshape(-1, _LANES),
                                       p=p)
        hist = hist[:, 0, :RADIX]                    # (n_tiles, RADIX)
        # base[d, t] = all counts of digits < d + counts of d in tiles < t
        flat = hist.T.reshape(-1)                    # digit-major
        base = (jnp.cumsum(flat) - flat).reshape(RADIX, n_tiles)
        d = digit_of(hi, lo, p).astype(jnp.int32)
        pos = base[d, tile] + rank.reshape(-1)
        hi, lo = _scatter_pass(hi, lo, pos)
    return hi[:n], lo[:n]


def sort_words(hi: jnp.ndarray, lo: jnp.ndarray, *,
               backend: str = "comparator", n_passes: int = MAX_PASSES,
               use_kernel: bool = False):
    """Sort u64 words (uint32 limb pairs) ascending; the one dedupe sort.

    Not jitted — traces into the caller (jit or shard_map). ``n_passes``
    must cover every significant bit of the valid words (sentinels are
    safe from ``MIN_PASSES`` up, see module docstring); ``backend``,
    ``n_passes`` and ``use_kernel`` must be static under the
    caller's jit.
    """
    if backend not in SORT_BACKENDS:
        raise ValueError(
            f"sort backend must be one of {SORT_BACKENDS}, got {backend!r}")
    if backend == "comparator":
        return jax.lax.sort((hi, lo), num_keys=2)
    n_passes = int(n_passes)
    assert MIN_PASSES <= n_passes <= MAX_PASSES, n_passes
    if hi.shape[0] == 0:
        return hi, lo
    if use_kernel:
        return _radix_sort_kernel(hi, lo, n_passes)
    return _radix_sort_jnp(hi, lo, n_passes)


@functools.partial(jax.jit,
                   static_argnames=("n_passes", "use_kernel"))
def radix_sort_words(hi: jnp.ndarray, lo: jnp.ndarray, *,
                     n_passes: int = MAX_PASSES, use_kernel: bool = False):
    """Jitted standalone radix sort (bench / direct test entry point)."""
    return sort_words(hi, lo, backend="radix", n_passes=n_passes,
                      use_kernel=use_kernel)
