"""Sort-by-64-bit-key + segmented reductions.

The exact-counting stage of HDB (Algorithm 4) groups (record, key) entries
by blocking key and reduces each group to ``(count, XOR-of-rid-hashes)``.
On Spark that is a shuffle + reduceByKey; here it is a single
``lax.sort`` with the u64 key as a two-operand lexicographic sort key,
followed by O(n) segmented reductions — all dense, fixed-shape, TPU-friendly.

Invalid entries are padded with the u64 sentinel key so they sort to the
tail and fall out of every reduction naturally.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from . import u64
from .u64 import U64


def sort_by_key(key: U64, payloads: Sequence[jnp.ndarray]) -> Tuple[U64, list]:
    """Sort flat arrays by u64 key (lexicographic on (hi, lo))."""
    operands = (key[0], key[1], *payloads)
    out = jax.lax.sort(operands, num_keys=2, is_stable=False)
    return (out[0], out[1]), list(out[2:])


def segment_starts(key: U64) -> jnp.ndarray:
    """Bool mask marking the first element of each equal-key run.

    Input must be sorted by key. Sentinel runs are still marked; callers
    mask with ``~u64.is_sentinel``.
    """
    prev = (jnp.roll(key[0], 1), jnp.roll(key[1], 1))
    first = jnp.arange(key[0].shape[0]) == 0
    return first | ~u64.eq(key, prev)


def _cum_xor(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix XOR of a 1-D integer array.

    Written as the full-window ``reduce_window`` that ``lax.cumsum``
    itself lowers to on TPU (XLA rewrites it into a linear-work scan).
    ``lax.associative_scan`` unrolls into ~2 log2(n) levels of strided
    slices, which the TPU compiler took over 8 GB and minutes to compile
    at 15M entries.
    """
    n = x.shape[0]
    return jax.lax.reduce_window(x, jnp.zeros((), x.dtype), jax.lax.bitwise_xor,
                                 (n,), (1,), ((n - 1, 0),))


def segment_ids(starts: jnp.ndarray) -> jnp.ndarray:
    """Monotone segment id per element from a start mask."""
    return jnp.cumsum(starts.astype(jnp.int32)) - 1


def segment_counts(key: U64) -> jnp.ndarray:
    """Per-ELEMENT size of the segment it belongs to (sorted input).

    Computed via positions of starts: size = next_start_pos - my_start_pos.
    """
    n = key[0].shape[0]
    starts = segment_starts(key)
    idx = jnp.arange(n, dtype=jnp.int32)
    # position of my segment's start
    start_pos = jax.lax.cummax(jnp.where(starts, idx, 0))
    # position of my segment's end (exclusive): scan from the right
    end_pos = jax.lax.cummin(jnp.where(starts, idx, n), reverse=True)
    # end_pos currently holds the NEXT start among [i..); for elements of the
    # last run that's n via the init fill. But careful: scan-min from right of
    # start positions: for element i, min over j>=i of (starts[j] ? j : n)
    # gives my own start for the first element of a run. Shift to exclude self.
    nxt = jnp.concatenate([end_pos[1:], jnp.full((1,), n, jnp.int32)])
    seg_end = jnp.where(starts, nxt, end_pos)
    # For non-start elements, end_pos already excludes self's start (self is
    # not a start), i.e. it is the next run boundary.
    return seg_end - start_pos


def segment_xor(key: U64, value: U64) -> U64:
    """Per-ELEMENT XOR of `value` over the element's segment (sorted input).

    Uses the prefix-XOR trick: cumulative XOR c[i]; segment XOR over
    [s, e) = c[e-1] ^ c[s-1] (with c[-1] = 0).
    """
    n = key[0].shape[0]
    starts = segment_starts(key)
    idx = jnp.arange(n, dtype=jnp.int32)
    start_pos = jax.lax.cummax(jnp.where(starts, idx, 0))
    sizes = segment_counts(key)
    end_pos = start_pos + sizes - 1  # inclusive
    cum_hi = _cum_xor(value[0])
    cum_lo = _cum_xor(value[1])
    before = start_pos - 1
    pre_hi = jnp.where(before >= 0, cum_hi[jnp.maximum(before, 0)], 0).astype(jnp.uint32)
    pre_lo = jnp.where(before >= 0, cum_lo[jnp.maximum(before, 0)], 0).astype(jnp.uint32)
    return cum_hi[end_pos] ^ pre_hi, cum_lo[end_pos] ^ pre_lo


def unique_rows(key: U64, sizes: jnp.ndarray) -> jnp.ndarray:
    """Mask selecting one representative element per segment (the start)."""
    del sizes
    return segment_starts(key)


def compact(mask: jnp.ndarray, key: U64, payloads: Sequence[jnp.ndarray],
            fill_payload: int = 0) -> Tuple[U64, list, jnp.ndarray]:
    """Stable-compact masked entries to the array prefix.

    Entries where ``mask`` is False get sentinel keys / fill payloads and
    move to the tail. Returns (key, payloads, n_valid).
    """
    order = jnp.argsort(~mask, stable=True)
    khi = jnp.where(mask, key[0], jnp.uint32(0xFFFFFFFF))[order]
    klo = jnp.where(mask, key[1], jnp.uint32(0xFFFFFFFF))[order]
    outs = [jnp.where(mask, p, jnp.asarray(fill_payload, p.dtype))[order] for p in payloads]
    return (khi, klo), outs, jnp.sum(mask.astype(jnp.int32))


def searchsorted_u64(table: U64, query: U64) -> jnp.ndarray:
    """Vectorized lower-bound binary search of u64 queries in a sorted table.

    ``table`` is the paper's "broadcasted counts map": a sorted array of
    surviving over-sized keys all-gathered to every shard. Returns the
    insertion index; pair with an equality check at that index for lookups.
    """
    n = table[0].shape[0]
    # combine into sortable uint64-equivalent via float trick is lossy; do
    # manual binary search over (hi, lo).
    lo_idx = jnp.zeros(query[0].shape, jnp.int32)
    hi_idx = jnp.full(query[0].shape, n, jnp.int32)
    steps = max(1, math.ceil(math.log2(max(n, 2))) + 1)
    for _ in range(steps):
        mid = (lo_idx + hi_idx) // 2
        mid_c = jnp.clip(mid, 0, n - 1)
        mid_key = (table[0][mid_c], table[1][mid_c])
        go_right = u64.lt(mid_key, query) & (mid < hi_idx)
        lo_idx = jnp.where(go_right, mid + 1, lo_idx)
        hi_idx = jnp.where(go_right, hi_idx, jnp.minimum(hi_idx, mid))
    return lo_idx


def lookup_u64(table: U64, values: jnp.ndarray, query: U64,
               default) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sorted-table lookup: returns (found_mask, value_or_default)."""
    n = table[0].shape[0]
    idx = searchsorted_u64(table, query)
    idx_c = jnp.clip(idx, 0, n - 1)
    hit = (idx < n) & u64.eq((table[0][idx_c], table[1][idx_c]), query)
    val = jnp.where(hit, values[idx_c], jnp.asarray(default, values.dtype))
    return hit, val
