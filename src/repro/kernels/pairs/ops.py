"""Public jit'd pair-engine ops: chunked slot decode + sort-based dedupe.

The engine works in *pair-slot space*: a block of size ``n`` owns C(n, 2)
consecutive slots of the canonical enumeration (see ref.py). All device
work is fixed-shape:

- ``decode_chunk``: decode slots ``[base, base + C)`` (padded with an
  in-range validity mask) into (a, b, src_size). Because chunk slots are
  contiguous and the cumulative table is sorted, the slot -> block map is
  an O(B + C) scatter-of-block-starts + cumsum rather than a per-slot
  binary search (XLA's searchsorted costs ~17 gather rounds; the scan
  form measured ~30x cheaper on CPU). The triangular decode runs in the
  Pallas kernel (``use_kernel=True``) or an equivalent jnp integer
  binary search whose depth adapts to the layout's max block size
  (``search_steps_for``); the member gathers stay in XLA.
- ``decode_block_local``: same, but for pre-split (block, local) pairs —
  the sampling fallback splits its int64 slot draws host-side because
  global slot indices overflow int32 at scale.
- dedupe: "largest block wins" is ONE sort by the 62-bit word
  ``[a:23 | b:23 | (MAX-size):16]`` + a segment-start winner mask.
  ``pack_sort_words`` builds the word as a uint32 limb pair on device;
  ``dedupe_packed_host`` sorts it as a single u64 with ``np.sort``
  (numpy's radix-ish sort beats XLA CPU's comparator sort ~40x, and on
  CPU host==device memory so there is no transfer) while
  ``dedupe_packed_device`` / ``dedupe_device`` sort on device for real
  accelerators. All produce identical winners.

sort_backend contract: the on-device sort behind ``dedupe_device`` and
``dedupe_packed_device`` is selected by ``sort_backend`` —
``"comparator"`` is XLA's ``lax.sort`` (2-key over the packed limbs, or
the general-rid 3-key form), ``"radix"`` is the ``kernels.sort`` LSB
radix engine over the packed words (requires rids < 2**PACK_RID_BITS;
``radix_passes_for`` bounds the static pass count from the max rid, so
small keyspaces skip their constant high digits). Both orders are
bit-identical; the host driver in core/pairs.py resolves ``"auto"`` per
device backend and enforces the pack bound. Measured crossover on this
CPU container (~300k slots): comparator ~6x the jnp radix mirror (XLA
CPU serializes the per-pass scatter), so "auto" never picks radix on
CPU — the kernel targets accelerators, where the comparator's
O(log^2 n) cross-lane rounds are the documented bottleneck.

int32 contract (x64 stays off — see core/u64.py): record ids and the
materialized slot range must be < 2**31, block sizes <= MAX_BLOCK_N; the
host driver in core/pairs.py enforces both and falls back to numpy. The
packed dedupe additionally needs rids < 2**PACK_RID_BITS; the driver
falls back to ``dedupe_device`` beyond that.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .pairs import (tri_decode_pallas, search_steps_for,  # noqa: F401
                    MAX_BLOCK_N, MAX_SEARCH_STEPS)
from ..sort import ops as sort_ops

_INT32_MAX = 2**31 - 1
_LANES = 128
_TILE = 8 * _LANES  # minimum int32 tile footprint of the Pallas kernel

# 62-bit sort-word layout: [a: PACK_RID_BITS | b: PACK_RID_BITS | inv_size: 16]
PACK_RID_BITS = 23
_PACK_SIZE_BITS = 16
_SIZE_MASK = (1 << _PACK_SIZE_BITS) - 1  # == MAX_BLOCK_N
# splitmix64 seed of the pair-fingerprint shard routing (see ref.py mirror)
ROUTE_SEED = 0x9A12


def tri_decode_jnp(local: jnp.ndarray, n: jnp.ndarray,
                   steps: int = MAX_SEARCH_STEPS
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """jnp mirror of the Pallas kernel: exact uint32 binary search."""
    t = local.astype(jnp.uint32)
    n = n.astype(jnp.uint32)
    nm1 = n - 1
    lo = jnp.zeros_like(t)
    hi = jnp.where(n >= 2, n - 2, 0)
    for _ in range(steps):
        mid = (lo + hi + 1) // 2
        cum = mid * nm1 - (mid * (mid - 1)) // 2
        go_right = cum <= t
        lo = jnp.where(go_right, mid, lo)
        hi = jnp.where(go_right, hi, mid - 1)
    i = lo
    cum_i = i * nm1 - (i * (i - 1)) // 2
    j = t - cum_i + i + 1
    return i.astype(jnp.int32), j.astype(jnp.int32)


def _tri_decode(local, n, steps: int, use_kernel: bool):
    if not use_kernel:
        return tri_decode_jnp(local, n, steps)
    flat = local.reshape(-1)
    pad = (-flat.shape[0]) % _TILE
    lp = jnp.pad(flat, (0, pad)).reshape(-1, _LANES)
    np_ = jnp.pad(n.reshape(-1), (0, pad)).reshape(-1, _LANES)
    i, j = tri_decode_pallas(lp, np_, steps=steps)
    sl = slice(0, flat.shape[0])
    return i.reshape(-1)[sl].reshape(local.shape), j.reshape(-1)[sl].reshape(local.shape)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "steps", "use_kernel"))
def decode_chunk(cum: jnp.ndarray, start: jnp.ndarray, size: jnp.ndarray,
                 members: jnp.ndarray, base: jnp.ndarray, total: jnp.ndarray,
                 *, chunk: int, steps: int = MAX_SEARCH_STEPS,
                 use_kernel: bool = False):
    """Decode pair slots [base, base+chunk) -> (a, b, src_size, valid).

    All CSR inputs are int32 device arrays; ``cum`` has length B+1 with
    ``cum[B] == total``. Slots >= total are masked invalid.
    """
    offsets = jnp.arange(chunk, dtype=jnp.int32)
    # base-relative validity: `base + offset` wraps int32 in padding lanes
    # when total approaches 2**31, so compare offsets against the remaining
    # slot count instead of comparing (possibly wrapped) absolute slots.
    valid = offsets < (total - base)
    slots = base + offsets
    # slot -> block: scatter each block's chunk-relative start, cumsum.
    # block[k] = #(blocks with cum[b] <= base + k) - 1, clipped into range.
    start_pos = jnp.clip(cum[:-1] - base, 0, chunk)
    delta = jnp.zeros((chunk + 1,), jnp.int32).at[start_pos].add(1)
    block = jnp.cumsum(delta[:chunk]) - 1
    block = jnp.clip(block, 0, cum.shape[0] - 2)
    local = jnp.where(valid, slots, 0) - cum[block]
    n = size[block]
    i, j = _tri_decode(local, n, steps, use_kernel)
    s0 = start[block]
    a = members[s0 + i]
    b = members[s0 + j]
    return (jnp.minimum(a, b), jnp.maximum(a, b), n, valid)


@functools.partial(jax.jit, static_argnames=("steps", "use_kernel"))
def decode_block_local(start: jnp.ndarray, size: jnp.ndarray,
                       members: jnp.ndarray, block: jnp.ndarray,
                       local: jnp.ndarray, valid: jnp.ndarray,
                       *, steps: int = MAX_SEARCH_STEPS,
                       use_kernel: bool = False):
    """Decode pre-split (block, local) slots (sampling fallback path)."""
    block = jnp.clip(block, 0, size.shape[0] - 1)
    n = size[block]
    i, j = _tri_decode(local, n, steps, use_kernel)
    s0 = start[block]
    a = members[s0 + i]
    b = members[s0 + j]
    return (jnp.minimum(a, b), jnp.maximum(a, b), n, valid)


# ---------------------------------------------------------------------------
# Largest-block-wins dedupe: one sort + segment-start winner mask
# ---------------------------------------------------------------------------


@jax.jit
def pack_sort_words(a: jnp.ndarray, b: jnp.ndarray, src_size: jnp.ndarray,
                    valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(a, b, size) -> uint32 limb pair (hi, lo) of the 62-bit sort word.

    Word = (a << 39) | (b << 16) | (MAX_BLOCK_N - size): ascending word
    order is (a, b) ascending with size DESCENDING inside each (a, b) run,
    so after any u64 sort the first element of a run is the largest-block
    winner. Invalid lanes become the all-ones sentinel (> any valid word).
    Requires a, b < 2**PACK_RID_BITS and size <= MAX_BLOCK_N.
    """
    au = a.astype(jnp.uint32)
    bu = b.astype(jnp.uint32)
    inv = (_SIZE_MASK - jnp.clip(src_size, 0, _SIZE_MASK)).astype(jnp.uint32)
    hi = (au << 7) | (bu >> 16)
    lo = (bu << 16) | inv
    sentinel = jnp.uint32(0xFFFFFFFF)
    return (jnp.where(valid, hi, sentinel), jnp.where(valid, lo, sentinel))


def dedupe_words_host(w: np.ndarray) -> np.ndarray:
    """u64 sort words -> sorted winner words (largest-block-wins).

    One ``np.sort``, sentinel truncation, and a first-of-(a, b)-run mask;
    the host mirror of ``dedupe_packed_device``. Shared by the
    single-device CPU driver and the per-shard buckets of the routed
    distributed dedupe.
    """
    w = np.sort(w)
    w = w[: np.searchsorted(w, np.uint64(1) << np.uint64(62))]  # drop sentinels
    if len(w) == 0:
        return w
    run = w >> np.uint64(_PACK_SIZE_BITS)  # the (a, b) part
    return w[np.concatenate([[True], run[1:] != run[:-1]])]


def dedupe_packed_host(hi: np.ndarray, lo: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host sort of packed words -> compacted (a, b, src_size) winners.

    ``np.sort`` on the single u64 word replaces XLA CPU's comparator
    sort; used by the driver when running on the CPU backend (host memory
    IS device memory there, so this costs no extra transfer).
    """
    w = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    return unpack_words_host(dedupe_words_host(w))


def pair_route_owner(a: jnp.ndarray, b: jnp.ndarray, valid: jnp.ndarray,
                     n_shards: int) -> jnp.ndarray:
    """Owning shard of pair (a, b) for the fingerprint-routed dedupe.

    The fingerprint hashes ONLY the 46-bit run id ``(a << 23) | b`` — the
    sort word WITHOUT its size bits — so every occurrence of a pair lands
    on the same shard no matter which block produced it (that invariant
    is what makes shard-local dedupe globally correct). Bit-exact numpy
    mirror: ``ref.np_pair_route_owner``. Invalid lanes get ``n_shards``
    (the route_buckets drop sentinel). Requires a, b < 2**PACK_RID_BITS.
    """
    from ...core import hashing  # local import: core.pairs imports this module
    au = a.astype(jnp.uint32)
    bu = b.astype(jnp.uint32)
    run_hi = au >> 9                              # (a << 23 | b) >> 32
    run_lo = ((au & 0x1FF) << 23) | bu            # low 32 bits of the run id
    _, h_lo = hashing.hash_u64((run_hi, run_lo), seed=ROUTE_SEED)
    owner = (h_lo % jnp.uint32(n_shards)).astype(jnp.int32)
    return jnp.where(valid, owner, jnp.int32(n_shards))


def radix_passes_for(max_rid: int) -> int:
    """Static radix pass count covering the 62-bit word for rids <= max_rid.

    The word's topmost varying bit is ``39 + bitlength(max a)`` (the
    a-field starts at bit 39); digits above it are constant zero on valid
    words and all-ones on the sentinel, which still sorts last (see
    ``kernels.sort.ops``). Clamped to at least the 16 size bits.
    """
    bits = _PACK_SIZE_BITS + PACK_RID_BITS + max(1, int(max_rid).bit_length())
    n = -(-bits // sort_ops.RADIX_BITS)
    return max(sort_ops.MIN_PASSES, min(sort_ops.MAX_PASSES, n))


def dedupe_packed_device(hi: jnp.ndarray, lo: jnp.ndarray,
                         sort_backend: str = "comparator",
                         n_passes: int = sort_ops.MAX_PASSES,
                         use_kernel: bool = False):
    """Shard-local dedupe of packed sort words: one sort + winner mask.

    The device mirror of ``dedupe_packed_host`` for use INSIDE shard_map
    (jit-free so it inherits the caller's tracing): sorts the uint32 limb
    pair via ``kernels.sort.sort_words`` (``sort_backend="comparator"``
    is the 2-key ``lax.sort``, ``"radix"`` the LSB radix engine —
    identical order to the u64 word either way) and marks the first
    element of each (a, b) run. Sentinel (all-ones) lanes sort to the
    tail and are never winners. Returns (hi_sorted, lo_sorted,
    winner_mask).
    """
    shi, slo = sort_ops.sort_words(hi, lo, backend=sort_backend,
                                   n_passes=n_passes, use_kernel=use_kernel)
    # run id = word >> 16 == (a << 23) | b: equal iff hi AND lo>>16 match
    srun = slo >> 16
    live = ~((shi == jnp.uint32(0xFFFFFFFF)) & (slo == jnp.uint32(0xFFFFFFFF)))
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (shi[1:] != shi[:-1]) | (srun[1:] != srun[:-1])])
    return shi, slo, live & first


def unpack_words_host(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """u64 sort words -> (a, b, src_size) int64 triplets (host side)."""
    a = (w >> np.uint64(39)).astype(np.int64)
    b = ((w >> np.uint64(16)) & np.uint64((1 << PACK_RID_BITS) - 1)).astype(np.int64)
    s = (np.uint64(_SIZE_MASK) - (w & np.uint64(_SIZE_MASK))).astype(np.int64)
    return a, b, s


@functools.partial(
    jax.jit, static_argnames=("sort_backend", "n_passes", "use_kernel"))
def dedupe_device(a: jnp.ndarray, b: jnp.ndarray, src_size: jnp.ndarray,
                  valid: jnp.ndarray, *, sort_backend: str = "comparator",
                  n_passes: int = sort_ops.MAX_PASSES,
                  use_kernel: bool = False):
    """Device sort (a, b, size desc); mark each pair's largest-block winner.

    ``sort_backend="comparator"`` is the general-rid path (no
    PACK_RID_BITS bound): a 3-key ``lax.sort``. ``"radix"`` re-expresses
    the same order over the packed 62-bit sort words and runs the
    ``kernels.sort`` radix engine (caller must guarantee rids <
    2**PACK_RID_BITS — the core/pairs.py driver checks ``_packable``).
    Returns (a_sorted, b_sorted, size_sorted, winner_mask); invalid lanes
    sort to the tail and are never winners. Host compacts by the mask.
    """
    if sort_backend == "radix":
        hi, lo = pack_sort_words(a, b, src_size, valid)
        shi, slo, winner = dedupe_packed_device(
            hi, lo, sort_backend="radix", n_passes=n_passes,
            use_kernel=use_kernel)
        # unpack the winner words back to int32 triplets on device
        ua = (shi >> 7).astype(jnp.int32)
        ub = (((shi & jnp.uint32(0x7F)) << 16) | (slo >> 16)).astype(jnp.int32)
        us = (jnp.uint32(_SIZE_MASK) - (slo & jnp.uint32(_SIZE_MASK))
              ).astype(jnp.int32)
        return ua, ub, us, winner
    av = jnp.where(valid, a, _INT32_MAX)
    bv = jnp.where(valid, b, _INT32_MAX)
    skey = _INT32_MAX - jnp.where(valid, src_size, 0)  # ascending = size desc
    sa, sb, ss = jax.lax.sort((av, bv, skey), num_keys=3)
    live = ~((sa == _INT32_MAX) & (sb == _INT32_MAX))
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (sa[1:] != sa[:-1]) | (sb[1:] != sb[:-1])])
    return sa, sb, _INT32_MAX - ss, live & first
