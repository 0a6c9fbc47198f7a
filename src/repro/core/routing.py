"""Shard routing primitives shared by the distributed HDB step and the
fingerprint-routed pair dedupe.

Every distributed exchange in this codebase follows the same HDB pattern
(paper §4): compute an int32 ``owner`` shard per entry, scatter entries
into fixed-capacity per-destination buckets (``route_buckets``), and swap
the buckets with ONE ``all_to_all`` (``exchange``). Fixed capacities keep
every buffer shape static under jit; overflows are *counted*, never
silent — callers decide whether to warn (HDB accepts lossy routing of a
shrinking survivor set) or fall back (pair dedupe must stay exact).

``linear_shard_index`` linearizes a multi-axis mesh position into the
flat shard id used by ``owner % n_shards`` routing. Axis sizes are taken
from the mesh *statically*: they are compile-time constants, and the
static ``int`` keeps the linearization free of traced arithmetic (the
traced ``jax.lax.axis_size`` would give the same value).

Ownership seeds are shared constants: ``KEY_OWNER_SEED`` partitions
64-bit block keys (the HDB exact-count exchange AND the sharded
streaming ``BlockStore``'s key-table/CMS/CSR slices — same partition, so
a batch shard and a streaming shard agree on who owns a key) and
``REP_OWNER_SEED`` partitions pair packs (the streaming pair ledger).
``np_owner_u64`` is the bit-exact host mirror of the device rule
(low 32 hash bits mod n_shards), letting host-resident streaming state
route without staging keys through the device.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import hashing

# Shared fingerprint-routing seeds (see module doc).
KEY_OWNER_SEED = 0xA110
REP_OWNER_SEED = 0xDED0

# Group ranks come from a one-hot running count (O(n * n_shards)
# vectorized adds; beats XLA's comparator argsort by a wide margin on CPU)
# only while the (n, n_shards+1) transient stays small; big routes (the
# HDB key exchange at production L) and wide meshes (> 64 shards) keep
# the O(n log n) argsort path — ``route_buckets`` is valid for ANY
# n_shards, the constants below only pick the rank strategy.
_ONEHOT_RANK_MAX_SHARDS = 64
_ONEHOT_RANK_MAX_ELEMS = 1 << 23  # int32 transient cap: 32 MiB


def np_owner_u64(x: np.ndarray, n_shards: int,
                 seed: int = KEY_OWNER_SEED) -> np.ndarray:
    """int32 owner shard per packed u64 value (host mirror).

    Bit-exact with the device rule used by ``core.distributed``:
    ``(low 32 bits of hash_u64(x, seed)) % n_shards``.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    h = hashing.np_hash_u64_vec(np.asarray(x, np.uint64), seed=seed)
    return ((h & np.uint64(0xFFFFFFFF))
            % np.uint64(n_shards)).astype(np.int32)


def linear_shard_index(mesh: Mesh, axis_names: Sequence[str]) -> jnp.ndarray:
    """Flat shard id of the calling device inside a shard_mapped fn.

    Row-major over ``axis_names``: consistent with how ``all_to_all`` over
    the same axis tuple orders its tiles, so ``owner == linear id`` routes
    to the right device.
    """
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * int(mesh.shape[name]) + jax.lax.axis_index(name)
    return idx


def route_buckets(khi, klo, payloads, owner, n_shards: int, cap: int):
    """Scatter entries into per-destination buckets (pre-``all_to_all``).

    Args:
      khi, klo: uint32 limb pair of each entry's 64-bit key.
      payloads: extra per-entry arrays routed alongside the key.
      owner: int32 destination shard per entry; use ``n_shards`` to drop.
      cap: per-destination bucket capacity (static).

    Returns ``(bhi, blo, bucketed_payloads, overflow_count)`` with bucket
    shape ``(n_shards, cap)``; absent slots carry all-ones sentinel keys
    and zero payloads. ``overflow_count`` is the number of live entries
    that exceeded their destination bucket's capacity (dropped).
    """
    n = owner.shape[0]
    if (n_shards <= _ONEHOT_RANK_MAX_SHARDS
            and n * (n_shards + 1) <= _ONEHOT_RANK_MAX_ELEMS):
        # rank within destination group via one-hot running count:
        # rank[i] = #(j < i : owner[j] == owner[i])
        onehot = (owner[:, None]
                  == jnp.arange(n_shards + 1, dtype=owner.dtype)[None, :])
        rank = jnp.take_along_axis(
            jnp.cumsum(onehot.astype(jnp.int32), axis=0),
            jnp.clip(owner, 0, n_shards)[:, None], axis=1)[:, 0] - 1
    else:
        # general path: sort by owner; rank = position among same-owner
        order = jnp.argsort(owner)  # stable not required; ranks only need uniqueness
        owner_s = owner[order]
        rank_sorted = jnp.arange(n, dtype=jnp.int32) - jnp.searchsorted(
            owner_s, owner_s, side="left").astype(jnp.int32)
        rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    pos = owner * cap + rank
    ok = (owner < n_shards) & (rank < cap)
    overflow = jnp.sum(((owner < n_shards) & (rank >= cap)).astype(jnp.int32))
    flat_pos = jnp.where(ok, pos, n_shards * cap)  # OOB -> dropped

    def scatter(x, fill):
        buf = jnp.full((n_shards * cap,), fill, x.dtype)
        return buf.at[flat_pos].set(x, mode="drop").reshape(n_shards, cap)

    bhi = scatter(khi, jnp.uint32(0xFFFFFFFF))
    blo = scatter(klo, jnp.uint32(0xFFFFFFFF))
    bpl = [scatter(p, jnp.asarray(0, p.dtype)) for p in payloads]
    return bhi, blo, bpl, overflow


def exchange(axis_names: Sequence[str], *buckets) -> Tuple[jnp.ndarray, ...]:
    """all_to_all each ``(n_shards, cap)`` bucket over the mesh axes.

    After the exchange, row ``p`` of each returned array is the bucket
    this shard received from source shard ``p``.
    """
    out: List[jnp.ndarray] = []
    for b in buckets:
        out.append(jax.lax.all_to_all(b, tuple(axis_names), 0, 0, tiled=True))
    return tuple(out)
