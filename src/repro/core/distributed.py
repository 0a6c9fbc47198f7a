"""Distributed HDB: the paper's Spark dataflow mapped onto a TPU pod mesh.

Sharding: records (and their key rows) are sharded over the mesh's
data-like axes; the model axis of the production mesh simply joins the
record sharding (blocking has no "model" dimension). Per iteration:

  - CMS:     built per shard, merged with ONE psum (linear sketch).
  - Exact:   surviving entries hash-route to an owner shard with ONE
             all_to_all; owner computes exact counts + XOR membership
             fingerprints with a local sort (keys are fully local after
             routing).
  - Dedupe:  block representatives hash-route BY FINGERPRINT with a second
             (much smaller) all_to_all; every over-sized key, with its
             size where it survives the dedupe, is all-gathered as the
             paper's "broadcasted counts map", so shards recover
             CMS-over-counted right-sized blocks as in Algorithm 4 (key
             not in the map => right-sized; in it with a size =>
             over-sized; in it without => duplicate, dropped). The paper
             keeps the over-sized keys in a Bloom filter; the map holds
             at most n_shards * rep_capacity_per_shard keys, so it is
             exact here at no cost beyond the survivor map.
  - Intersect: purely record-local (Alg. 2), no communication.

Record payloads never move; the only shuffled bytes are 8-byte key hashes
and int32 sizes of the *shrinking* survivor set — the paper's minimal-
data-movement thesis, with fixed-capacity buffers instead of dynamic
shuffles (capacity overflows are counted, never silent). The shared
bucketing/exchange primitives live in ``core.routing``.

Pair materialization (§3.1) reuses the same dataflow:
``dedupe_pairs_distributed`` shards the canonical pair-slot space, packs
every decoded pair into the kernels' 62-bit sort word, and hash-routes it
BY PAIR FINGERPRINT (splitmix64 of the word's (a, b) bits) with one
all_to_all per round, so the largest-block-wins sort-dedupe is
shard-local and no device ever materializes the full pair set.

Routed-dedupe contract:
  - Bit-identical PairSets to single-device ``core.pairs.dedupe_pairs``
    on every mesh shape (the fingerprint partitions pairs, per-shard
    winners are disjoint, and the budget-exceeded path decodes the same
    seeded global slot sample as every other backend).
  - Per-shard peak pair-buffer: n_rounds * n_shards * cap words with
    cap = ceil(chunk_per_shard / n_shards * route_slack), i.e.
    ~ceil(total_slots / n_shards) * route_slack — the distributed
    engine's memory knob.
  - ``route_slack`` tuning: slack s bounds the tolerated per-destination
    skew of the pair-fingerprint hash within one chunk; splitmix64 is
    close to uniform, so bucket occupancy is ~Binomial(chunk, 1/n_shards)
    and the default s=2.0 puts overflow many sigma out for chunks >= 4k.
    Raise it (cost: linearly larger buckets) only if the driver warns —
    overflow triggers a lossless fallback to the single-device engine,
    never silent pair drops. Small chunks with few slots per shard
    amplify relative skew; prefer fewer, larger rounds.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import warnings
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import hashing, routing, segments, sketches, u64
from .. import obs
from ..distributed import sharding
from .hdb import (BlockingResult, HDBConfig, INT32_MAX, IterationStats,
                  RepCapacityWarning, intersect_keys,
                  pad_to_intersect_width)
from .routing import route_buckets as _route

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Fixed buffer capacities for the distributed exchanges."""

    route_slack: float = 2.0       # all_to_all bucket slack over the mean
    rep_capacity_per_shard: int = 1 << 14


@dataclasses.dataclass(frozen=True)
class Exchange:
    """What one ``all_to_all`` of a sharded run carried, as counted on the
    device: ``sent[s, d]`` live entries that shard ``s`` routed to shard
    ``d``, before each destination bucket's ``cap`` (a count over ``cap``
    overflowed). ``stage`` is ``"hdb"`` for the exact-count exchange of
    block-key entries (u64 key + int32 record id), ``index`` its
    iteration; ``"pairs"`` for a round of 62-bit pair sort words."""

    stage: str
    index: int
    sent: np.ndarray    # (n_shards, n_shards) int
    cap: int

    @property
    def fill(self) -> int:
        """The fullest destination bucket."""
        return int(self.sent.max())

    @property
    def off_chip(self) -> np.ndarray:
        """Live entries each shard sent to the others."""
        return self.sent.sum(axis=1) - np.diag(self.sent)

    def span_args(self) -> dict:
        """``fill``, ``cap`` and one ``off_chip_<shard>`` count per shard,
        as program-span arguments."""
        return dict(fill=self.fill, cap=self.cap,
                    **{f"off_chip_{i}": int(v)
                       for i, v in enumerate(self.off_chip)})


def _route_cap(n_entries: int, n_shards: int, slack: float) -> int:
    """Per-destination bucket capacity for ``n_entries`` routed lanes."""
    return int(np.ceil(n_entries / n_shards * slack))


def _sent_matrix(owner, n_shards: int, axes):
    """Inside a shard_mapped body: every shard's live entries per
    destination (``owner == n_shards`` is dropped), all-gathered into the
    ``(n_shards, n_shards)`` ``Exchange.sent`` matrix."""
    sent = jnp.stack([jnp.sum((owner == d).astype(jnp.int32))
                      for d in range(n_shards)])
    return jax.lax.all_gather(sent, axes)


def _sort_reps(xhi, xlo, sz, khi, klo, live):
    """Representative lanes in (fingerprint, size, key) order, padding
    last: three stable sorts of a lane index, least significant key
    first. No two lanes share a key (each block has one owner and one
    representative; padding lanes are all alike), so this is the order
    of one 5-key ``lax.sort`` of the six arrays. Compiled for a described
    v5e 2x2 at 131,200 lanes on an 8-core CPU host, beside other
    compiles, that sort took 211 s and these three 51 s together."""
    order = jnp.arange(xhi.shape[0], dtype=jnp.int32)
    _, _, order = jax.lax.sort((khi, klo, order), num_keys=2, is_stable=True)
    _, order = jax.lax.sort((sz[order], order), num_keys=1, is_stable=True)
    _, _, order = jax.lax.sort((xhi[order], xlo[order], order), num_keys=2,
                               is_stable=True)
    return tuple(x[order] for x in (xhi, xlo, sz, khi, klo, live))


def make_hdb_step(cfg: HDBConfig, mesh: Mesh,
                  axis_names: Sequence[str],
                  dist: DistConfig = DistConfig()):
    """Build the jitted, shard_mapped distributed HDB iteration.

    Thin wrapper that normalizes ``axis_names`` so the lru-cached builder
    keys on hashable statics only — repeated drivers over the same mesh
    geometry reuse the compiled step instead of re-jitting per call (the
    repro.analysis R005 hazard; the routed-dedupe builders below already
    worked this way).
    """
    return _make_hdb_step_cached(cfg, mesh, tuple(axis_names), dist)


@functools.lru_cache(maxsize=16)
def _make_hdb_step_cached(cfg: HDBConfig, mesh: Mesh,
                          axis_names: Tuple[str, ...],
                          dist: DistConfig):
    n_shards = sharding.axis_size(mesh, tuple(axis_names))
    axes = tuple(axis_names)

    def local_step(keys_packed, valid, psize):
        n_loc, k = valid.shape
        shard = routing.linear_shard_index(mesh, axes)
        rid0 = shard * jnp.int32(n_loc)
        key = (keys_packed[..., 0], keys_packed[..., 1])

        # ---- rough over-size detection (Alg. 3), CMS merged via psum ----
        flat_key = (key[0].reshape(-1), key[1].reshape(-1))
        flat_valid = valid.reshape(-1)
        cms = sketches.cms_build(cfg.cms, flat_key, flat_valid)
        cms = jax.lax.psum(cms, axes)
        s = sketches.cms_query(cfg.cms, cms, flat_key).reshape(valid.shape)
        right_cms = valid & (s <= cfg.max_block_size)
        progress = s.astype(jnp.float32) <= cfg.max_similarity * psize.astype(jnp.float32)
        keep = valid & ~right_cms & progress
        dropped_sim = valid & ~right_cms & ~progress

        # ---- exact count: route surviving entries to key-owner shards ----
        L = n_loc * k
        flat_keep = keep.reshape(-1)
        khi = jnp.where(flat_keep, flat_key[0], jnp.uint32(0xFFFFFFFF))
        klo = jnp.where(flat_keep, flat_key[1], jnp.uint32(0xFFFFFFFF))
        rid = rid0 + jnp.broadcast_to(
            jnp.arange(n_loc, dtype=jnp.int32)[:, None], (n_loc, k)).reshape(-1)
        _, owner_h = hashing.hash_u64((khi, klo), seed=routing.KEY_OWNER_SEED)
        owner = jnp.where(flat_keep,
                          (owner_h % jnp.uint32(n_shards)).astype(jnp.int32),
                          jnp.int32(n_shards))
        cap = _route_cap(L, n_shards, dist.route_slack)
        bhi, blo, (brid,), route_overflow = _route(khi, klo, [rid], owner, n_shards, cap)
        route_sent = _sent_matrix(owner, n_shards, axes)
        bhi, blo, brid = routing.exchange(axes, bhi, blo, brid)

        # ---- owner-side exact counts + fingerprints (local sort) ----
        fhi, flo, frid = bhi.reshape(-1), blo.reshape(-1), brid.reshape(-1)
        (shi, slo), (srid,) = segments.sort_by_key((fhi, flo), [frid])
        skey = (shi, slo)
        live = ~u64.is_sentinel(skey)
        sizes = segments.segment_counts(skey)
        fp = hashing.fingerprint_rid(srid)
        fp = (jnp.where(live, fp[0], 0), jnp.where(live, fp[1], 0))
        xors = segments.segment_xor(skey, fp)
        over = live & (sizes > cfg.max_block_size)
        reps = segments.segment_starts(skey) & over

        # ---- dedupe: route representatives by membership fingerprint ----
        rcap = dist.rep_capacity_per_shard
        n_reps = jnp.sum(reps.astype(jnp.int32))
        rep_overflow = jnp.maximum(n_reps - rcap, 0)
        rep_idx = jnp.nonzero(reps, size=rcap, fill_value=skey[0].shape[0] - 1)[0]
        rep_ok = jnp.arange(rcap, dtype=jnp.int32) < n_reps
        r_khi = jnp.where(rep_ok, shi[rep_idx], jnp.uint32(0xFFFFFFFF))
        r_klo = jnp.where(rep_ok, slo[rep_idx], jnp.uint32(0xFFFFFFFF))
        r_xhi = jnp.where(rep_ok, xors[0][rep_idx], jnp.uint32(0xFFFFFFFF))
        r_xlo = jnp.where(rep_ok, xors[1][rep_idx], jnp.uint32(0xFFFFFFFF))
        r_sz = jnp.where(rep_ok, sizes[rep_idx], INT32_MAX)
        _, xo = hashing.hash_u64((r_xhi, r_xlo), seed=routing.REP_OWNER_SEED)
        xowner = jnp.where(rep_ok, (xo % jnp.uint32(n_shards)).astype(jnp.int32),
                           jnp.int32(n_shards))
        xcap = int(np.ceil(rcap / n_shards * dist.route_slack)) + 8
        r_live = rep_ok.astype(jnp.int32)
        xhi_b, xlo_b, (xsz_b, xkhi_b, xklo_b, xlive_b), x_overflow = _route(
            r_xhi, r_xlo, [r_sz, r_khi, r_klo, r_live], xowner, n_shards, xcap)
        xhi_b, xlo_b, xsz_b, xkhi_b, xklo_b, xlive_b = routing.exchange(
            axes, xhi_b, xlo_b, xsz_b, xkhi_b, xklo_b, xlive_b)
        g_xhi, g_xlo, g_sz, g_khi, g_klo, g_live = _sort_reps(
            xhi_b.reshape(-1), xlo_b.reshape(-1), xsz_b.reshape(-1),
            xkhi_b.reshape(-1), xklo_b.reshape(-1), xlive_b.reshape(-1))
        dup = ((g_xhi == jnp.roll(g_xhi, 1)) & (g_xlo == jnp.roll(g_xlo, 1))
               & (g_sz == jnp.roll(g_sz, 1)))
        dup = dup.at[0].set(False)
        is_real = g_live > 0
        survivor = is_real & ~dup
        n_dup = jnp.sum((is_real & dup).astype(jnp.int32))
        n_dup = jax.lax.psum(n_dup, axes)

        # ---- broadcast the counts map (all_gather + sort): every
        # over-sized key once (one owner, one representative), sized
        # where it survived the dedupe and 0 where it is a duplicate ----
        t_khi = jnp.where(is_real, g_khi, jnp.uint32(0xFFFFFFFF))
        t_klo = jnp.where(is_real, g_klo, jnp.uint32(0xFFFFFFFF))
        t_sz = jnp.where(survivor, g_sz, 0)
        t_khi = jax.lax.all_gather(t_khi, axes, tiled=True)
        t_klo = jax.lax.all_gather(t_klo, axes, tiled=True)
        t_sz = jax.lax.all_gather(t_sz, axes, tiled=True)
        t_khi, t_klo, t_sz = jax.lax.sort((t_khi, t_klo, t_sz), num_keys=2)

        # ---- classify original local entries (paper Alg. 4 lines 9-19) ----
        over_sized, ex_size = segments.lookup_u64((t_khi, t_klo), t_sz,
                                                  (khi, klo), 0)
        over_sized = over_sized.reshape(valid.shape)
        ex_size = ex_size.reshape(valid.shape)
        # an entry lost to a full buffer can hide an over-sized key from
        # the map: then no key is accepted on the exact count, so no
        # accepted block ever passes max_block_size (the loss is counted)
        lost = jax.lax.psum(rep_overflow + route_overflow + x_overflow, axes)
        right_exact = keep & ~over_sized & (lost == 0)
        survive = keep & (ex_size > 0)
        accepted = right_cms | right_exact

        # ---- intersect locally (Alg. 2) ----
        new_key, new_valid, new_psize, n_dropped_mk = intersect_keys(
            cfg, key, survive, ex_size)
        new_key, new_valid, new_psize = pad_to_intersect_width(
            cfg, new_key, new_valid, new_psize)

        def tot(x):
            return jax.lax.psum(jnp.sum(x.astype(jnp.int32)), axes)

        stats = {
            "n_live_keys": tot(valid),
            "n_right_cms": tot(right_cms),
            "n_right_exact": tot(right_exact),
            "n_dropped_similarity": tot(dropped_sim),
            "n_dropped_max_keys": jax.lax.psum(n_dropped_mk, axes),
            "n_duplicate_blocks": n_dup,
            "n_surviving_oversized": jax.lax.psum(
                jnp.sum(survivor.astype(jnp.int32)), axes),
            "n_surviving_entries": tot(survive),
            "rep_overflow": lost,
            "route_sent": route_sent,
        }
        new_packed = jnp.stack([new_key[0], new_key[1]], axis=-1)
        return accepted, new_packed, new_valid, new_psize, stats

    spec3 = P(axes, None, None)
    spec2 = P(axes, None)
    stats_spec = {k: P() for k in [
        "n_live_keys", "n_right_cms", "n_right_exact", "n_dropped_similarity",
        "n_dropped_max_keys", "n_duplicate_blocks", "n_surviving_oversized",
        "n_surviving_entries", "rep_overflow", "route_sent"]}
    mapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(spec3, spec2, spec2),
        out_specs=(spec2, spec3, spec2, spec2, stats_spec),
        check_vma=False)
    return jax.jit(mapped)


def distributed_hashed_dynamic_blocking(
    keys_packed, valid, cfg: HDBConfig, mesh: Mesh,
    axis_names: Sequence[str] = ("data",),
    dist: DistConfig = DistConfig(),
    checkpoint_cb=None,
    start_iteration: int = 0,
    verbose: bool = False,
) -> BlockingResult:
    """Multi-device HDB driver (Algorithm 1) over a shard_mapped step.

    ``checkpoint_cb(iteration, state_pytree)`` — optional fault-tolerance
    hook invoked after every iteration with the (sharded) live state.
    """
    n = valid.shape[0]
    axes = tuple(axis_names)
    n_shards = sharding.axis_size(mesh, axes)
    assert n % n_shards == 0, (n, n_shards)
    sharding3 = NamedSharding(mesh, P(axes, None, None))
    sharding2 = NamedSharding(mesh, P(axes, None))
    keys_packed = jax.device_put(keys_packed, sharding3)
    valid = jax.device_put(valid, sharding2)
    psize = jax.device_put(np.full(valid.shape, INT32_MAX, np.int32), sharding2)

    step = make_hdb_step(cfg, mesh, axes, dist)
    acc_rid: List[np.ndarray] = []
    acc_hi: List[np.ndarray] = []
    acc_lo: List[np.ndarray] = []
    all_stats: List[IterationStats] = []
    exchanges: List[Exchange] = []
    for it in range(start_iteration, cfg.max_iterations):
        with obs.span("repro.dist.hdb.iteration", iteration=it):
            with obs.span("repro.dist.hdb.step"):
                accepted, new_keys, new_valid, new_psize, stats = step(
                    keys_packed, valid, psize)
            with obs.span("repro.dist.hdb.accept"):
                acc = np.asarray(accepted)
                ridx, kidx = np.nonzero(acc)
                keys_np = np.asarray(keys_packed)
                acc_rid.append(ridx.astype(np.int64))
                acc_hi.append(keys_np[ridx, kidx, 0])
                acc_lo.append(keys_np[ridx, kidx, 1])
                ex = Exchange("hdb", it, np.asarray(stats.pop("route_sent")),
                              _route_cap(valid.size // n_shards, n_shards,
                                         dist.route_slack))
                st = IterationStats(iteration=it, **{k: int(v) for k, v in
                                                     stats.items()})
            obs.mark("repro.dist.hdb.iteration.counts", slots=valid.size,
                     live=st.n_live_keys, rep_overflow=st.rep_overflow,
                     **ex.span_args())
        exchanges.append(ex)
        all_stats.append(st)
        logger.log(logging.INFO if verbose else logging.DEBUG,
                   "[hdb-dist] iter=%d %s", it, st)
        if st.rep_overflow:
            warnings.warn(
                f"[hdb-dist] buffer overflow ({st.rep_overflow} entries "
                "dropped); raise DistConfig capacities",
                RepCapacityWarning, stacklevel=2)
        keys_packed, valid, psize = new_keys, new_valid, new_psize
        if checkpoint_cb is not None:
            checkpoint_cb(it, {"keys": keys_packed, "valid": valid, "psize": psize})
        if st.n_surviving_entries == 0:
            break
    return BlockingResult(
        rids=np.concatenate(acc_rid) if acc_rid else np.zeros((0,), np.int64),
        key_hi=np.concatenate(acc_hi) if acc_hi else np.zeros((0,), np.uint32),
        key_lo=np.concatenate(acc_lo) if acc_lo else np.zeros((0,), np.uint32),
        stats=all_stats,
        num_records=n,
        exchanges=exchanges,
    )


# ---------------------------------------------------------------------------
# Distributed pair materialization + fingerprint-routed dedupe (paper §3.1
# over the mesh)
# ---------------------------------------------------------------------------


def _pair_contract_reason(blocks, budget: int, per_round: int,
                          exact: bool) -> Optional[str]:
    """None if the routed distributed engine applies, else why not."""
    from . import pairs as pairs_lib
    from ..kernels import pairs as pairs_kernels

    reason = pairs_lib._device_contract_ok(blocks, budget)
    if reason is not None:
        return reason
    if not pairs_lib._packable(blocks):
        return (f"record ids >= 2**{pairs_kernels.PACK_RID_BITS} break the "
                "62-bit sort-word pack")
    if exact and blocks.num_pair_slots + per_round > INT32_MAX:
        # shard bases of the padded final round would wrap int32: base =
        # r0 + shard*chunk can reach total + per_round - chunk - 1. The
        # single-device guards in core/pairs.py never see per-shard
        # offsets, so this check must live here.
        return (f"slot space {blocks.num_pair_slots} + round {per_round} "
                "overflows int32 at the per-shard slot offsets")
    return None


@functools.lru_cache(maxsize=64)
def _make_routed_round_step(mesh, axes, n_shards: int, chunk: int, cap: int,
                            steps: int, sampled: bool):
    """Build the jitted shard_mapped decode+pack+route+exchange round.

    Exact mode decodes slots [base, base+chunk) per shard (``total`` is a
    traced scalar operand so different datasets share one executable);
    sampled mode decodes pre-split (block, local) slot chunks. Both
    return this shard's routed sort-word buckets, the psum'd route
    overflow and the round's ``Exchange.sent`` matrix. Cached: repeated
    drivers over the same mesh geometry reuse the compiled step instead
    of re-jitting per call.
    """
    from ..kernels import pairs as pairs_kernels

    def shared_tail(a, b, s, v):
        hi, lo = pairs_kernels.pack_sort_words(a, b, s, v)
        owner = pairs_kernels.pair_route_owner(a, b, v, n_shards)
        bhi, blo, _, overflow = routing.route_buckets(
            hi, lo, [], owner, n_shards, cap)
        bhi, blo = routing.exchange(axes, bhi, blo)
        return (bhi.reshape(-1), blo.reshape(-1),
                jax.lax.psum(overflow, axes),
                _sent_matrix(owner, n_shards, axes))

    if sampled:
        def local_round(start, size, members, block, local, valid):
            a, b, s, v = pairs_kernels.decode_block_local(
                start, size, members, block[0], local[0], valid[0],
                steps=steps, use_kernel=False)
            return shared_tail(a, b, s, v)

        in_specs = (P(), P(), P(), P(axes, None), P(axes, None),
                    P(axes, None))
    else:
        def local_round(cum, start, size, members, base, total):
            a, b, s, v = pairs_kernels.decode_chunk(
                cum, start, size, members, base[0], total,
                chunk=chunk, steps=steps, use_kernel=False)
            return shared_tail(a, b, s, v)

        in_specs = (P(), P(), P(), P(), P(axes), P())

    return jax.jit(jax.shard_map(
        local_round, mesh=mesh, in_specs=in_specs,
        out_specs=(P(axes), P(axes), P(), P()), check_vma=False))


@functools.lru_cache(maxsize=64)
def _make_local_dedupe(mesh, axes, n_rounds: int,
                       sort_backend: str = "comparator",
                       n_passes: int = 16):
    """Build the shard-local sort-dedupe over the accumulated buckets.

    ``sort_backend`` picks the in-shard sort engine (comparator
    ``lax.sort`` vs the ``kernels/sort`` radix kernel) — part of the
    cache key, like every other static of the compiled step.
    """
    from ..kernels import pairs as pairs_kernels

    def local_dedupe(*bufs):
        hi = jnp.concatenate(bufs[:n_rounds])
        lo = jnp.concatenate(bufs[n_rounds:])
        return pairs_kernels.dedupe_packed_device(
            hi, lo, sort_backend=sort_backend, n_passes=n_passes,
            use_kernel=False)

    specs = (P(axes),) * (2 * n_rounds)
    return jax.jit(jax.shard_map(
        local_dedupe, mesh=mesh, in_specs=specs,
        out_specs=(P(axes), P(axes), P(axes)), check_vma=False))


@functools.lru_cache(maxsize=64)
def _make_decode_round_step(mesh, axes, chunk: int):
    """Decode-only round of the legacy global-sort path (cached jit)."""
    from ..kernels import pairs as pairs_kernels

    def local_decode(cum, start, size, members, base, total):
        return pairs_kernels.decode_chunk(
            cum, start, size, members, base[0], total,
            chunk=chunk, use_kernel=False)

    return jax.jit(jax.shard_map(
        local_decode, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(axes), P()),
        out_specs=(P(axes), P(axes), P(axes), P(axes)),
        check_vma=False))


def _single_device_pairs(blocks, budget: int, sample_seed: int,
                         sort_backend: str, reason: str, mark: str,
                         exchanges=()):
    """The single-device engine's PairSet in place of the routed one,
    marked in the trace (``repro.dist.pairs.fallback``) and on the set
    (``PairSet.fallback``), so that no run takes it unawares."""
    from . import pairs as pairs_lib

    obs.mark("repro.dist.pairs.fallback", reason=mark)
    pset = pairs_lib.dedupe_pairs(blocks, budget=budget,
                                  sample_seed=sample_seed,
                                  sort_backend=sort_backend)
    pset.fallback = reason
    pset.exchanges = list(exchanges)
    return pset


def dedupe_pairs_distributed(
    blocks, mesh: Mesh, axis_names: Sequence[str] = ("data",),
    budget: int = 50_000_000, chunk_per_shard: int = 1 << 18,
    route_slack: float = 2.0, sample_seed: int = 0,
    sort_backend: str = "auto",
):
    """Fingerprint-routed distributed pair dedupe (no global sort).

    Mirrors the HDB all_to_all dataflow: every shard decodes its slice of
    the canonical pair-slot space in fixed ``chunk_per_shard`` chunks
    (``kernels.pairs.decode_chunk``), packs each pair into the 62-bit
    sort word, and routes it to ``owner = splitmix64((a << 23) | b) %
    n_shards`` with the shared ``routing.route_buckets`` + one
    ``all_to_all`` per round. Since ownership depends only on (a, b),
    all occurrences of a pair meet on one shard, so the largest-block-
    wins sort-dedupe runs SHARD-LOCALLY over ~total/n_shards words —
    no device ever holds the full pair set. Shard winner sets are
    disjoint by construction; the host merges them with one u64 sort of
    the (much smaller) deduped output.

    Contract: bit-identical PairSets to single-device
    ``core.pairs.dedupe_pairs`` (any backend) for both the exact and the
    budget-exceeded sampled path (the uniform slot sample is global and
    seeded, shared with every other backend). Per-shard peak pair-buffer
    size is ceil(total/n_shards) * route_slack words (n_rounds *
    n_shards * cap with cap = ceil(chunk/n_shards * route_slack)).
    Routing overflow beyond ``route_slack`` is detected per round and
    falls back to the single-device driver rather than dropping pairs;
    so does a layout outside the routed engine's contract. Either way
    the set's ``fallback`` says why; ``exchanges`` holds each round's
    counts.

    ``sort_backend`` picks the shard-local dedupe sort: ``"auto"`` keeps
    the per-platform winner (per-shard numpy u64 ``np.sort`` on the CPU
    backend, the radix kernel on real accelerators), ``"comparator"`` /
    ``"radix"`` force the on-device engine either way — same contract as
    ``core.pairs.dedupe_pairs``, and still bit-identical.
    """
    from . import pairs as pairs_lib
    from ..kernels import pairs as pairs_kernels
    from ..kernels.pairs import ref as pairs_ref

    axes = tuple(axis_names)
    n_shards = sharding.axis_size(mesh, axes)
    if sort_backend not in pairs_lib._SORT_BACKENDS:
        raise ValueError(
            f"sort_backend must be one of {pairs_lib._SORT_BACKENDS}, "
            f"got {sort_backend!r}")
    total = blocks.num_pair_slots
    exact = total <= budget
    # the backend-shared seeded global sample (bit-identical to every
    # single-device backend); drawn up front so the chunk clamp below
    # sees the real workload
    slots = (None if exact
             else pairs_lib._sample_slots(total, budget, sample_seed))
    workload = total if exact else len(slots)
    if total > 0 and workload == 0:
        # budget <= 0 draws an empty sample; every backend returns the
        # empty inexact PairSet (counting stays exact via total_slots)
        return pairs_lib._empty_pairset(False, total)
    # clamp the per-shard chunk to the workload (mirrors _dedupe_device):
    # small samples/totals must not pay for full chunk_per_shard lanes
    chunk = min(chunk_per_shard,
                pairs_lib._round_up(max(1, -(-workload // n_shards)), 1024))
    per_round = n_shards * chunk
    reason = _pair_contract_reason(blocks, budget, per_round, exact)
    if total == 0:
        return pairs_lib.dedupe_pairs(blocks, budget=budget,
                                      sample_seed=sample_seed,
                                      sort_backend=sort_backend)
    if reason is not None:
        warnings.warn(f"routed distributed pairs unavailable ({reason}); "
                      "using single-device driver", RuntimeWarning,
                      stacklevel=2)
        return _single_device_pairs(blocks, budget, sample_seed,
                                    sort_backend, reason, "contract")

    # host casts + explicit uploads: dtype-coercing jnp.asarray and scalar
    # jnp dtype constructors are implicit host->device transfers, rejected
    # under jax.transfer_guard("disallow") (repro.analysis R001)
    start32 = jnp.asarray(blocks.start.astype(np.int32))
    size32 = jnp.asarray(blocks.size.astype(np.int32))
    mem32 = jnp.asarray(blocks.members.astype(np.int32))
    steps = pairs_kernels.search_steps_for(int(blocks.size.max()))
    cap = _route_cap(chunk, n_shards, route_slack)
    step = _make_routed_round_step(mesh, axes, n_shards, chunk, cap,
                                   steps, sampled=not exact)

    rhi, rlo, ovfs, sents = [], [], [], []
    if exact:
        cum32 = jnp.asarray(
            pairs_ref.cum_pair_counts(blocks.size).astype(np.int32))
        total32 = jax.device_put(np.int32(total))
        shard_offsets = np.arange(n_shards, dtype=np.int32) * chunk
        for r, r0 in enumerate(range(0, total, per_round)):
            with obs.span("repro.dist.pairs.round", round=r):
                base = jnp.asarray(np.int32(r0) + shard_offsets)
                bhi, blo, ovf, sent = step(cum32, start32, size32, mem32,
                                           base, total32)
            rhi.append(bhi); rlo.append(blo); ovfs.append(ovf)
            sents.append(sent)
    else:
        # budget-exceeded: decode the sample drawn above, split
        # block/local host-side because global slot indices are int64
        cum = pairs_ref.cum_pair_counts(blocks.size)
        block = (np.searchsorted(cum, slots, side="right") - 1).astype(np.int32)
        local = (slots - cum[block]).astype(np.int32)
        valid = np.ones(len(slots), bool)
        pad = (-len(slots)) % per_round
        if pad:
            block = np.pad(block, (0, pad))
            local = np.pad(local, (0, pad))
            valid = np.pad(valid, (0, pad))
        for r, off in enumerate(range(0, len(block), per_round)):
            sl = slice(off, off + per_round)
            with obs.span("repro.dist.pairs.round", round=r):
                bhi, blo, ovf, sent = step(
                    start32, size32, mem32,
                    jnp.asarray(block[sl].reshape(n_shards, chunk)),
                    jnp.asarray(local[sl].reshape(n_shards, chunk)),
                    jnp.asarray(valid[sl].reshape(n_shards, chunk)))
            rhi.append(bhi); rlo.append(blo); ovfs.append(ovf)
            sents.append(sent)
    # one deferred host sync: rounds pipeline freely in the common
    # no-overflow case, and the fallback discards the buckets anyway
    ovfs, sents = jax.device_get((ovfs, sents))
    exchanges = [Exchange("pairs", r, np.asarray(sent), cap)
                 for r, sent in enumerate(sents)]
    for ex, ovf in zip(exchanges, ovfs):
        obs.mark("repro.dist.pairs.round.counts", round=ex.index,
                 words=int(ex.sent.sum()), overflow=int(ovf),
                 **ex.span_args())
    if any(int(o) for o in ovfs):
        reason = (f"routed pair dedupe overflowed a bucket (cap {cap}, "
                  f"slack {route_slack})")
        warnings.warn(
            f"{reason}; falling back to the single-device driver — "
            "raise route_slack to keep the routed path",
            RepCapacityWarning, stacklevel=2)
        return _single_device_pairs(blocks, budget, sample_seed,
                                    sort_backend, reason, "overflow",
                                    exchanges)

    # routed pairs always satisfy the pack bound (contract check above),
    # so "auto" resolves to the per-platform winner and "radix" never
    # degrades here
    sort_kind = pairs_lib.resolve_sort_backend(sort_backend, blocks)
    if sort_kind == "host":
        # CPU mirror of the single-device driver's packed strategy: each
        # shard's routed bucket is sorted with numpy's u64 sort (host ==
        # device memory on CPU, and np.sort beats XLA CPU's comparator
        # sort ~40x). Still shard-local: one bounded bucket at a time.
        per_round_words = [
            ((np.asarray(h).astype(np.uint64) << np.uint64(32))
             | np.asarray(l).astype(np.uint64)).reshape(n_shards, -1)
            for h, l in zip(rhi, rlo)]
        words = np.concatenate([
            pairs_kernels.dedupe_words_host(
                np.concatenate([wr[s] for wr in per_round_words]))
            for s in range(n_shards)])
    else:
        # data-dependent pass count only for the radix sort (n_passes is
        # part of the lru_cache key; the comparator ignores it)
        n_passes = (pairs_lib._radix_passes_for_blocks(blocks)
                    if sort_kind == "radix" else 16)
        dedupe = _make_local_dedupe(mesh, axes, len(rhi), sort_kind,
                                    n_passes)
        shi, slo, winner = dedupe(*rhi, *rlo)
        w = np.asarray(winner)
        words = ((np.asarray(shi).astype(np.uint64) << np.uint64(32))
                 | np.asarray(slo).astype(np.uint64))[w]
    # shard winner sets are disjoint: one host sort of the deduped output
    # restores the canonical global (a, b) order
    a, b, s = pairs_kernels.unpack_words_host(np.sort(words))
    return pairs_lib.PairSet(a=a, b=b, src_size=s, exact=exact,
                             total_slots=total, exchanges=exchanges)


def materialize_pairs_distributed(
    blocks, mesh: Mesh, axis_names: Sequence[str] = ("data",),
    budget: int = 50_000_000, chunk_per_shard: int = 1 << 18,
    sample_seed: int = 0,
    dedupe: str = "routed", route_slack: float = 2.0,
    sort_backend: str = "auto",
):
    """Shard pair-slot decoding over the mesh and dedupe the result.

    ``dedupe="routed"`` (default) is the fingerprint-routed shard-local
    dedupe (``dedupe_pairs_distributed``); ``dedupe="global"`` keeps the
    legacy single global sort over the gathered pair buffer — retained as
    the benchmark baseline (``benchmarks/bench_pairs.py --mesh``) and for
    A/B debugging. Both are bit-identical to the single-device engine,
    and both route their dedupe sort through the shared ``sort_backend``
    knob (``"auto"``/``"comparator"``/``"radix"``) — the global
    baseline's one big sort is just the same abstraction over the whole
    pair buffer instead of per-shard buckets.
    """
    if dedupe == "routed":
        return dedupe_pairs_distributed(
            blocks, mesh, axis_names, budget=budget,
            chunk_per_shard=chunk_per_shard, route_slack=route_slack,
            sample_seed=sample_seed, sort_backend=sort_backend)
    if dedupe != "global":
        raise ValueError(f"dedupe must be 'routed' or 'global', got {dedupe!r}")

    from . import pairs as pairs_lib
    from ..kernels import pairs as pairs_kernels
    from ..kernels.pairs import ref as pairs_ref

    axes = tuple(axis_names)
    n_shards = sharding.axis_size(mesh, axes)
    chunk = chunk_per_shard
    per_round = n_shards * chunk
    total = blocks.num_pair_slots
    reason = pairs_lib._device_contract_ok(blocks, budget)
    if reason is None and total + per_round > INT32_MAX:
        # shard bases of the padded final round would wrap int32
        reason = f"slot space {total} + round {per_round} overflows int32"
    if total == 0 or total > budget or reason is not None:
        if reason is not None:
            warnings.warn(f"distributed pairs unavailable ({reason}); "
                          "using single-device driver", RuntimeWarning,
                          stacklevel=2)
        return pairs_lib.dedupe_pairs(blocks, budget=budget,
                                      sample_seed=sample_seed,
                                      sort_backend=sort_backend)

    cum32 = jnp.asarray(pairs_ref.cum_pair_counts(blocks.size).astype(np.int32))
    start32 = jnp.asarray(blocks.start.astype(np.int32))
    size32 = jnp.asarray(blocks.size.astype(np.int32))
    mem32 = jnp.asarray(blocks.members.astype(np.int32))
    total32 = jax.device_put(np.int32(total))
    mapped = _make_decode_round_step(mesh, axes, chunk)

    shard_offsets = np.arange(n_shards, dtype=np.int32) * chunk
    out_a, out_b, out_s, out_v = [], [], [], []
    for r0 in range(0, total, per_round):
        base = jnp.asarray(np.int32(r0) + shard_offsets)
        a, b, s, v = mapped(cum32, start32, size32, mem32, base, total32)
        out_a.append(np.asarray(a)); out_b.append(np.asarray(b))
        out_s.append(np.asarray(s)); out_v.append(np.asarray(v))
    # the legacy baseline is "one big device sort": "host" (a CPU-only
    # shortcut of the routed/single-device drivers) maps to the
    # comparator here so the baseline stays a device sort measurement
    sort_kind = pairs_lib.resolve_sort_backend(sort_backend, blocks)
    if sort_kind == "host":
        sort_kind = "comparator"
    kw = {}
    if sort_kind == "radix":
        kw["n_passes"] = pairs_lib._radix_passes_for_blocks(blocks)
    sa, sb, ss, winner = pairs_kernels.dedupe_device(
        jnp.asarray(np.concatenate(out_a)), jnp.asarray(np.concatenate(out_b)),
        jnp.asarray(np.concatenate(out_s)), jnp.asarray(np.concatenate(out_v)),
        sort_backend=sort_kind, use_kernel=False, **kw)
    w = np.asarray(winner)
    return pairs_lib.PairSet(
        a=np.asarray(sa)[w].astype(np.int64),
        b=np.asarray(sb)[w].astype(np.int64),
        src_size=np.asarray(ss)[w].astype(np.int64),
        exact=True, total_slots=total)
