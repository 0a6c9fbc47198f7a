"""Fingerprint-sharded BlockStore: N key-partitioned slices, one surface.

The single-host ``BlockStore`` keeps one ``LevelKeys`` (CMS + key table)
per level plus one ``BlockCsr`` and one ``PairLedger``. This module
partitions all three by fingerprint over ``core.routing``'s shared owner
rule and re-exposes the exact ``BlockStore`` surface:

- **Key space** (``ShardedLevelKeys``): key-table rows and CMS fold-ins
  route to ``owner = splitmix64(key64, KEY_OWNER_SEED) % n_shards`` — the
  SAME partition the distributed batch step uses for its exact-count
  exchange, so a batch shard and a streaming shard agree on who owns a
  key. Each shard's CMS slice holds only its keys' entries; because the
  CMS is a linear sketch their elementwise sum IS the union sketch, and
  the composite keeps that psum-merged replica current for estimates
  (mirroring ``jax.lax.psum(cms)`` in ``core.distributed``).
- **Accepted-blocks CSR** (``StoreShard.csr``): partitioned by block-key
  owner — the shard that counts a key also materializes its block.
- **Pair ledger** (``StoreShard.ledger``): partitioned by pair-pack
  fingerprint (``REP_OWNER_SEED``), matching how
  ``dedupe_pairs_distributed`` meets all occurrences of a pair on one
  shard.

Routing invariants (see docs/STREAMING.md):

- Every routed update is *aggregated first* (``reduce_by_key``), so one
  ingest sends at most one key-table delta per (level, key) — one
  ``route_buckets`` + ``exchange``/``all_to_all`` per level when a mesh
  is attached, mirroring the distributed HDB step's dataflow.
- Shard key sets are disjoint, so merged views (``accepted_blocks``,
  ``candidate_pairs``, splice/pair deltas) are re-sorted concatenations —
  bit-identical to the single-host store's output, property-tested.
- ``n_shards=1`` degenerates exactly to today's behavior: one shard owns
  every key, every routed exchange is the identity.
- Bucket overflow on the mesh path is *counted, never silent*: the
  exchange warns (``RepCapacityWarning``), falls back losslessly to host
  grouping, and bumps ``ShardRouter.exchange_fallback_total`` (surfaced
  in the serving metrics snapshot).
"""
from __future__ import annotations

import functools
import warnings
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..core import hdb as hdb_mod
from ..core import pairs as pairs_mod
from ..core import routing, sketches
from ..core.hdb import RepCapacityWarning
from .store import (BlockCsr, LevelKeys, LevelState, PairLedger,
                    merge_blocks, unpack_key64)

_SENT32 = np.uint32(0xFFFFFFFF)


def _ceil_pow2(n: int, floor: int = 256) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


@functools.lru_cache(maxsize=32)
def _make_keytab_exchange(mesh, axes: Tuple[str, ...], n_shards: int,
                          rows: int, cap: int):
    """Jitted shard_mapped key-table delta exchange (one per level call).

    Each source shard scatters its (key, count, fingerprint) deltas into
    fixed-``cap`` per-destination buckets by key owner and swaps them
    with ONE ``all_to_all`` (``routing.exchange``). Absent lanes carry
    all-ones sentinel keys. Statics (rows per shard is padded to a power
    of two by the caller) bound the compile cache — the repro.analysis
    R005 contract, same builder pattern as ``core.distributed``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..core import hashing, u64

    def local(khi, klo, cnt, fhi, flo):
        live = ~u64.is_sentinel((khi, klo))
        _, oh = hashing.hash_u64((khi, klo), seed=routing.KEY_OWNER_SEED)
        owner = jnp.where(live,
                          (oh % jnp.uint32(n_shards)).astype(jnp.int32),
                          jnp.int32(n_shards))
        bhi, blo, (bcnt, bfhi, bflo), ovf = routing.route_buckets(
            khi, klo, [cnt, fhi, flo], owner, n_shards, cap)
        bhi, blo, bcnt, bfhi, bflo = routing.exchange(
            axes, bhi, blo, bcnt, bfhi, bflo)
        return bhi, blo, bcnt, bfhi, bflo, jax.lax.psum(ovf, axes)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes),) * 5,
        out_specs=(P(axes, None),) * 5 + (P(),),
        check_vma=False))


class ShardRouter:
    """Owner computation + the mesh-backed routed key-delta exchange.

    Without a mesh the exchange is a host owner-grouping mirror — bit-
    identical, used by tests/benches and as the lossless overflow
    fallback. With a mesh it stages deltas through ``route_buckets`` +
    one ``all_to_all`` per call on emulated or real devices.
    """

    def __init__(self, n_shards: int, mesh=None,
                 axis_names: Sequence[str] = ("data",),
                 route_slack: float = 2.0):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.n_shards = n_shards
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.route_slack = route_slack
        self.exchange_total = 0
        self.exchange_fallback_total = 0
        if mesh is not None:
            from ..distributed import sharding
            size = sharding.axis_size(mesh, self.axis_names)
            if size != n_shards:
                raise ValueError(
                    f"mesh axes {self.axis_names} have {size} devices but "
                    f"the store has {n_shards} shards — they must match "
                    "(one shard per device)")

    def key_owner(self, key64: np.ndarray) -> np.ndarray:
        return routing.np_owner_u64(key64, self.n_shards,
                                    seed=routing.KEY_OWNER_SEED)

    def pair_owner(self, pack: np.ndarray) -> np.ndarray:
        return routing.np_owner_u64(pack, self.n_shards,
                                    seed=routing.REP_OWNER_SEED)

    # ------------------------------------------------------------------

    def _group_host(self, d_key, d_cnt, d_fp):
        owner = self.key_owner(d_key)
        out = []
        for s in range(self.n_shards):
            m = owner == s
            out.append((d_key[m], d_cnt[m], d_fp[m]))
        return out

    def exchange_key_deltas(self, d_key: np.ndarray, d_cnt: np.ndarray,
                            d_fp: np.ndarray
                            ) -> List[Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]]:
        """Route aggregated key-table deltas to their owner shards.

        Returns one (key, cnt, fp) triple per shard, key-sorted (the
        ``update_keytab`` input contract). ``d_key`` is sorted unique
        (``reduce_by_key`` output), so every key crosses the wire exactly
        once and the received slices need no re-aggregation.
        """
        self.exchange_total += 1
        if self.mesh is None or self.n_shards == 1 or len(d_key) == 0:
            return self._group_host(d_key, d_cnt, d_fp)
        import jax.numpy as jnp

        n = self.n_shards
        rows = _ceil_pow2(-(-len(d_key) // n), floor=64)
        cap = max(8, int(np.ceil(rows / n * self.route_slack)))
        total = n * rows
        khi = np.full(total, _SENT32, np.uint32)
        klo = np.full(total, _SENT32, np.uint32)
        hi, lo = unpack_key64(d_key)
        khi[:len(d_key)], klo[:len(d_key)] = hi, lo
        # per-ingest count deltas are bounded by the micro-batch entry
        # count, so int32 lanes are exact (the table itself stays int64)
        cnt = np.zeros(total, np.int32)
        cnt[:len(d_key)] = d_cnt.astype(np.int32)
        fhi = np.zeros(total, np.uint32)
        flo = np.zeros(total, np.uint32)
        fhi[:len(d_key)], flo[:len(d_key)] = unpack_key64(d_fp)
        step = _make_keytab_exchange(self.mesh, self.axis_names, n, rows, cap)
        bhi, blo, bcnt, bfhi, bflo, ovf = step(
            jnp.asarray(khi), jnp.asarray(klo), jnp.asarray(cnt),
            jnp.asarray(fhi), jnp.asarray(flo))
        if int(np.asarray(ovf)):
            warnings.warn(
                f"sharded key-table exchange overflowed a bucket (cap {cap}, "
                f"slack {self.route_slack}); falling back to host grouping "
                "for this delta — raise route_slack to keep the routed path",
                RepCapacityWarning, stacklevel=3)
            self.exchange_fallback_total += 1
            return self._group_host(d_key, d_cnt, d_fp)
        bhi = np.asarray(bhi).reshape(n, -1)
        blo = np.asarray(blo).reshape(n, -1)
        bcnt = np.asarray(bcnt).reshape(n, -1)
        bfhi = np.asarray(bfhi).reshape(n, -1)
        bflo = np.asarray(bflo).reshape(n, -1)
        out = []
        for d in range(n):
            live = ~((bhi[d] == _SENT32) & (blo[d] == _SENT32))
            key = ((bhi[d][live].astype(np.uint64) << np.uint64(32))
                   | blo[d][live].astype(np.uint64))
            fp = ((bfhi[d][live].astype(np.uint64) << np.uint64(32))
                  | bflo[d][live].astype(np.uint64))
            c = bcnt[d][live].astype(np.int64)
            order = np.argsort(key)
            out.append((key[order], c[order], fp[order]))
        return out


class ShardedLevelKeys:
    """N per-shard ``LevelKeys`` slices + a psum-merged CMS replica.

    Presents the exact ``LevelKeys`` method surface to ``LevelState``.
    Per-shard sketches are the authoritative partitioned state (each
    fold-in lands on the entry's key owner); their elementwise sum equals
    the merged replica at all times (CMS linearity), which serves every
    estimate without a gather across shards.
    """

    def __init__(self, cms_cfg: sketches.CMSConfig,
                 slices: List[LevelKeys], router: ShardRouter):
        self.cms_cfg = cms_cfg
        self.slices = slices
        self.router = router
        self.cms = np.zeros((cms_cfg.depth, cms_cfg.width), np.int32)

    # ---- CMS ----

    def cms_apply(self, key64: np.ndarray, idx: np.ndarray,
                  sign: int) -> None:
        for j in range(len(self.cms)):
            np.add.at(self.cms[j], idx[j], sign)
        owner = self.router.key_owner(key64)
        for s, sl in enumerate(self.slices):
            m = owner == s
            if m.any():
                sl.cms_apply(key64[m], idx[:, m], sign)

    def cms_lookup(self, idx: np.ndarray) -> np.ndarray:
        return np.stack([self.cms[j][idx[j]] for j in range(len(self.cms))])

    # ---- key table ----

    def update_keytab(self, d_key: np.ndarray, d_cnt: np.ndarray,
                      d_fp: np.ndarray) -> np.ndarray:
        parts = self.router.exchange_key_deltas(d_key, d_cnt, d_fp)
        for sl, (k, c, f) in zip(self.slices, parts):
            if len(k):
                sl.update_keytab(k, c, f)
        return d_key

    def lookup(self, key64: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        flat = np.asarray(key64, np.uint64).reshape(-1)
        owner = self.router.key_owner(flat)
        cnt = np.zeros(flat.shape, np.int64)
        surv = np.zeros(flat.shape, bool)
        found = np.zeros(flat.shape, bool)
        for s, sl in enumerate(self.slices):
            m = owner == s
            if m.any():
                c, sv, f = sl.lookup(flat[m])
                cnt[m], surv[m], found[m] = c, sv, f
        shape = np.asarray(key64, np.uint64).shape
        return cnt.reshape(shape), surv.reshape(shape), found.reshape(shape)

    def lookup_fp(self, key64: np.ndarray) -> np.ndarray:
        flat = np.asarray(key64, np.uint64).reshape(-1)
        owner = self.router.key_owner(flat)
        fp = np.zeros(flat.shape, np.uint64)
        for s, sl in enumerate(self.slices):
            m = owner == s
            if m.any():
                fp[m] = sl.lookup_fp(flat[m])
        return fp.reshape(np.asarray(key64, np.uint64).shape)

    def oversized(self, max_block_size: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        ks, cs, fs = [], [], []
        for sl in self.slices:
            k, c, f = sl.oversized(max_block_size)
            ks.append(k)
            cs.append(c)
            fs.append(f)
        key = np.concatenate(ks)
        # global key order restores the single-host survivor-pass input
        # order exactly (shard key sets are disjoint)
        order = np.argsort(key)
        return (key[order], np.concatenate(cs)[order],
                np.concatenate(fs)[order])

    def set_survivors(self, over_key: np.ndarray,
                      surv: np.ndarray) -> np.ndarray:
        owner = self.router.key_owner(over_key)
        changed = []
        for s, sl in enumerate(self.slices):
            m = owner == s
            # every shard is called even with no over-keys: its stale
            # survivor flags from the previous ingest must clear
            ch = sl.set_survivors(over_key[m], surv[m])
            if len(ch):
                changed.append(ch)
        if not changed:
            return np.zeros((0,), np.uint64)
        return np.sort(np.concatenate(changed))

    @property
    def num_keys(self) -> int:
        return sum(sl.num_keys for sl in self.slices)

    @property
    def keytab_bytes(self) -> int:
        return sum(sl.keytab_bytes for sl in self.slices)

    @property
    def cms_bytes(self) -> int:
        return self.cms.nbytes + sum(sl.cms_bytes for sl in self.slices)


class StoreShard:
    """One shard's slice of the partitioned persistent blocking state.

    Owns the per-level ``LevelKeys`` (keys whose fingerprint routes
    here), the accepted-blocks CSR restricted to its block keys, and the
    pair-ledger slice for its pair fingerprints. Pure container + byte
    accounting; all cross-shard coordination lives in
    ``ShardedBlockStore``/``ShardedLevelKeys``.
    """

    def __init__(self, cfg: hdb_mod.HDBConfig, shard_id: int):
        self.cfg = cfg
        self.shard_id = shard_id
        self.level_keys: List[Optional[LevelKeys]] = (
            [None] * cfg.max_iterations)
        self.csr = BlockCsr()
        self.ledger = PairLedger()

    def keys_at(self, level: int) -> LevelKeys:
        ks = self.level_keys[level]
        if ks is None:
            ks = LevelKeys.empty(self.cfg.cms)
            self.level_keys[level] = ks
        return ks

    @property
    def keytab_bytes(self) -> int:
        return sum(ks.keytab_bytes for ks in self.level_keys
                   if ks is not None)

    @property
    def num_keys(self) -> int:
        return sum(ks.num_keys for ks in self.level_keys if ks is not None)

    @property
    def total_bytes(self) -> int:
        return self.keytab_bytes + self.csr.nbytes + self.ledger.nbytes


class ShardedBlockStore:
    """N fingerprint-routed ``StoreShard``s behind the BlockStore surface.

    Duck-typed drop-in for ``BlockStore`` everywhere the streaming and
    serving layers use one (``DeltaBlocker``, ``StreamingEngine``,
    ``DedupeService`` tenants): same constructor-compatible ``cfg``, same
    methods, and every merged view is bit-identical to the single-host
    store after the same ingest sequence. ``mesh``/``axis_names`` attach
    the device-routed exchange (one ``all_to_all`` per level per ingest)
    and tell ``DeltaBlocker`` to sync the pair ledger through
    ``dedupe_pairs_distributed``; without a mesh the routing runs through
    the bit-identical host mirror.
    """

    def __init__(self, cfg: hdb_mod.HDBConfig = hdb_mod.HDBConfig(),
                 n_shards: int = 1, mesh=None,
                 axis_names: Sequence[str] = ("data",),
                 route_slack: float = 2.0):
        self.cfg = cfg
        self.n_shards = n_shards
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.router = ShardRouter(n_shards, mesh=mesh, axis_names=axis_names,
                                  route_slack=route_slack)
        self.shards = [StoreShard(cfg, s) for s in range(n_shards)]
        self.num_records = 0
        self.levels: List[Optional[LevelState]] = [None] * cfg.max_iterations

    # ------------------------------------------------------------------
    # level access
    # ------------------------------------------------------------------

    def level(self, i: int, width: Optional[int] = None) -> LevelState:
        st = self.levels[i]
        if st is None:
            assert width is not None, f"level {i} accessed before first ingest"
            keyspace = ShardedLevelKeys(
                self.cfg.cms, [sh.keys_at(i) for sh in self.shards],
                self.router)
            st = LevelState.empty(width, self.cfg.cms, keyspace=keyspace)
            self.levels[i] = st
        elif width is not None and st.width != width:
            raise ValueError(
                f"level {i} width mismatch: store has {st.width}, delta has "
                f"{width} (top-level key schema must be stable)")
        return st

    # ------------------------------------------------------------------
    # accepted-blocks CSR (key-owner partitioned)
    # ------------------------------------------------------------------

    def members_of(self, key64: np.ndarray) -> List[np.ndarray]:
        key64 = np.asarray(key64, np.uint64)
        owner = self.router.key_owner(key64)
        out: List[Optional[np.ndarray]] = [None] * len(key64)
        for s, sh in enumerate(self.shards):
            m = np.flatnonzero(owner == s)
            if len(m):
                for qi, mem in zip(m, sh.csr.members_of(key64[m])):
                    out[qi] = mem
        return out  # type: ignore[return-value]

    def affected_slice(self, keys: np.ndarray) -> pairs_mod.Blocks:
        owner = self.router.key_owner(keys)
        return merge_blocks([sh.csr.affected_slice(keys[owner == s])
                             for s, sh in enumerate(self.shards)])

    def block_size_of(self, key64: np.ndarray) -> np.ndarray:
        owner = self.router.key_owner(key64)
        size = np.zeros(len(key64), np.int64)
        for s, sh in enumerate(self.shards):
            m = owner == s
            if m.any():
                size[m] = sh.csr.size_of(key64[m])
        return size

    def apply_assignment_deltas(self, add_k: np.ndarray, add_r: np.ndarray,
                                ret_k: np.ndarray, ret_r: np.ndarray,
                                snapshot_keys: Optional[np.ndarray] = None
                                ) -> Tuple[np.ndarray, pairs_mod.Blocks,
                                           pairs_mod.Blocks]:
        ao = self.router.key_owner(add_k)
        ro = self.router.key_owner(ret_k)
        so = (None if snapshot_keys is None
              else self.router.key_owner(snapshot_keys))
        affected, olds, news = [], [], []
        for s, sh in enumerate(self.shards):
            aff_s, old_s, new_s = sh.csr.splice(
                add_k[ao == s], add_r[ao == s],
                ret_k[ro == s], ret_r[ro == s],
                None if snapshot_keys is None else snapshot_keys[so == s])
            affected.append(aff_s)
            olds.append(old_s)
            news.append(new_s)
        return (np.sort(np.concatenate(affected)),
                merge_blocks(olds), merge_blocks(news))

    # ------------------------------------------------------------------
    # ledger (pair-fingerprint partitioned)
    # ------------------------------------------------------------------

    def apply_pair_deltas(self, pair_pack: np.ndarray, src: np.ndarray
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if len(pair_pack) == 0:
            z = np.zeros((0,), np.uint64)
            return z, np.zeros((0,), np.int64), z
        owner = self.router.pair_owner(pair_pack)
        add_p, add_s, retr = [], [], []
        for s, sh in enumerate(self.shards):
            m = owner == s
            ap, asrc, rp = sh.ledger.apply(pair_pack[m], src[m])
            add_p.append(ap)
            add_s.append(asrc)
            retr.append(rp)
        ap = np.concatenate(add_p)
        asrc = np.concatenate(add_s)
        order = np.argsort(ap)
        return ap[order], asrc[order], np.sort(np.concatenate(retr))

    def ledger_src(self, pack: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        owner = self.router.pair_owner(pack)
        cur = np.zeros(len(pack), np.int64)
        found = np.zeros(len(pack), bool)
        for s, sh in enumerate(self.shards):
            m = owner == s
            if m.any():
                c, f = sh.ledger.src_of(pack[m])
                cur[m], found[m] = c, f
        return cur, found

    # ------------------------------------------------------------------
    # merged views (bit-identical to the single-host store)
    # ------------------------------------------------------------------

    @property
    def led_pack(self) -> np.ndarray:
        return np.sort(np.concatenate(
            [sh.ledger.pack for sh in self.shards]))

    @property
    def led_src(self) -> np.ndarray:
        pack = np.concatenate([sh.ledger.pack for sh in self.shards])
        src = np.concatenate([sh.ledger.src for sh in self.shards])
        return src[np.argsort(pack)]

    def accepted_blocks(self, min_size: int = 1) -> pairs_mod.Blocks:
        return merge_blocks([sh.csr.view(min_size) for sh in self.shards])

    def candidate_pairs(self) -> pairs_mod.PairSet:
        pack = np.concatenate([sh.ledger.pack for sh in self.shards])
        src = np.concatenate([sh.ledger.src for sh in self.shards])
        order = np.argsort(pack)
        from .store import unpack_pair
        a, b = unpack_pair(pack[order])
        blk = self.accepted_blocks(min_size=2)
        return pairs_mod.PairSet(a=a, b=b, src_size=src[order].copy(),
                                 exact=True, total_slots=blk.num_pair_slots)

    # ------------------------------------------------------------------
    # stats
    # ------------------------------------------------------------------

    def shard_skew(self) -> float:
        """max/mean ratio of per-shard state bytes (1.0 == balanced)."""
        per = [sh.total_bytes for sh in self.shards]
        mean = sum(per) / max(len(per), 1)
        return float(max(per) / mean) if mean else 1.0

    def memory_stats(self) -> dict:
        out = {"num_records": self.num_records,
               "n_shards": self.n_shards,
               "ledger_pairs": sum(sh.ledger.num_pairs
                                   for sh in self.shards),
               "accepted_blocks": sum(sh.csr.num_blocks
                                      for sh in self.shards),
               "accepted_assignments": sum(sh.csr.num_assignments
                                           for sh in self.shards)}
        keytab_bytes = cms_bytes = 0
        for i, st in enumerate(self.levels):
            if st is not None:
                out[f"level{i}_rows"] = st.num_rows
                out[f"level{i}_entries"] = st.num_entries
                out[f"level{i}_keys"] = st.num_keys
                keytab_bytes += st.keyspace.keytab_bytes
                cms_bytes += st.keyspace.cms_bytes
        out["keytab_bytes"] = keytab_bytes
        out["cms_bytes"] = cms_bytes
        out["csr_bytes"] = sum(sh.csr.nbytes for sh in self.shards)
        out["ledger_bytes"] = sum(sh.ledger.nbytes for sh in self.shards)
        for s, sh in enumerate(self.shards):
            out[f"shard{s}_keytab_bytes"] = sh.keytab_bytes
            out[f"shard{s}_csr_bytes"] = sh.csr.nbytes
            out[f"shard{s}_ledger_bytes"] = sh.ledger.nbytes
        out["shard_skew"] = self.shard_skew()
        out["exchange_fallback_total"] = self.router.exchange_fallback_total
        return out
