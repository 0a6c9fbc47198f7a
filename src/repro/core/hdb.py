"""Hashed Dynamic Blocking — Algorithms 1–4 of the paper, in fixed-shape JAX.

The iteration state is a dense per-record key matrix (records never move;
only 64-bit key hashes flow — the paper's data-movement thesis). From the
second iteration on it is padded to one width, ``intersect_width``, so the
intersection step compiles for two widths per run. The count step runs over
the live entries only: each iteration gathers every row's leading columns
that hold its valid entries into a power-of-two bucket of lanes, and counts
densely where that bucket would reach the whole matrix. Each host-level
iteration runs two jit-compiled steps around a host dedupe of the over-sized
block representatives:

  1. ROUGH OVER-SIZE DETECTION (Alg. 3): build a Count-Min Sketch over all
     live (record, key) entries, query approximate block sizes. Keys with
     ``s <= MAX_BLOCK_SIZE`` are right-sized (CMS never undercounts, so this
     is safe). Keys failing the progress heuristic ``s/psize > MAX_SIMILARITY``
     are discarded.
  2. EXACTLY COUNT AND DEDUPE (Alg. 4): sort surviving entries by key;
     segmented count + XOR-of-rid-fingerprints give every entry its exact
     block size and its block's membership hash. Blocks the CMS over-counted
     are recovered as right-sized. Over-sized blocks with identical
     membership hashes are duplicates — one survivor is kept (smallest key);
     that dedupe runs on the host over one representative per block.
  3. INTERSECT KEYS (Alg. 2): each record combines pairs of its surviving
     over-sized keys into new candidate keys carrying
     ``psize = min(parent sizes)``; records holding more than ``MAX_KEYS``
     keys are dropped from further processing.

Single-device path below; the shard_map-distributed path (sketch
all-reduce + all_to_all exact counting + Bloom/table broadcast, faithful
to the paper's Spark dataflow) lives in ``core/distributed.py`` and reuses
these functions.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import warnings
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import u64, hashing, segments, sketches
from .. import obs
from .u64 import U64

INT32_MAX = np.iinfo(np.int32).max
_SENT32 = np.uint32(0xFFFFFFFF)
LANE_FLOOR = 1 << 12  # fewest lanes a compacted count step runs over
logger = logging.getLogger(__name__)


class RepCapacityWarning(RuntimeWarning):
    """Fixed-capacity representative/route buffers overflowed; some blocks
    were dropped. Raise the relevant capacity config."""


@dataclasses.dataclass(frozen=True)
class HDBConfig:
    """Hyper-parameters (paper §5 defaults)."""

    max_block_size: int = 500
    max_keys: int = 80            # Alg. 2 line 2: per-record key cap
    max_similarity: float = 0.9   # progress heuristic (Alg. 3 line 11)
    max_oversize_keys: int = 16   # TPU adaptation: keys carried into intersection
    max_iterations: int = 8
    cms_depth: int = 4
    cms_width: int = 1 << 20
    rep_capacity: int = 1 << 20   # capacity for over-sized block representatives

    @property
    def cms(self) -> sketches.CMSConfig:
        return sketches.CMSConfig(self.cms_depth, self.cms_width)

    @property
    def intersect_width(self) -> int:
        ko = self.max_oversize_keys
        return ko * (ko - 1) // 2


@dataclasses.dataclass
class IterationStats:
    iteration: int
    n_live_keys: int
    n_right_cms: int        # accepted by CMS bound
    n_right_exact: int      # recovered from CMS over-count
    n_dropped_similarity: int
    n_dropped_max_keys: int
    n_duplicate_blocks: int
    n_surviving_oversized: int  # unique over-sized blocks after dedupe
    n_surviving_entries: int
    rep_overflow: int


@dataclasses.dataclass
class BlockingResult:
    """Accepted (record, key) assignments across all iterations."""

    rids: np.ndarray        # (M,) int64 record ids
    key_hi: np.ndarray      # (M,) uint32
    key_lo: np.ndarray      # (M,) uint32
    stats: List[IterationStats]
    num_records: int
    # a sharded run's exact-count exchanges, one per iteration
    # (``core.distributed.Exchange``); empty on one device
    exchanges: list = dataclasses.field(default_factory=list)

    @property
    def rep_overflow_total(self) -> int:
        """Over-sized block representatives dropped by the fixed
        ``rep_capacity`` buffer, summed over iterations.

        Nonzero means this result silently diverges from a capless run
        (e.g. the streaming BlockStore, which has no representative
        cap): dropped representatives never enter the survivor table, so
        their blocks neither dedupe nor intersect. The per-iteration
        counts are in ``stats[i].rep_overflow``; a ``RepCapacityWarning``
        fires as the overflow happens.
        """
        return sum(st.rep_overflow for st in self.stats)


# ---------------------------------------------------------------------------
# Jitted single-device iteration
# ---------------------------------------------------------------------------


def rough_classify(cfg: HDBConfig, s: jnp.ndarray, valid: jnp.ndarray,
                   psize: jnp.ndarray):
    """Algorithm 3 decision rule, given CMS estimates ``s``.

    Shared by the batch iteration (which builds the CMS from the live
    entries it is classifying) and the streaming delta path (which queries
    the persistent fold-in CMS held by a BlockStore): both must apply the
    same float32 progress comparison bit-for-bit for the incremental
    result to reproduce the batch result exactly.

    Returns (right_mask, keep_mask, dropped_similarity_mask).
    """
    right = valid & (s <= cfg.max_block_size)
    progress = s.astype(jnp.float32) <= cfg.max_similarity * psize.astype(jnp.float32)
    keep = valid & ~right & progress
    dropped_sim = valid & ~right & ~progress
    return right, keep, dropped_sim


def rough_oversize_detection(cfg: HDBConfig, key: U64, valid: jnp.ndarray,
                             psize: jnp.ndarray):
    """Algorithm 3. Returns (right_mask, keep_mask, dropped_mask, approx_counts)."""
    flat_key = (key[0].reshape(-1), key[1].reshape(-1))
    flat_valid = valid.reshape(-1)
    cms = sketches.cms_build(cfg.cms, flat_key, flat_valid)
    s = sketches.cms_query(cfg.cms, cms, flat_key).reshape(valid.shape)
    right, keep, dropped_sim = rough_classify(cfg, s, valid, psize)
    return right, keep, dropped_sim, s


def dedupe_oversized_reps(r_xhi, r_xlo, r_sz, r_khi, r_klo):
    """Deduplicate over-sized block representatives (Alg. 4 lines 6-9).

    One representative per over-sized block, described by its membership
    fingerprint ``(r_xhi, r_xlo)``, exact size ``r_sz`` and block key
    ``(r_khi, r_klo)``; lanes with the sentinel key are padding. Blocks
    with identical (fingerprint, size) are duplicates; the smallest key
    of each group survives.

    Host numpy, shared by the batch iteration (reps pulled from the
    device between its two steps) and the streaming delta path (reps
    taken from the BlockStore key table). It runs on the host because
    the live representatives are few next to the entries, while a 5-key
    device sort of the fixed-capacity buffer takes minutes of TPU
    compile time per shape. Returns:
      n_dup: number of duplicate representatives dropped
      survivor_in: bool mask aligned with the INPUT lanes marking survivors
    """
    r_xhi, r_xlo, r_khi, r_klo = (np.asarray(x, np.uint32)
                                  for x in (r_xhi, r_xlo, r_khi, r_klo))
    r_sz = np.asarray(r_sz, np.int32)
    live = np.flatnonzero(~((r_khi == _SENT32) & (r_klo == _SENT32)))
    # (fingerprint, size, key) order: duplicates adjacent, smallest key first
    order = live[np.lexsort((r_klo[live], r_khi[live], r_sz[live],
                             r_xlo[live], r_xhi[live]))]
    xhi, xlo, sz = r_xhi[order], r_xlo[order], r_sz[order]
    dup = np.zeros(len(order), bool)
    dup[1:] = (xhi[1:] == xhi[:-1]) & (xlo[1:] == xlo[:-1]) & (sz[1:] == sz[:-1])
    survivor_in = np.zeros(len(r_khi), bool)
    survivor_in[order[~dup]] = True
    return int(dup.sum()), survivor_in


def count_exact(cfg: HDBConfig, key: U64, keep: jnp.ndarray,
                orig: jnp.ndarray, k: int):
    """Algorithm 4, device half (single-shard fast path — see
    core/distributed.py for the all_to_all + Bloom-broadcast variant).

    Runs over flat lanes: ``key`` and ``keep`` per lane, and ``orig``,
    each lane's flat index ``row * k + col`` in the dense ``(n, k)``
    matrix (``n * k`` for a padding lane). Sorts the surviving entries
    by key; segmented count and XOR of rid fingerprints give every entry
    its exact block size and membership fingerprint. Returns
    ``(counted, reps, n_reps, rep_overflow)``: ``counted`` holds the
    key-sorted entries ``classify_exact`` needs, ``reps`` one
    (fingerprint, size, key) representative per over-sized block in a
    ``rep_capacity`` buffer, in key order, padded with sentinels — the
    input of the host ``dedupe_oversized_reps``.
    """
    lanes = keep.shape[0]
    khi = jnp.where(keep, key[0], jnp.uint32(0xFFFFFFFF))
    klo = jnp.where(keep, key[1], jnp.uint32(0xFFFFFFFF))
    (shi, slo), (sorig,) = segments.sort_by_key((khi, klo), [orig])
    srid = sorig // k  # entry (row, col) sits at flat index row * k + col
    skey = (shi, slo)
    live = ~u64.is_sentinel(skey)
    sizes = segments.segment_counts(skey)
    fp = hashing.fingerprint_rid(srid)
    fp = (jnp.where(live, fp[0], 0), jnp.where(live, fp[1], 0))
    xors = segments.segment_xor(skey, fp)
    over = live & (sizes > cfg.max_block_size)

    reps = segments.segment_starts(skey) & over
    # each entry's block ordinal among the over-sized blocks, in key
    # order: reps mark only a block's first entry, so the running count
    # is constant along a block
    rep_ord = jnp.cumsum(reps.astype(jnp.int32)) - 1
    n_reps = rep_ord[-1] + 1
    rep_idx = jnp.nonzero(reps, size=cfg.rep_capacity, fill_value=lanes - 1)[0]
    rep_valid = jnp.arange(cfg.rep_capacity, dtype=jnp.int32) < n_reps
    rep_overflow = jnp.maximum(n_reps - cfg.rep_capacity, 0)
    rep_lanes = (
        jnp.where(rep_valid, xors[0][rep_idx], jnp.uint32(0xFFFFFFFF)),
        jnp.where(rep_valid, xors[1][rep_idx], jnp.uint32(0xFFFFFFFF)),
        jnp.where(rep_valid, sizes[rep_idx], INT32_MAX),
        jnp.where(rep_valid, shi[rep_idx], jnp.uint32(0xFFFFFFFF)),
        jnp.where(rep_valid, slo[rep_idx], jnp.uint32(0xFFFFFFFF)))
    counted = {"sorig": sorig, "sizes": sizes, "live": live, "over": over,
               "rep_ord": rep_ord}
    return counted, rep_lanes, n_reps, rep_overflow


def classify_exact(keep: jnp.ndarray, counted, survivor: jnp.ndarray):
    """Algorithm 4, second device half: classify the key-sorted entries
    given ``survivor``, the host dedupe's flag for each representative
    lane of ``count_exact`` (``rep_capacity`` long). A padding lane's
    ``sorig`` is ``n * k``, out of range: its write is dropped.

    Returns dense masks shaped like ``keep``:
      right_exact: entries whose block the CMS over-counted
      survive:     entries on surviving (deduped) over-sized blocks
      size:        exact block size for ``survive`` entries
    """
    n, k = keep.shape
    nk = n * k
    assert nk < 1 << 30, "block sizes must fit the 30-bit field below"
    cap = survivor.shape[0]
    sorig, rep_ord = counted["sorig"], counted["rep_ord"]
    live, over = counted["live"], counted["over"]
    # an over-sized entry survives iff its block's representative did
    # (duplicates were dropped; blocks past rep_capacity had no lane)
    survive_sorted = (over & (rep_ord < cap)
                      & survivor[jnp.clip(rep_ord, 0, cap - 1)])

    # one scatter back to the dense layout: the size in the low 30 bits,
    # the two masks in the top two; slots no lane holds stay 0
    code = (jnp.where(live, counted["sizes"], 0).astype(jnp.uint32)
            | (survive_sorted.astype(jnp.uint32) << 30)
            | ((live & ~over).astype(jnp.uint32) << 31))
    dense = (jnp.zeros((nk,), jnp.uint32).at[sorig].set(code, mode="drop")
             .reshape(n, k))
    right_exact = (dense >> 31).astype(bool) & keep
    survive = ((dense >> 30) & 1).astype(bool) & keep
    size = (dense & jnp.uint32((1 << 30) - 1)).astype(jnp.int32)
    return right_exact, survive, size


def pad_to_intersect_width(cfg: HDBConfig, key: U64, valid: jnp.ndarray,
                           psize: jnp.ndarray):
    """Pad ``intersect_keys``' output with invalid sentinel columns to
    ``cfg.intersect_width``, so that every iteration after the first has
    one shape and compiles once. Changes no result: an invalid column
    holds no entry."""
    pad = cfg.intersect_width - valid.shape[1]
    if pad <= 0:
        return key, valid, psize
    cols = ((0, 0), (0, pad))
    sent = jnp.uint32(0xFFFFFFFF)
    return ((jnp.pad(key[0], cols, constant_values=sent),
             jnp.pad(key[1], cols, constant_values=sent)),
            jnp.pad(valid, cols), jnp.pad(psize, cols))


def intersect_keys(cfg: HDBConfig, key: U64, survive: jnp.ndarray,
                   size: jnp.ndarray):
    """Algorithm 2: pairwise-intersect each record's over-sized keys.

    Keeps the ``max_oversize_keys`` smallest surviving blocks per record
    (rarest = most discriminative; DESIGN.md §2) and emits all pairwise
    combinations with ``psize = min(parent sizes)``.
    """
    n, k = survive.shape
    ko = min(cfg.max_oversize_keys, k)
    n_keys = jnp.sum(survive.astype(jnp.int32), axis=1)
    row_dead = n_keys > cfg.max_keys  # Alg. 2 line 2
    # order keys: surviving first, then by exact size ascending; key value
    # breaks ties so the cap selection is deterministic (oracle-testable)
    sort_sz = jnp.where(survive, size, INT32_MAX)
    sort_sz, khi_s, klo_s, surv_s = jax.lax.sort(
        (sort_sz, key[0], key[1], survive.astype(jnp.int32)), num_keys=3, dimension=1)
    khi_s, klo_s = khi_s[:, :ko], klo_s[:, :ko]
    sz_s = sort_sz[:, :ko]
    ok = (surv_s[:, :ko] > 0) & ~row_dead[:, None]

    ii, jj = np.triu_indices(ko, 1)
    a = (khi_s[:, ii], klo_s[:, ii])
    b = (khi_s[:, jj], klo_s[:, jj])
    lo_key = u64.minimum(a, b)
    hi_key = u64.where(u64.eq(lo_key, a), b, a)
    new_key = hashing.combine(lo_key, hi_key)
    new_psize = jnp.minimum(sz_s[:, ii], sz_s[:, jj])
    new_valid = ok[:, ii] & ok[:, jj]
    new_khi = jnp.where(new_valid, new_key[0], jnp.uint32(0xFFFFFFFF))
    new_klo = jnp.where(new_valid, new_key[1], jnp.uint32(0xFFFFFFFF))
    # per-record set semantics: one row-sort carrying psize, then mask repeats
    s_khi, s_klo, s_psize, s_valid = jax.lax.sort(
        (new_khi, new_klo, new_psize, new_valid.astype(jnp.int32)),
        num_keys=2, dimension=1)
    same_prev = jnp.concatenate(
        [jnp.zeros((s_khi.shape[0], 1), bool),
         (s_khi[:, 1:] == s_khi[:, :-1]) & (s_klo[:, 1:] == s_klo[:, :-1])], axis=1)
    out_valid = (s_valid > 0) & ~same_prev
    n_dropped_max_keys = jnp.sum(row_dead.astype(jnp.int32))
    return (s_khi, s_klo), out_valid, s_psize, n_dropped_max_keys


def row_extents(valid: jnp.ndarray) -> jnp.ndarray:
    """Per row, one past its last valid column (0 for a row with none):
    every valid entry of row ``r`` lies in its first ``extent[r]`` columns.

    ``intersect_keys`` row-sorts its output with the sentinel keys last
    and ``pad_to_intersect_width`` appends sentinel columns, so from the
    second iteration on a row's extent is about its count of valid
    entries, whatever the padded width.
    """
    cols = jnp.arange(1, valid.shape[1] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(valid, cols, 0), axis=1)


@jax.jit
def lanes_needed(valid: jnp.ndarray) -> jnp.ndarray:
    """Lanes the count step's compaction gathers: the sum of the rows'
    extents, at least the number of valid entries."""
    return jnp.sum(row_extents(valid))


def lane_bucket(needed: int, slots: int) -> int:
    """Lanes the count step runs over: the next power of two at or above
    ``needed``, at least ``LANE_FLOOR``, or all ``slots`` (dense) where
    that bucket would reach them."""
    bucket = max(LANE_FLOOR, 1 << max(needed - 1, 0).bit_length())
    return slots if bucket >= slots else bucket


def compact_lanes(valid: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """The dense flat index ``row * k + col`` of each of ``lanes`` lanes:
    the first ``extent`` columns of each row (``row_extents``), rows in
    order, then padding lanes at ``n * k``. ``lanes`` must hold
    ``lanes_needed(valid)``. Indices ascend along the lanes, as in the
    dense matrix."""
    n, k = valid.shape
    extent = row_extents(valid)
    end = jnp.cumsum(extent)
    start = end - extent
    # each lane's row: every row marks its first lane, a running max
    # fills the rest (a row with no extent shares its start with the next
    # row that has one, and the larger row id wins)
    row = jax.lax.cummax(
        jnp.zeros((lanes,), jnp.int32).at[start].max(
            jnp.arange(n, dtype=jnp.int32), mode="drop"))
    lane = jnp.arange(lanes, dtype=jnp.int32)
    return jnp.where(lane < end[-1], row * k + lane - start[row], n * k)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _count_step(cfg: HDBConfig, lanes: int, keys_packed: jnp.ndarray,
                valid: jnp.ndarray, psize: jnp.ndarray):
    """Algorithms 3 and 4 over ``lanes`` lanes: the whole ``(n, k)``
    matrix when ``lanes >= n * k``, else its live part gathered by
    ``compact_lanes``. The rough masks come back in the dense layout."""
    n, k = valid.shape
    dense = lanes >= n * k
    flat = (keys_packed[..., 0].reshape(-1), keys_packed[..., 1].reshape(-1),
            valid.reshape(-1), psize.reshape(-1))
    if dense:
        orig = jnp.arange(n * k, dtype=jnp.int32)
        khi, klo, live, ps = flat
    else:
        orig = compact_lanes(valid, lanes)
        at = jnp.minimum(orig, n * k - 1)
        khi, klo, live, ps = (x[at] for x in flat)
        live = live & (orig < n * k)
    right_cms, keep, dropped_sim, _ = rough_oversize_detection(
        cfg, (khi, klo), live, ps)
    counted, reps, n_reps, rep_overflow = count_exact(
        cfg, (khi, klo), keep, orig, k)
    if dense:
        rough = tuple(m.reshape(n, k) for m in (right_cms, keep, dropped_sim))
    else:
        # one scatter back to the dense layout, padding lanes dropped
        code = (right_cms.astype(jnp.uint8) | (keep.astype(jnp.uint8) << 1)
                | (dropped_sim.astype(jnp.uint8) << 2))
        packed = (jnp.zeros((n * k,), jnp.uint8).at[orig].set(code, mode="drop")
                  .reshape(n, k))
        rough = tuple(((packed >> b) & 1).astype(bool) for b in range(3))
    return rough, counted, reps, n_reps, rep_overflow


@functools.partial(jax.jit, static_argnums=0)
def _intersect_step(cfg: HDBConfig, keys_packed: jnp.ndarray,
                    valid: jnp.ndarray, rough, counted, survivor):
    key = (keys_packed[..., 0], keys_packed[..., 1])
    right_cms, keep, dropped_sim = rough
    right_exact, survive, size = classify_exact(keep, counted, survivor)
    accepted = right_cms | right_exact
    new_key, new_valid, new_psize, n_dropped_mk = intersect_keys(cfg, key, survive, size)
    new_key, new_valid, new_psize = pad_to_intersect_width(
        cfg, new_key, new_valid, new_psize)
    stats = {
        "n_live_keys": jnp.sum(valid.astype(jnp.int32)),
        "n_right_cms": jnp.sum(right_cms.astype(jnp.int32)),
        "n_right_exact": jnp.sum(right_exact.astype(jnp.int32)),
        "n_dropped_similarity": jnp.sum(dropped_sim.astype(jnp.int32)),
        "n_dropped_max_keys": n_dropped_mk,
        "n_surviving_entries": jnp.sum(survive.astype(jnp.int32)),
        "next_lanes_needed": lanes_needed(new_valid),
    }
    new_state = (jnp.stack([new_key[0], new_key[1]], axis=-1), new_valid, new_psize)
    return accepted, new_state, stats


def hdb_iteration(cfg: HDBConfig, keys_packed: jnp.ndarray, valid: jnp.ndarray,
                  psize: jnp.ndarray, lanes: int | None = None):
    """One full HDB iteration. Returns (accepted_mask, new_state, stats).

    Two jitted device steps (CMS + exact counts; classification +
    intersection) around the host dedupe of the over-sized
    representatives, whose survivor flags go back as one fixed
    ``rep_capacity`` mask so the second step compiles once per key width
    and lane count. The count step runs over ``lanes`` lanes
    (``lane_bucket``; read from ``valid`` when not given); ``stats``
    carries ``next_lanes_needed``, the ``lanes_needed`` of the new state.
    """
    if lanes is None:
        lanes = lane_bucket(int(lanes_needed(valid)), valid.size)
    with obs.span("repro.hdb.count"):
        rough, counted, reps, n_reps, rep_overflow = _count_step(
            cfg, lanes, keys_packed, valid, psize)
        m = min(int(n_reps), cfg.rep_capacity)
        reps = [np.asarray(r)[:m] for r in reps]
    with obs.span("repro.hdb.reps"):
        n_dup, survivor = dedupe_oversized_reps(*reps)
        flags = np.zeros(cfg.rep_capacity, bool)
        flags[:m] = survivor
    with obs.span("repro.hdb.intersect"):
        accepted, new_state, stats = _intersect_step(
            cfg, keys_packed, valid, rough, counted, jax.device_put(flags))
    stats.update(n_duplicate_blocks=n_dup,
                 n_surviving_oversized=int(survivor.sum()),
                 rep_overflow=int(rep_overflow))
    return accepted, new_state, stats


# ---------------------------------------------------------------------------
# Host-side driver (Algorithm 1)
# ---------------------------------------------------------------------------


def hashed_dynamic_blocking(
    keys_packed: jnp.ndarray,
    valid: jnp.ndarray,
    cfg: HDBConfig = HDBConfig(),
    verbose: bool = False,
) -> BlockingResult:
    """Run HDB to convergence over a dense top-level key matrix.

    Args:
      keys_packed: (N, K, 2) uint32 u64 keys from ``blocks.build_keys``.
      valid: (N, K) bool.
    """
    n = valid.shape[0]
    # explicit upload: eager jnp.full is an implicit host->device transfer
    # (rejected under jax.transfer_guard("disallow") — repro.analysis R001)
    psize = jnp.asarray(np.full(valid.shape, INT32_MAX, np.int32))
    acc_rid: List[np.ndarray] = []
    acc_hi: List[np.ndarray] = []
    acc_lo: List[np.ndarray] = []
    all_stats: List[IterationStats] = []
    needed = int(lanes_needed(valid))
    for it in range(cfg.max_iterations):
        with obs.span("repro.hdb.iteration", iteration=it):
            lanes = lane_bucket(needed, valid.size)
            accepted, (new_keys, new_valid, new_psize), stats = hdb_iteration(
                cfg, keys_packed, valid, psize, lanes)
            with obs.span("repro.hdb.accept"):
                acc_np = np.asarray(accepted)
                ridx, kidx = np.nonzero(acc_np)
                keys_np = np.asarray(keys_packed)
                acc_rid.append(ridx.astype(np.int64))
                acc_hi.append(keys_np[ridx, kidx, 0])
                acc_lo.append(keys_np[ridx, kidx, 1])
                needed = int(stats.pop("next_lanes_needed"))
                st = IterationStats(iteration=it, **{k: int(v) for k, v in
                                                     stats.items()})
            obs.mark("repro.hdb.iteration.counts",
                     slots=valid.size, live=st.n_live_keys, lanes=lanes,
                     reps=st.n_duplicate_blocks + st.n_surviving_oversized,
                     surviving=st.n_surviving_oversized)
        all_stats.append(st)
        logger.log(logging.INFO if verbose else logging.DEBUG,
                   "[hdb] iter=%d %s", it, st)
        if st.rep_overflow:
            warnings.warn(
                f"[hdb] representative capacity overflow ({st.rep_overflow} "
                "blocks dropped); raise HDBConfig.rep_capacity",
                RepCapacityWarning, stacklevel=2)
        keys_packed, valid, psize = new_keys, new_valid, new_psize
        if st.n_surviving_entries == 0:
            break
    else:
        leftover = int(jnp.sum(valid.astype(jnp.int32)))
        if leftover:
            logger.info("[hdb] max_iterations reached with %d live keys dropped",
                        leftover)
    return BlockingResult(
        rids=np.concatenate(acc_rid) if acc_rid else np.zeros((0,), np.int64),
        key_hi=np.concatenate(acc_hi) if acc_hi else np.zeros((0,), np.uint32),
        key_lo=np.concatenate(acc_lo) if acc_lo else np.zeros((0,), np.uint32),
        stats=all_stats,
        num_records=n,
    )
