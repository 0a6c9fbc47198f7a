"""System tests for Hashed Dynamic Blocking (Algorithms 1-4)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blocks, hdb, pairs, baselines
from repro.core.blocks import ColumnBlocking, TokenColumn
from repro.data import synthetic, metrics


@pytest.fixture(scope="module")
def corpus():
    return synthetic.generate(synthetic.SyntheticSpec(num_entities=2000, seed=3))


@pytest.fixture(scope="module")
def built(corpus):
    return blocks.build_keys(corpus.columns, corpus.blocking)


def _block_sizes(result):
    b = pairs.build_blocks(result, min_size=1)
    return b.size


def test_every_accepted_block_is_right_sized(built):
    keys, valid = built
    cfg = hdb.HDBConfig(max_block_size=50, max_iterations=6)
    res = hdb.hashed_dynamic_blocking(keys, valid, cfg)
    sizes = _block_sizes(res)
    assert len(sizes) > 0
    assert sizes.max() <= 50


def test_no_duplicate_assignments(built):
    keys, valid = built
    res = hdb.hashed_dynamic_blocking(keys, valid, hdb.HDBConfig(max_block_size=50))
    key64 = (res.key_hi.astype(np.uint64) << np.uint64(32)) | res.key_lo
    assign = np.stack([key64, res.rids.astype(np.uint64)], 1)
    assert len(np.unique(assign, axis=0)) == len(assign)


def test_hdb_recall_superset_of_threshold(built, corpus):
    """HDB accepts every block THR accepts (iteration 1 == THR) plus
    intersections of the over-sized remainder => PC(HDB) >= PC(THR)."""
    keys, valid = built
    labeled = corpus.labeled_pairs()
    thr = baselines.threshold_blocking(keys, valid, max_block_size=50)
    res = hdb.hashed_dynamic_blocking(keys, valid, hdb.HDBConfig(max_block_size=50))
    m_thr = metrics.evaluate(thr, corpus, labeled)
    m_hdb = metrics.evaluate(res, corpus, labeled)
    assert m_hdb.pc >= m_thr.pc - 1e-9
    assert m_hdb.pc > 0.5  # sanity: blocking actually finds duplicates


def test_hdb_finds_intersections_threshold_misses():
    """Two over-sized blocks whose intersection is a right-sized block:
    THR drops everything; HDB must find the intersection (the paper's
    'Jones' x 'Tim' example)."""
    n = 400
    # column A: everyone shares value a0 => one giant block
    col_a = TokenColumn(jnp.full((n, 1), 7, jnp.uint32), jnp.ones((n, 1), bool))
    # column B: first 30 records share b0, rest unique
    b = np.arange(n, dtype=np.uint32) + 1000
    b[:30] = 999
    col_b = TokenColumn(jnp.asarray(b[:, None]), jnp.ones((n, 1), bool))
    keys, valid = blocks.build_keys(
        {"a": col_a, "b": col_b},
        {"a": ColumnBlocking.identity(), "b": ColumnBlocking.identity()})
    cfg = hdb.HDBConfig(max_block_size=100, max_iterations=4)
    thr = baselines.threshold_blocking(keys, valid, max_block_size=100)
    res = hdb.hashed_dynamic_blocking(keys, valid, cfg)
    thr_blocks = pairs.build_blocks(thr)
    hdb_blocks = pairs.build_blocks(res)
    # THR: block A over-sized (400) dropped; block b0 (30) kept.
    assert thr_blocks.num_blocks == 1
    # HDB additionally intersects A with b0 -> same 30 records (duplicate
    # membership -> deduped), so pairs must cover the 30-clique.
    pset = pairs.dedupe_pairs(hdb_blocks)
    clique = set()
    for x, y in zip(pset.a, pset.b):
        if x < 30 and y < 30:
            clique.add((int(x), int(y)))
    assert len(clique) == 30 * 29 // 2


def test_duplicate_blocks_are_deduped():
    """Two columns with identical partitions produce identical over-sized
    blocks; after intersection they'd explode quadratically unless deduped
    (paper Alg. 4). Verify the iteration reports duplicates."""
    n = 300
    v = np.repeat(np.arange(2, dtype=np.uint32), n // 2)
    cols = {
        "a": TokenColumn(jnp.asarray(v[:, None]), jnp.ones((n, 1), bool)),
        "b": TokenColumn(jnp.asarray((v + 10)[:, None]), jnp.ones((n, 1), bool)),
    }
    spec = {k: ColumnBlocking.identity() for k in cols}
    keys, valid = blocks.build_keys(cols, spec)
    cfg = hdb.HDBConfig(max_block_size=50, max_iterations=3)
    res = hdb.hashed_dynamic_blocking(keys, valid, cfg)
    # iteration 1: intersecting the deduped pair of over-sized blocks can
    # only produce blocks identical to their parents -> progress heuristic
    # kills them; nothing right-sized ever appears.
    assert sum(s.n_duplicate_blocks for s in res.stats) >= 2
    assert len(res.rids) == 0


def test_rep_capacity_overflow_is_warned_and_counted():
    """A tiny ``rep_capacity`` drops over-sized block representatives —
    a silent divergence from the capless streaming store unless surfaced:
    the run must emit RepCapacityWarning AND report the dropped count in
    ``BlockingResult.rep_overflow_total``."""
    n, m = 160, 16      # two 16-way partitions: 32 over-sized 10-blocks
    va = (np.arange(n, dtype=np.uint32) % m)
    vb = (np.arange(n, dtype=np.uint32) // (n // m))
    cols = {
        "a": TokenColumn(jnp.asarray(va[:, None]), jnp.ones((n, 1), bool)),
        "b": TokenColumn(jnp.asarray(vb[:, None]), jnp.ones((n, 1), bool)),
    }
    spec = {k: ColumnBlocking.identity() for k in cols}
    keys, valid = blocks.build_keys(cols, spec)
    cfg_small = hdb.HDBConfig(max_block_size=5, max_iterations=2,
                              rep_capacity=4)
    with pytest.warns(hdb.RepCapacityWarning):
        res = hdb.hashed_dynamic_blocking(keys, valid, cfg_small)
    # iteration 0 found 2*m over-sized representatives, capacity 4
    assert res.stats[0].rep_overflow == 2 * m - 4
    assert res.rep_overflow_total >= 2 * m - 4
    # a capacious run keeps every representative and reports zero
    cfg_big = hdb.HDBConfig(max_block_size=5, max_iterations=2,
                            rep_capacity=1 << 10)
    res_big = hdb.hashed_dynamic_blocking(keys, valid, cfg_big)
    assert res_big.rep_overflow_total == 0
    # the count quantifies the divergence: dropped reps' blocks vanish
    # from the survivor set instead of surviving to intersection
    assert res.stats[0].n_surviving_oversized == 4
    assert res_big.stats[0].n_surviving_oversized == 2 * m


def test_progress_heuristic_terminates():
    """Blocks too similar to parents are discarded (MAX_SIMILARITY)."""
    n = 500
    v = np.zeros(n, np.uint32)
    cols = {
        "a": TokenColumn(jnp.asarray(v[:, None]), jnp.ones((n, 1), bool)),
        "b": TokenColumn(jnp.asarray(v[:, None] + 5), jnp.ones((n, 1), bool)),
        "c": TokenColumn(jnp.asarray(v[:, None] + 9), jnp.ones((n, 1), bool)),
    }
    spec = {k: ColumnBlocking.identity() for k in cols}
    keys, valid = blocks.build_keys(cols, spec)
    res = hdb.hashed_dynamic_blocking(
        keys, valid, hdb.HDBConfig(max_block_size=100, max_iterations=6))
    assert len(res.rids) == 0
    assert len(res.stats) < 6  # converged before the cap, didn't spin


def test_max_keys_guard():
    """Records with more than MAX_KEYS over-sized keys are dropped from
    intersection (Alg. 2 line 2). Six *distinct* binary partitions (bit i of
    rid) give every record 6 over-sized keys with distinct memberships."""
    n = 256
    rid = np.arange(n, dtype=np.uint32)
    cols = {
        f"c{i}": TokenColumn(jnp.asarray(((rid >> i) & 1)[:, None] + 10 * i),
                             jnp.ones((n, 1), bool))
        for i in range(6)
    }
    spec = {k: ColumnBlocking.identity() for k in cols}
    keys, valid = blocks.build_keys(cols, spec)
    cfg = hdb.HDBConfig(max_block_size=50, max_keys=4, max_iterations=2)
    res = hdb.hashed_dynamic_blocking(keys, valid, cfg)
    assert res.stats[0].n_dropped_max_keys == n
    assert len(res.rids) == 0
    # with a permissive max_keys the same corpus DOES produce intersections
    res2 = hdb.hashed_dynamic_blocking(
        keys, valid, hdb.HDBConfig(max_block_size=50, max_keys=80,
                                   max_iterations=4))
    assert len(res2.rids) > 0


def test_cms_overcount_recovery(built):
    """With a tiny CMS, many right-sized blocks get over-counted; the exact
    stage must recover them (identical final accepted set modulo none lost)."""
    keys, valid = built
    big = hdb.hashed_dynamic_blocking(
        keys, valid, hdb.HDBConfig(max_block_size=50, cms_width=1 << 20))
    small = hdb.hashed_dynamic_blocking(
        keys, valid, hdb.HDBConfig(max_block_size=50, cms_width=1 << 10))
    def key_set(r):
        return set(zip(r.rids.tolist(), r.key_hi.tolist(), r.key_lo.tolist()))
    assert key_set(big) == key_set(small)
    assert sum(s.n_right_exact for s in small.stats) > 0


def _padded_state(rng, n, k, needed, card, cfg):
    """A state in the layout ``intersect_keys`` + ``pad_to_intersect_width``
    leave: each row's keys sorted and front-packed, sentinel columns after,
    a few interior entries invalid (as a repeated key is), and
    ``lanes_needed`` exactly ``needed``."""
    cap = min(k, 2 * needed // n + 1)
    extent = np.minimum(rng.integers(0, cap + 1, n), k)
    while extent.sum() != needed:  # move the sum onto ``needed``
        d = np.sign(needed - extent.sum())
        room = np.flatnonzero((extent < k) if d > 0 else (extent > 0))
        extent[rng.choice(room)] += d
    khi = np.full((n, k), 0xFFFFFFFF, np.uint32)
    klo = khi.copy()
    valid = np.zeros((n, k), bool)
    for r, e in enumerate(extent):
        ids = np.sort(rng.choice(card, e, replace=False)).astype(np.uint32)
        khi[r, :e], klo[r, :e] = ids, ids * np.uint32(2654435761)
        valid[r, :e] = True
        valid[r, :max(e - 1, 0)] &= rng.random(max(e - 1, 0)) > 0.05
    psize = np.where(valid, rng.integers(cfg.max_block_size + 1,
                                         10 * cfg.max_block_size, (n, k)), 0)
    return (jnp.asarray(np.stack([khi, klo], -1)), jnp.asarray(valid),
            jnp.asarray(psize.astype(np.int32)))


def _iteration(cfg, state, lanes):
    acc, new_state, stats = hdb.hdb_iteration(cfg, *state, lanes)
    return ([np.asarray(acc)] + [np.asarray(x) for x in new_state],
            {k: int(v) for k, v in stats.items()})


@pytest.mark.parametrize("case", ["deep_run", "pow2", "floor", "dense"])
def test_compacted_count_matches_dense(case, request, monkeypatch):
    """The count step over the compacted live lanes gives what the dense
    count over every slot gives: accepted entries, the next state and
    every statistic, bit for bit."""
    if case == "deep_run":
        # >= 3 iterations, the first dense, the later ones sparse
        keys, valid = request.getfixturevalue("built")
        cfg = hdb.HDBConfig(max_block_size=20, cms_width=1 << 12)
        chosen = []
        bucket = hdb.lane_bucket
        monkeypatch.setattr(hdb, "lane_bucket", lambda needed, slots: chosen.append(
            (bucket(needed, slots), slots)) or chosen[-1][0])
        got = hdb.hashed_dynamic_blocking(keys, valid, cfg)
        monkeypatch.setattr(hdb, "lane_bucket", lambda needed, slots: slots)
        want = hdb.hashed_dynamic_blocking(keys, valid, cfg)
        assert len(got.stats) >= 3
        assert chosen[0][0] == chosen[0][1]          # iteration 0 dense
        assert all(lanes < slots for lanes, slots in chosen[1:])
        assert chosen[-1][0] == hdb.LANE_FLOOR
        assert got.stats == want.stats
        for a, b in [(got.rids, want.rids), (got.key_hi, want.key_hi),
                     (got.key_lo, want.key_lo)]:
            np.testing.assert_array_equal(a, b)
        return
    n, k = 1024, 120
    needed, expect = {"pow2": (8192, 8192), "floor": (300, hdb.LANE_FLOOR),
                      "dense": (70000, n * k)}[case]
    # ~40 entries a key and about as many CMS buckets as keys: blocks on
    # both sides of max_block_size, and CMS over-counts to recover
    card = needed // 40 + 2
    cfg = hdb.HDBConfig(max_block_size=30, rep_capacity=1 << 12,
                        cms_width=1 << (card - 1).bit_length())
    state = _padded_state(np.random.default_rng(11), n, k, needed, card, cfg)
    assert int(hdb.lanes_needed(state[1])) == needed
    assert hdb.lane_bucket(needed, n * k) == expect
    chosen_arrays, chosen_stats = _iteration(cfg, state, None)
    # the chosen layout against the other one: the dense count, or the
    # tightest compaction where the dense count was chosen
    other = needed if expect == n * k else n * k
    other_arrays, other_stats = _iteration(cfg, state, other)
    assert chosen_stats == other_stats
    # both outcomes occur: accepted blocks and over-sized survivors
    assert chosen_stats["n_right_cms"] + chosen_stats["n_right_exact"] > 0
    assert chosen_stats["n_surviving_oversized"] > 0
    for a, b in zip(chosen_arrays, other_arrays):
        np.testing.assert_array_equal(a, b)


def test_intersection_output_is_front_packed(built):
    """What the gathered compaction relies on: after ``intersect_keys``
    and ``pad_to_intersect_width`` each row's valid entries lie within its
    first ``c_r`` columns (``c_r`` its non-sentinel keys), and
    ``compact_lanes`` hands every valid slot to exactly one lane, in
    dense order."""
    keys, valid = built
    cfg = hdb.HDBConfig(max_block_size=20, cms_width=1 << 12)
    psize = jnp.asarray(np.full(valid.shape, hdb.INT32_MAX, np.int32))
    _, (keys1, valid1, _), _ = hdb.hdb_iteration(cfg, keys, valid, psize)
    keys1, valid1 = np.asarray(keys1), np.asarray(valid1)
    n, k = valid1.shape
    assert k == cfg.intersect_width and valid1.any()
    c = (~((keys1[..., 0] == 0xFFFFFFFF) & (keys1[..., 1] == 0xFFFFFFFF))
         ).sum(axis=1)
    assert not (valid1 & (np.arange(k)[None, :] >= c[:, None])).any()
    extent = np.asarray(hdb.row_extents(jnp.asarray(valid1)))
    assert (extent <= c).all()
    needed = int(hdb.lanes_needed(jnp.asarray(valid1)))
    assert valid1.sum() <= needed == extent.sum()
    lanes = hdb.lane_bucket(needed, n * k)
    assert needed <= lanes < n * k
    orig = np.asarray(hdb.compact_lanes(jnp.asarray(valid1), lanes))
    real = orig[orig < n * k]
    assert len(real) == needed and (orig[needed:] == n * k).all()
    assert (np.diff(real) > 0).all()
    assert set(np.flatnonzero(valid1.reshape(-1))) <= set(real.tolist())


def test_padding_lanes_never_overwrite_a_real_slot():
    """The compacted count has padding lanes past the live ones; their
    writes into the dense layout are dropped, so the slot-0 entry of an
    over-sized block keeps its exact size and survival."""
    n, k, m = 64, 8, 40
    khi = np.full((n, k), 0xFFFFFFFF, np.uint32)
    klo = khi.copy()
    khi[:m, 0], klo[:m, 0] = 5, 6       # one 40-record block in column 0
    valid = khi != 0xFFFFFFFF
    state = (jnp.asarray(np.stack([khi, klo], -1)), jnp.asarray(valid),
             jnp.asarray(np.where(valid, 1000, 0).astype(np.int32)))
    cfg = hdb.HDBConfig(max_block_size=10, cms_width=1 << 8,
                        rep_capacity=1 << 4)
    lanes = 128                          # 40 live lanes, 88 padding lanes
    rough, counted, _, n_reps, _ = hdb._count_step(cfg, lanes, *state)
    assert int(n_reps) == 1
    survivor = jnp.ones((cfg.rep_capacity,), bool)
    _, survive, size = hdb.classify_exact(rough[1], counted, survivor)
    np.testing.assert_array_equal(np.asarray(survive), valid)
    np.testing.assert_array_equal(np.asarray(size)[:, 0],
                                  np.where(valid[:, 0], m, 0))
    assert int((np.asarray(counted["sorig"]) == n * k).sum()) == lanes - m
    dense, _, _, _, _ = hdb._count_step(cfg, n * k, *state)
    for a, b in zip(rough, dense):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
