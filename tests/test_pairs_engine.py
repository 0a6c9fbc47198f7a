"""Oracle tests for the device-side pair materialization engine.

The numpy (shift-method reference), JAX, and Pallas (interpret) backends
must emit BIT-IDENTICAL deduped PairSets — including the budget-exceeded
uniform-sampling fallback and the largest-block-wins provenance — on
randomized block layouts. The triangular decode kernel is additionally
checked against the float64 closed-form oracle at the int32 contract
boundary (n = MAX_BLOCK_N).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st

from repro.core import blocks as blocks_mod, hdb, pairs
from repro.core.distributed import (dedupe_pairs_distributed,
                                    materialize_pairs_distributed)
from repro.kernels.pairs import (MAX_BLOCK_N, decode_chunk, dedupe_device,
                                 dedupe_packed_device, pack_sort_words,
                                 pair_route_owner, tri_decode_jnp,
                                 tri_decode_pallas, unpack_words_host)
from repro.kernels.pairs import ref as pairs_ref
from repro.data import synthetic

BACKENDS = ("numpy", "jax", "pallas")


def _random_blocks(seed, n_blocks, max_size, universe):
    """Random CSR Blocks with heavy membership overlap (cross-block dupes)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(2, max_size + 1, n_blocks).astype(np.int64)
    start = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    members = np.concatenate(
        [np.sort(rng.choice(universe, n, replace=False)) for n in sizes]
    ).astype(np.int64)
    zu = np.zeros(n_blocks, np.uint32)
    return pairs.Blocks(zu, zu, start, sizes, members)


def _assert_pairsets_equal(got, want, label):
    assert got.exact == want.exact, label
    assert got.total_slots == want.total_slots, label
    np.testing.assert_array_equal(got.a, want.a, err_msg=label)
    np.testing.assert_array_equal(got.b, want.b, err_msg=label)
    np.testing.assert_array_equal(got.src_size, want.src_size, err_msg=label)


# ---------------------------------------------------------------------------
# backend parity on randomized layouts
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000),
       n_blocks=st.sampled_from([1, 7, 40]),
       max_size=st.sampled_from([3, 16, 48]))
def test_backends_agree_exact(seed, n_blocks, max_size):
    blk = _random_blocks(seed, n_blocks, max_size, universe=400)
    want = pairs.dedupe_pairs(blk, backend="numpy")
    assert want.exact
    # exact results are the distinct-pair set: cross-check count bounds
    assert 0 < len(want.a) <= blk.num_pair_slots
    for be in ("jax", "pallas"):
        got = pairs.dedupe_pairs(blk, backend=be)
        _assert_pairsets_equal(got, want, f"backend={be} seed={seed}")


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_backends_agree_sampling_fallback(seed):
    blk = _random_blocks(seed, 30, 40, universe=300)
    budget = blk.num_pair_slots // 3
    want = pairs.dedupe_pairs(blk, budget=budget, backend="numpy",
                              sample_seed=seed)
    assert not want.exact
    assert want.total_slots == blk.num_pair_slots  # counting stays exact
    assert len(want.a) <= budget
    for be in ("jax", "pallas"):
        got = pairs.dedupe_pairs(blk, budget=budget, backend=be,
                                 sample_seed=seed)
        _assert_pairsets_equal(got, want, f"backend={be} seed={seed}")


def test_sample_slots_budget_bounded_allocation_and_determinism():
    """_sample_slots must draw exactly min(budget, total) distinct slots
    in O(budget) memory — the old permutation branch materialized and
    shuffled slot spaces up to 2**24 (~128 MiB) for any budget."""
    import tracemalloc

    total, budget = 1 << 24, 1024
    tracemalloc.start()
    s1 = pairs._sample_slots(total, budget, seed=42)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # old code: >= 128 MiB int64 permutation; new bound is O(budget)
    assert peak < 4 << 20, f"peak allocation {peak} bytes is not O(budget)"
    assert len(s1) == budget
    assert np.all(np.diff(s1) > 0) and 0 <= s1[0] and s1[-1] < total
    # deterministic per seed, sensitive to it
    np.testing.assert_array_equal(s1, pairs._sample_slots(total, budget, 42))
    assert not np.array_equal(s1, pairs._sample_slots(total, budget, 43))
    # dense draws still return exactly budget distinct slots
    s2 = pairs._sample_slots(100, 90, seed=0)
    assert len(s2) == 90 and len(np.unique(s2)) == 90
    assert len(pairs._sample_slots(100, 200, seed=0)) == 100
    assert len(pairs._sample_slots(100, 0, seed=0)) == 0


def test_sampling_is_deterministic_and_seed_sensitive():
    blk = _random_blocks(0, 30, 40, universe=300)
    budget = blk.num_pair_slots // 4
    p1 = pairs.dedupe_pairs(blk, budget=budget, backend="jax", sample_seed=7)
    p2 = pairs.dedupe_pairs(blk, budget=budget, backend="jax", sample_seed=7)
    p3 = pairs.dedupe_pairs(blk, budget=budget, backend="jax", sample_seed=8)
    np.testing.assert_array_equal(p1.a, p2.a)
    np.testing.assert_array_equal(p1.b, p2.b)
    assert len(p1.a) != len(p3.a) or not np.array_equal(p1.a, p3.a)


# ---------------------------------------------------------------------------
# largest-block-wins provenance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_largest_block_wins_provenance(backend):
    # pair (0, 1) appears in a 5-block, a 9-block, and a 3-block
    groups = [np.arange(5), np.arange(9), np.array([0, 1, 50])]
    sizes = np.array([len(g) for g in groups], np.int64)
    start = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    blk = pairs.Blocks(np.zeros(3, np.uint32), np.zeros(3, np.uint32),
                       start, sizes,
                       np.concatenate(groups).astype(np.int64))
    p = pairs.dedupe_pairs(blk, backend=backend)
    by_pair = {(a, b): s for a, b, s in zip(p.a, p.b, p.src_size)}
    assert by_pair[(0, 1)] == 9          # largest source block wins
    assert by_pair[(0, 50)] == 3         # only source
    assert by_pair[(5, 8)] == 9
    # distinct set: the 5-block is a subset of the 9-block
    assert len(p.a) == 9 * 8 // 2 + 2    # C(9,2) + (0,50) + (1,50)


# ---------------------------------------------------------------------------
# triangular decode kernel at the contract boundary
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [2, 3, 17, 1000, MAX_BLOCK_N])
def test_tri_decode_matches_oracle_at_boundaries(n):
    last = n * (n - 1) // 2 - 1
    t = np.unique(np.clip(
        np.array([0, 1, n - 2, n - 1, last // 2, last - 1, last]), 0, last))
    n_arr = np.full(len(t), n, np.int64)
    ri, rj = pairs_ref.tri_decode_ref(t, n_arr)
    # ref must satisfy the bitmap identity b(i,j,n) == t
    np.testing.assert_array_equal(pairs.pair_bit_index(ri, rj, n), t)
    # tri_decode_jnp is a jit-free mirror meant to trace inside
    # decode_chunk; call it the way its callers do
    gi, gj = jax.jit(tri_decode_jnp, static_argnames=("steps",))(
        jnp.asarray(t.astype(np.int32)), jnp.asarray(n_arr.astype(np.int32)))
    np.testing.assert_array_equal(np.asarray(gi), ri)
    np.testing.assert_array_equal(np.asarray(gj), rj)


def test_tri_decode_pallas_matches_jnp_dense():
    rng = np.random.default_rng(0)
    n = rng.integers(2, 300, 4096).astype(np.int64)
    t = (rng.random(4096) * (n * (n - 1) // 2)).astype(np.int64)
    t32, n32 = t.astype(np.int32), n.astype(np.int32)
    ji, jj = jax.jit(tri_decode_jnp, static_argnames=("steps",))(
        jnp.asarray(t32), jnp.asarray(n32))
    pi, pj = tri_decode_pallas(jnp.asarray(t32.reshape(-1, 128)),
                               jnp.asarray(n32.reshape(-1, 128)))
    np.testing.assert_array_equal(np.asarray(pi).reshape(-1), np.asarray(ji))
    np.testing.assert_array_equal(np.asarray(pj).reshape(-1), np.asarray(jj))


def test_decode_chunk_validity_immune_to_int32_wrap():
    """Padding lanes past total near 2**31 must stay invalid even though
    base + offset wraps int32 (regression: wrapped-negative slots used to
    pass the `slots < total` check)."""
    total = 2**31 - 100
    # a single synthetic block table; only validity counting matters here
    cum = jnp.asarray(np.array([0, total], np.int32))
    start = jnp.asarray(np.zeros(1, np.int32))
    size = jnp.asarray(np.array([3], np.int32))
    members = jnp.asarray(np.array([0, 1, 2], np.int32))
    base = total - 512
    _, _, _, v = decode_chunk(cum, start, size, members,
                              jax.device_put(np.int32(base)),
                              jax.device_put(np.int32(total)), chunk=1024)
    v = np.asarray(v)
    assert v.sum() == 512 and v[:512].all() and not v[512:].any()


def test_decode_chunk_masks_out_of_range_slots():
    blk = _random_blocks(1, 4, 6, universe=50)
    total = blk.num_pair_slots
    cum = jnp.asarray(pairs_ref.cum_pair_counts(blk.size).astype(np.int32))
    a, b, s, v = decode_chunk(
        cum, jnp.asarray(blk.start.astype(np.int32)),
        jnp.asarray(blk.size.astype(np.int32)),
        jnp.asarray(blk.members.astype(np.int32)),
        jax.device_put(np.int32(0)), jax.device_put(np.int32(total)),
        chunk=1024)
    v = np.asarray(v)
    assert v.sum() == total and not v[total:].any()


def test_dedupe_device_pushes_invalid_to_tail():
    a = jnp.asarray(np.array([5, 3, 3, 9], np.int32))
    b = jnp.asarray(np.array([6, 4, 4, 11], np.int32))
    s = jnp.asarray(np.array([2, 7, 3, 2], np.int32))
    valid = jnp.asarray(np.array([True, True, True, False]))
    sa, sb, ss, w = dedupe_device(a, b, s, valid)
    w = np.asarray(w)
    assert w.sum() == 2
    np.testing.assert_array_equal(np.asarray(sa)[w], [3, 5])
    np.testing.assert_array_equal(np.asarray(ss)[w], [7, 2])  # largest wins


# ---------------------------------------------------------------------------
# integration: HDB result -> blocks -> engine; distributed decode
# ---------------------------------------------------------------------------


def test_engine_on_real_hdb_blocks():
    corpus = synthetic.generate(synthetic.SyntheticSpec(num_entities=150, seed=2))
    keys, valid = blocks_mod.build_keys(corpus.columns, corpus.blocking)
    res = hdb.hashed_dynamic_blocking(keys, valid,
                                      hdb.HDBConfig(max_block_size=25))
    blk = pairs.build_blocks(res)
    want = pairs.dedupe_pairs(blk, backend="numpy")
    for be in ("jax", "pallas"):
        _assert_pairsets_equal(pairs.dedupe_pairs(blk, backend=be), want, be)


def test_distributed_materialization_matches_single_device():
    blk = _random_blocks(4, 50, 30, universe=600)
    mesh = jax.make_mesh((1,), ("data",))
    for dedupe in ("routed", "global"):
        got = materialize_pairs_distributed(blk, mesh, ("data",),
                                            chunk_per_shard=2048,
                                            dedupe=dedupe)
        want = pairs.dedupe_pairs(blk, backend="numpy")
        _assert_pairsets_equal(got, want, f"distributed-{dedupe}")


# ---------------------------------------------------------------------------
# fingerprint-routed dedupe: oracle layout + shard-local ops
# (multi-device parity for all three mesh kinds runs in _dist_worker.py —
# the main test process is locked to 1 device)
# ---------------------------------------------------------------------------


def _raw_pairs(blk):
    chunks = [(np.minimum(a, b), np.maximum(a, b), s)
              for a, b, s in pairs.iter_block_pairs(blk)]
    return (np.concatenate([c[0] for c in chunks]),
            np.concatenate([c[1] for c in chunks]),
            np.concatenate([c[2] for c in chunks]))


@pytest.mark.parametrize("n_shards", [1, 3, 8])
def test_routed_oracle_equals_global_dedupe(n_shards):
    """Per-shard dedupe over the fingerprint partition, merged, must equal
    the global dedupe — the identity the routed distributed path rests on."""
    blk = _random_blocks(11, 40, 30, universe=400)
    ra, rb, rs = _raw_pairs(blk)
    oa, ob, os_ = pairs_ref.dedupe_routed_ref(ra, rb, rs, n_shards)
    wa, wb, ws = pairs_ref.dedupe_ref(ra, rb, rs)
    np.testing.assert_array_equal(oa, wa)
    np.testing.assert_array_equal(ob, wb)
    np.testing.assert_array_equal(os_, ws)


def test_pair_route_owner_matches_numpy_mirror():
    rng = np.random.default_rng(3)
    a = rng.integers(0, 1 << 23, 4096).astype(np.int32)
    b = rng.integers(0, 1 << 23, 4096).astype(np.int32)
    valid = rng.random(4096) < 0.9
    # pair_route_owner is jit-free by contract (traces inside shard_map);
    # call it through jit like its callers do
    route = jax.jit(functools.partial(pair_route_owner, n_shards=8))
    got = np.asarray(route(jnp.asarray(a), jnp.asarray(b), jnp.asarray(valid)))
    want = np.where(valid, pairs_ref.np_pair_route_owner(a, b, 8), 8)
    np.testing.assert_array_equal(got, want)
    # owners must be well spread (splitmix64 avalanche)
    counts = np.bincount(got[valid], minlength=8)
    assert counts.min() > 0.5 * counts.mean()


def test_dedupe_packed_device_matches_host():
    rng = np.random.default_rng(7)
    a = rng.integers(0, 500, 2048).astype(np.int32)
    b = (a + rng.integers(1, 100, 2048)).astype(np.int32)
    s = rng.integers(2, 600, 2048).astype(np.int32)
    valid = rng.random(2048) < 0.8
    hi, lo = pack_sort_words(jnp.asarray(a), jnp.asarray(b), jnp.asarray(s),
                             jnp.asarray(valid))
    # dedupe_packed_device is jit-free by contract; jit it like callers do
    shi, slo, winner = jax.jit(dedupe_packed_device)(hi, lo)
    w = np.asarray(winner)
    words = ((np.asarray(shi).astype(np.uint64) << np.uint64(32))
             | np.asarray(slo).astype(np.uint64))[w]
    ga, gb, gs = unpack_words_host(np.sort(words))
    wa, wb, ws = pairs_ref.dedupe_ref(a[valid], b[valid], s[valid])
    np.testing.assert_array_equal(ga, wa)
    np.testing.assert_array_equal(gb, wb)
    np.testing.assert_array_equal(gs, ws)


def test_routed_dedupe_single_device_mesh_all_paths():
    """1-device mesh exercises the full routed machinery (pack, route,
    all_to_all, shard-local dedupe) without subprocess devices."""
    blk = _random_blocks(21, 30, 25, universe=300)
    mesh = jax.make_mesh((1,), ("data",))
    want = pairs.dedupe_pairs(blk, backend="numpy")
    got = dedupe_pairs_distributed(blk, mesh, ("data",), chunk_per_shard=1024)
    _assert_pairsets_equal(got, want, "routed-1dev-exact")
    # budget-exceeded sampling path (global seeded sample)
    budget = blk.num_pair_slots // 4
    want_s = pairs.dedupe_pairs(blk, budget=budget, backend="numpy",
                                sample_seed=3)
    got_s = dedupe_pairs_distributed(blk, mesh, ("data",), budget=budget,
                                     chunk_per_shard=512, sample_seed=3)
    _assert_pairsets_equal(got_s, want_s, "routed-1dev-sampled")
    # backend dispatch through the core driver
    got_d = pairs.dedupe_pairs(blk, backend="distributed", chunk_pairs=1024)
    _assert_pairsets_equal(got_d, want, "backend-distributed")


def test_routed_dedupe_zero_budget_returns_empty_inexact():
    blk = _random_blocks(2, 5, 6, universe=60)
    mesh = jax.make_mesh((1,), ("data",))
    p = dedupe_pairs_distributed(blk, mesh, ("data",), budget=0)
    assert not p.exact and len(p.a) == 0
    assert p.total_slots == blk.num_pair_slots  # counting stays exact


def test_enumerate_pairs_rejects_distributed_backend():
    blk = _random_blocks(2, 5, 6, universe=60)
    with pytest.raises(ValueError, match="no.*distributed backend"):
        next(pairs.enumerate_pairs(blk, backend="distributed"))


def test_routed_dedupe_empty_and_tiny():
    mesh = jax.make_mesh((1,), ("data",))
    z64 = np.zeros((0,), np.int64)
    zu = np.zeros((0,), np.uint32)
    empty = pairs.Blocks(zu, zu, z64, z64, z64)
    p = dedupe_pairs_distributed(empty, mesh, ("data",))
    assert p.exact and len(p.a) == 0 and p.total_slots == 0
    one = pairs.Blocks(np.zeros(1, np.uint32), np.zeros(1, np.uint32),
                       np.zeros(1, np.int64), np.array([2], np.int64),
                       np.array([7, 42], np.int64))
    p1 = dedupe_pairs_distributed(one, mesh, ("data",), chunk_per_shard=256)
    assert p1.exact and list(p1.a) == [7] and list(p1.b) == [42]


def test_routed_dedupe_falls_back_beyond_pack_bound():
    """rids >= 2**PACK_RID_BITS can't take the packed routed path; the
    driver must fall back to the single-device engine, not mis-pack."""
    from repro.kernels.pairs import PACK_RID_BITS
    blk = _random_blocks(9, 12, 10, universe=200)
    big = pairs.Blocks(blk.key_hi, blk.key_lo, blk.start, blk.size,
                       blk.members + (1 << PACK_RID_BITS))
    mesh = jax.make_mesh((1,), ("data",))
    want = pairs.dedupe_pairs(big, backend="numpy")
    with pytest.warns(RuntimeWarning, match="62-bit sort-word pack"):
        got = dedupe_pairs_distributed(big, mesh, ("data",))
    _assert_pairsets_equal(got, want, "routed-pack-fallback")


def test_routed_int32_guard_at_slot_edge(monkeypatch):
    """Per-shard slot offsets near 2**31: the routed driver must refuse
    layouts where base + per_round wraps int32 (the single-device guards
    in core/pairs.py never see per-shard offsets) and fall back."""
    n = MAX_BLOCK_N  # C(65535, 2) = 2_147_418_113, just under 2**31
    blk = pairs.Blocks(np.zeros(1, np.uint32), np.zeros(1, np.uint32),
                       np.zeros(1, np.int64), np.array([n], np.int64),
                       np.arange(n, dtype=np.int64))
    total = blk.num_pair_slots
    assert total + (1 << 18) > 2**31 - 1 > total  # sits exactly at the edge
    z = np.zeros((0,), np.int64)
    sentinel = pairs.PairSet(z, z, z, True, 0)
    monkeypatch.setattr(pairs, "dedupe_pairs", lambda *a, **k: sentinel)
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.warns(RuntimeWarning, match="overflows int32"):
        got = dedupe_pairs_distributed(blk, mesh, ("data",),
                                       budget=2**31 - 2)
    assert got is sentinel  # fell back without decoding 2B slots
    assert "overflows int32" in got.fallback  # and the set says why


def test_routed_decode_validity_at_int32_slot_edge_per_shard_bases():
    """Routed-boundary companion of
    test_decode_chunk_validity_immune_to_int32_wrap: at the largest total
    the routed guard admits (total + n_shards*chunk <= 2**31 - 1), the
    final round's per-shard bases overshoot r0 by shard*chunk — the
    straddling shard must mask its tail and fully-past-the-end shards
    must decode nothing, with no int32 wrap corrupting validity."""
    n_shards, chunk = 8, 1024
    per_round = n_shards * chunk
    total = 2**31 - 1 - per_round  # guard-admitted maximum
    cum = jnp.asarray(np.array([0, total], np.int32))
    start = jnp.asarray(np.zeros(1, np.int32))
    size = jnp.asarray(np.array([3], np.int32))
    members = jnp.asarray(np.array([0, 1, 2], np.int32))
    r0 = (total // per_round) * per_round
    for shard in range(n_shards):
        base = r0 + shard * chunk
        assert base + chunk <= 2**31 - 1  # the invariant the guard enforces
        live = max(0, min(chunk, total - base))
        _, _, _, v = decode_chunk(cum, start, size, members,
                                  jax.device_put(np.int32(base)),
                                  jax.device_put(np.int32(total)),
                                  chunk=chunk)
        v = np.asarray(v)
        assert v.sum() == live and v[:live].all() and not v[live:].any(), shard


def test_enumerate_pairs_streams_all_slots():
    blk = _random_blocks(5, 20, 20, universe=200)
    for be in BACKENDS:
        tot = 0
        for a, b, s in pairs.enumerate_pairs(blk, backend=be,
                                             chunk_pairs=2048):
            assert np.all(a < b)
            tot += len(a)
        assert tot == blk.num_pair_slots, be


def test_oversize_blocks_fall_back_to_numpy():
    # a block larger than MAX_BLOCK_N breaks the int32 contract
    n = MAX_BLOCK_N + 1
    blk = pairs.Blocks(np.zeros(1, np.uint32), np.zeros(1, np.uint32),
                       np.zeros(1, np.int64), np.array([n], np.int64),
                       np.arange(n, dtype=np.int64))
    with pytest.warns(RuntimeWarning, match="MAX_BLOCK_N"):
        p = pairs.dedupe_pairs(blk, budget=1000, backend="jax")
    assert not p.exact and len(p.a) <= 1000


def test_backends_agree_beyond_pack_rid_bound():
    """rids >= 2**PACK_RID_BITS force the general lax.sort dedupe path,
    which must still match the numpy reference exactly."""
    from repro.kernels.pairs import PACK_RID_BITS
    blk = _random_blocks(9, 12, 10, universe=200)
    big = pairs.Blocks(blk.key_hi, blk.key_lo, blk.start, blk.size,
                       blk.members + (1 << PACK_RID_BITS))
    want = pairs.dedupe_pairs(big, backend="numpy")
    got = pairs.dedupe_pairs(big, backend="jax")
    _assert_pairsets_equal(got, want, "big-rid general dedupe")


def test_empty_blocks():
    z64 = np.zeros((0,), np.int64)
    zu = np.zeros((0,), np.uint32)
    blk = pairs.Blocks(zu, zu, z64, z64, z64)
    for be in BACKENDS:
        p = pairs.dedupe_pairs(blk, backend=be)
        assert p.exact and len(p.a) == 0 and p.total_slots == 0
