"""Radix-sort dedupe backend: kernel parity + sort_backend threading.

The ``kernels/sort`` LSB radix engine must order u64 sort words (uint32
limb pairs) bit-identically to ``np.sort`` and ``lax.sort`` — a sorted
multiset is unique — on every edge the pair engine can feed it:
sentinel-only buffers, heavy duplicate runs, empty inputs, and
full-capacity field values of the 62-bit pack. The Pallas
histogram/rank kernel (interpret mode here) must match the fused-jnp
mirror bit-for-bit, and the ``sort_backend`` knob must leave every
dedupe result unchanged across comparator/radix on all drivers.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pairs
from repro.core.distributed import dedupe_pairs_distributed
from repro.kernels import sort as ksort
from repro.kernels.pairs import (PACK_RID_BITS, dedupe_device,
                                 dedupe_packed_device, pack_sort_words,
                                 radix_passes_for, unpack_words_host)
from repro.kernels.pairs import ref as pairs_ref

SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


def _limbs(w):
    w = np.asarray(w, np.uint64)
    return (jnp.asarray((w >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((w & np.uint64(0xFFFFFFFF)).astype(np.uint32)))


def _join(hi, lo):
    return ((np.asarray(hi).astype(np.uint64) << np.uint64(32))
            | np.asarray(lo).astype(np.uint64))


def _radix(w, use_kernel=False, n_passes=ksort.MAX_PASSES):
    hi, lo = _limbs(w)
    shi, slo = ksort.radix_sort_words(hi, lo, n_passes=n_passes,
                                      use_kernel=use_kernel)
    return _join(shi, slo)


# ---------------------------------------------------------------------------
# sort parity on edge inputs (satellite: sentinel-only / duplicates /
# empty / full-capacity limb pairs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel", [False, True])
def test_radix_matches_npsort_random(use_kernel):
    rng = np.random.default_rng(0)
    w = rng.integers(0, 1 << 62, 2048, dtype=np.uint64)
    w[rng.random(2048) < 0.1] = SENTINEL
    np.testing.assert_array_equal(_radix(w, use_kernel), np.sort(w))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_radix_sentinel_only(use_kernel):
    w = np.full(1000, SENTINEL, np.uint64)
    np.testing.assert_array_equal(_radix(w, use_kernel), w)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_radix_duplicate_words(use_kernel):
    rng = np.random.default_rng(1)
    base = rng.integers(0, 1 << 62, 7, dtype=np.uint64)
    w = rng.choice(base, 2048).astype(np.uint64)
    np.testing.assert_array_equal(_radix(w, use_kernel), np.sort(w))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_radix_empty(use_kernel):
    w = np.zeros((0,), np.uint64)
    assert len(_radix(w, use_kernel)) == 0


@pytest.mark.parametrize("use_kernel", [False, True])
def test_radix_full_capacity_limb_pairs(use_kernel):
    """Max field values of the 62-bit pack: a = b = 2**23 - 1 and the
    extreme size codes, mixed with sentinels — every digit boundary of
    the limb pair is exercised, at an exact tile multiple (no padding)
    and off-multiple (padding lanes)."""
    rid_max = (1 << PACK_RID_BITS) - 1
    a = np.asarray([rid_max, rid_max, 0, 0, rid_max - 1], np.int32)
    b = np.asarray([rid_max, rid_max, 1, rid_max, rid_max], np.int32)
    s = np.asarray([2, 65535, 65535, 2, 3], np.int32)
    hi, lo = pack_sort_words(jnp.asarray(a), jnp.asarray(b), jnp.asarray(s),
                             jnp.asarray(np.ones(5, bool)))
    base = _join(hi, lo)
    rng = np.random.default_rng(2)
    for n in (1024, 1000):  # tile-exact and padded
        w = rng.choice(np.concatenate([base, [SENTINEL]]), n).astype(np.uint64)
        np.testing.assert_array_equal(_radix(w, use_kernel), np.sort(w))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_radix_truncated_passes_keep_sentinels_last(use_kernel):
    """With n_passes bounding only the valid words' significant bits the
    all-ones sentinel must still sort strictly last (its untouched high
    digits are ignored; its low 16 bits beat any valid size code)."""
    rng = np.random.default_rng(3)
    w = rng.integers(0, 1 << 40, 2048, dtype=np.uint64)
    w[:17] = SENTINEL
    np.testing.assert_array_equal(_radix(w, use_kernel, n_passes=10),
                                  np.sort(w))


def test_radix_matches_laxsort():
    rng = np.random.default_rng(4)
    w = rng.integers(0, 1 << 62, 2048, dtype=np.uint64)
    hi, lo = _limbs(w)
    chi, clo = jax.lax.sort((hi, lo), num_keys=2)
    np.testing.assert_array_equal(_radix(w), _join(chi, clo))


def test_numpy_oracle_matches_npsort():
    rng = np.random.default_rng(5)
    w = rng.integers(0, 1 << 62, 3000, dtype=np.uint64)
    w[:5] = SENTINEL
    np.testing.assert_array_equal(ksort.np_radix_sort_words(w), np.sort(w))


@pytest.mark.parametrize("n", [128 * 8, 129])
def test_pallas_kernel_bit_identical_to_jnp_mirror(n):
    rng = np.random.default_rng(6)
    w = rng.integers(0, 1 << 62, n, dtype=np.uint64)
    w[rng.random(n) < 0.05] = SENTINEL
    np.testing.assert_array_equal(_radix(w, use_kernel=True),
                                  _radix(w, use_kernel=False))


def test_radix_pass_histogram_and_rank():
    """One kernel pass: the per-tile histogram must count every digit and
    the in-tile ranks must be a stable enumeration of each digit class."""
    rng = np.random.default_rng(7)
    n = 2048  # two tiles
    w = rng.integers(0, 1 << 62, n, dtype=np.uint64)
    hi = (w >> np.uint64(32)).astype(np.uint32).reshape(-1, 128)
    lo = (w & np.uint64(0xFFFFFFFF)).astype(np.uint32).reshape(-1, 128)
    rank, hist = ksort.radix_pass_pallas(jnp.asarray(hi), jnp.asarray(lo),
                                         p=3)
    rank = np.asarray(rank).reshape(-1)
    hist = np.asarray(hist)[:, 0, :ksort.RADIX]
    d = ((w >> np.uint64(3 * ksort.RADIX_BITS))
         & np.uint64(ksort.RADIX - 1)).astype(np.int64)
    tile = np.arange(n) // 1024
    for t in range(2):
        np.testing.assert_array_equal(
            hist[t], np.bincount(d[tile == t], minlength=ksort.RADIX))
        for k in range(ksort.RADIX):
            sel = (tile == t) & (d == k)
            np.testing.assert_array_equal(np.sort(rank[sel]),
                                          np.arange(sel.sum()))


# ---------------------------------------------------------------------------
# sort_backend threading through the dedupe stack
# ---------------------------------------------------------------------------


def _random_blocks(seed, n_blocks, max_size, universe):
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


def test_dedupe_packed_device_radix_matches_comparator():
    rng = np.random.default_rng(8)
    a = rng.integers(0, 500, 2048).astype(np.int32)
    b = (a + rng.integers(1, 100, 2048)).astype(np.int32)
    s = rng.integers(2, 600, 2048).astype(np.int32)
    valid = rng.random(2048) < 0.8
    hi, lo = pack_sort_words(jnp.asarray(a), jnp.asarray(b), jnp.asarray(s),
                             jnp.asarray(valid))
    outs = {}
    for sb in ("comparator", "radix"):
        # dedupe_packed_device is jit-free by contract ("for use INSIDE
        # shard_map"); call it through jit, as its real callers do
        fn = jax.jit(functools.partial(
            dedupe_packed_device, sort_backend=sb,
            n_passes=radix_passes_for(600)))
        shi, slo, win = fn(hi, lo)
        outs[sb] = _join(shi, slo)[np.asarray(win)]
    np.testing.assert_array_equal(outs["radix"], outs["comparator"])
    ga, gb, gs = unpack_words_host(np.sort(outs["radix"]))
    wa, wb, ws = pairs_ref.dedupe_ref(a[valid], b[valid], s[valid])
    np.testing.assert_array_equal(ga, wa)
    np.testing.assert_array_equal(gb, wb)
    np.testing.assert_array_equal(gs, ws)


def test_dedupe_device_radix_matches_comparator():
    rng = np.random.default_rng(9)
    a = rng.integers(0, 1000, 4096).astype(np.int32)
    b = (a + rng.integers(1, 50, 4096)).astype(np.int32)
    s = rng.integers(2, 65535, 4096).astype(np.int32)
    valid = rng.random(4096) < 0.9
    args = (jnp.asarray(a), jnp.asarray(b), jnp.asarray(s), jnp.asarray(valid))
    ca, cb, cs, cw = dedupe_device(*args, sort_backend="comparator")
    ra, rb, rs, rw = dedupe_device(*args, sort_backend="radix",
                                   n_passes=radix_passes_for(1050))
    cw, rw = np.asarray(cw), np.asarray(rw)
    np.testing.assert_array_equal(np.asarray(ra)[rw], np.asarray(ca)[cw])
    np.testing.assert_array_equal(np.asarray(rb)[rw], np.asarray(cb)[cw])
    np.testing.assert_array_equal(np.asarray(rs)[rw], np.asarray(cs)[cw])


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("sort_backend", ["comparator", "radix"])
def test_dedupe_pairs_sort_backends_bit_identical(backend, sort_backend):
    blk = _random_blocks(10, 40, 30, universe=400)
    want = pairs.dedupe_pairs(blk, backend="numpy")
    got = pairs.dedupe_pairs(blk, backend=backend, sort_backend=sort_backend)
    _assert_pairsets_equal(got, want, f"{backend}/{sort_backend}")
    # budget-exceeded sampled path shares the seeded global sample
    budget = blk.num_pair_slots // 3
    want_s = pairs.dedupe_pairs(blk, budget=budget, backend="numpy",
                                sample_seed=11)
    got_s = pairs.dedupe_pairs(blk, budget=budget, backend=backend,
                               sample_seed=11, sort_backend=sort_backend)
    _assert_pairsets_equal(got_s, want_s, f"sampled {backend}/{sort_backend}")


@pytest.mark.parametrize("sort_backend", ["auto", "comparator", "radix"])
def test_routed_dedupe_sort_backends_one_device_mesh(sort_backend):
    """The routed distributed dedupe must be sort_backend-invariant (the
    emulated 8-host parity runs in the slow-lane _dist_worker)."""
    blk = _random_blocks(12, 30, 25, universe=300)
    mesh = jax.make_mesh((1,), ("data",))
    want = pairs.dedupe_pairs(blk, backend="numpy")
    got = dedupe_pairs_distributed(blk, mesh, ("data",), chunk_per_shard=1024,
                                   sort_backend=sort_backend)
    _assert_pairsets_equal(got, want, f"routed/{sort_backend}")


def test_radix_beyond_pack_bound_degrades_with_warning():
    blk = _random_blocks(13, 12, 10, universe=200)
    big = pairs.Blocks(blk.key_hi, blk.key_lo, blk.start, blk.size,
                       blk.members + (1 << PACK_RID_BITS))
    want = pairs.dedupe_pairs(big, backend="numpy")
    with pytest.warns(RuntimeWarning, match="62-bit sort"):
        got = pairs.dedupe_pairs(big, backend="jax", sort_backend="radix")
    _assert_pairsets_equal(got, want, "radix-degrade")


def test_invalid_sort_backend_rejected():
    blk = _random_blocks(14, 3, 5, universe=40)
    with pytest.raises(ValueError, match="sort_backend"):
        pairs.dedupe_pairs(blk, backend="jax", sort_backend="bogus")
    # eager validation: the numpy shortcut (sub-crossover workloads with
    # backend="auto") must reject the typo too, not silently ignore it
    assert blk.num_pair_slots < pairs._AUTO_NUMPY_CROSSOVER
    with pytest.raises(ValueError, match="sort_backend"):
        pairs.dedupe_pairs(blk, backend="auto", sort_backend="bogus")


def test_radix_passes_for_bounds():
    # 16 size bits + 23 b bits + bitlength(max a) digits, clamped
    assert radix_passes_for(0) == -(-(16 + 23 + 1) // ksort.RADIX_BITS)
    assert radix_passes_for((1 << PACK_RID_BITS) - 1) == ksort.MAX_PASSES
    assert radix_passes_for(1 << 40) == ksort.MAX_PASSES  # clamped
