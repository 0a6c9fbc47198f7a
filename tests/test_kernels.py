"""Pallas kernel parity tests: interpret-mode kernel vs pure-jnp oracle,
swept across shapes/dtypes as required for every kernel."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.minhash import minhash
from repro.kernels.minhash.ref import minhash_ref
from repro.kernels.hash64 import combine64, mix64_bulk
from repro.kernels.hash64.ref import combine64_ref
from repro.kernels.cms import cms_update
from repro.kernels.cms.ref import cms_update_ref
from repro.core import sketches, hashing


# ---------------------------------------------------------------------------
# minhash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,t,m", [
    (8, 16, 8),       # tiny, heavy padding
    (64, 128, 24),    # exact tile fit
    (100, 70, 16),    # ragged both axes
    (257, 129, 32),   # off-by-one over tiles
])
def test_minhash_kernel_matches_ref(r, t, m):
    rng = np.random.default_rng(r * 1000 + t)
    tokens = jnp.asarray(rng.integers(0, 1 << 32, (r, t), dtype=np.uint64)
                         .astype(np.uint32))
    mask = jnp.asarray(rng.random((r, t)) < 0.8)
    got = minhash(tokens, mask, m, use_kernel=True)
    want = minhash_ref(tokens, mask, m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mask_kind", ["all", "none", "empty_rows"])
def test_minhash_kernel_mask_edge_cases(mask_kind):
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 1 << 31, (32, 16), dtype=np.int64)
                         .astype(np.uint32))
    if mask_kind == "all":
        mask = jnp.ones((32, 16), bool)
    elif mask_kind == "none":
        mask = jnp.zeros((32, 16), bool)
    else:
        mask = jnp.asarray(np.repeat([[True], [False]], [16, 16], axis=0)
                           .reshape(32, 1) * np.ones((1, 16), bool))
    got = minhash(tokens, mask, 8, use_kernel=True)
    want = minhash_ref(tokens, mask, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# hash64
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(16,), (1000,), (64, 80), (3, 5, 7)])
def test_combine64_kernel_matches_ref(shape):
    rng = np.random.default_rng(int(np.prod(shape)))
    mk = lambda: jnp.asarray(rng.integers(0, 1 << 32, shape, dtype=np.uint64)
                             .astype(np.uint32))
    ahi, alo, bhi, blo = mk(), mk(), mk(), mk()
    ghi, glo = combine64(ahi, alo, bhi, blo, use_kernel=True)
    whi, wlo = combine64_ref(ahi, alo, bhi, blo)
    np.testing.assert_array_equal(np.asarray(ghi), np.asarray(whi))
    np.testing.assert_array_equal(np.asarray(glo), np.asarray(wlo))


def test_combine64_is_symmetric_under_swap():
    """Canonical ordering => combine(a,b) == combine(b,a)."""
    rng = np.random.default_rng(5)
    mk = lambda: jnp.asarray(rng.integers(0, 1 << 32, (512,), dtype=np.uint64)
                             .astype(np.uint32))
    ahi, alo, bhi, blo = mk(), mk(), mk(), mk()
    h1 = combine64(ahi, alo, bhi, blo, use_kernel=True)
    h2 = combine64(bhi, blo, ahi, alo, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(h1[0]), np.asarray(h2[0]))
    np.testing.assert_array_equal(np.asarray(h1[1]), np.asarray(h2[1]))


@pytest.mark.parametrize("n", [1, 512, 5000])
def test_mix64_bulk_matches_ref_and_python(n):
    rng = np.random.default_rng(n)
    vals = rng.integers(0, (1 << 64) - 1, n, dtype=np.uint64)
    packed = jnp.asarray(hashing.np_to_u64_arrays(vals))
    ghi, glo = mix64_bulk(packed[..., 0], packed[..., 1], use_kernel=True)
    got = (np.asarray(ghi).astype(np.uint64) << np.uint64(32)) | np.asarray(glo)
    want = np.asarray([hashing.np_mix64(int(v)) for v in vals], np.uint64)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# cms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth,n,width", [
    (1, 256, 2048),
    (4, 1024, 4096),
    (4, 3000, 2048),   # ragged key axis
    (6, 128, 8192),    # wider than block_width
])
def test_cms_kernel_matches_ref(depth, n, width):
    rng = np.random.default_rng(depth * n)
    idx = jnp.asarray(rng.integers(0, width, (depth, n)), jnp.int32)
    mask = jnp.asarray(rng.random(n) < 0.7)
    got = cms_update(idx, mask, width, use_kernel=True,
                     block_keys=256, block_width=1024)
    want = cms_update_ref(idx, mask, width)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cms_kernel_plugs_into_sketch_queries():
    """Kernel-built sketch must answer queries identically to cms_build."""
    cfg = sketches.CMSConfig(depth=4, width=1 << 12)
    rng = np.random.default_rng(9)
    vals = rng.integers(0, 500, 4096, dtype=np.uint64)
    packed = jnp.asarray(hashing.np_to_u64_arrays(vals))
    key = (packed[..., 0], packed[..., 1])
    mask = jnp.ones(len(vals), bool)
    idx = sketches.cms_indices(cfg, key)
    sk_kernel = cms_update(idx, mask, cfg.width, use_kernel=True,
                           block_keys=512, block_width=1024)
    sk_ref = sketches.cms_build(cfg, key, mask)
    np.testing.assert_array_equal(np.asarray(sk_kernel), np.asarray(sk_ref))
