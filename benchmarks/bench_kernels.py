"""Kernel-layer microbenches (paper §3 hot spots).

Wall times here are the CPU jnp reference paths (the production path on
this container); the Pallas kernels target TPU and are validated in
interpret mode (tests/test_kernels.py) — interpret-mode timings are not
meaningful and are reported only as parity checks.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit

from repro.core import hashing, minhash, sketches


def _time(fn, *args, iters=5):
    fn(*args)  # warm/compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run():
    rng = np.random.default_rng(0)
    # minhash over 32k records x 32 tokens, 24 hashes
    tokens = jnp.asarray(rng.integers(0, 1 << 31, (32768, 32)), jnp.uint32)
    mask = jnp.ones(tokens.shape, bool)
    # once-per-run microbench jits throughout:
    f = jax.jit(lambda t, m: minhash.minhash_tokens(t, m, 24))  # repro: noqa[R005]
    t = _time(f, tokens, mask)
    emit("kernel/minhash_ref_32kx32x24", t * 1e6,
         f"mh_per_s={32768 * 24 / t:.3g}")

    # bulk mix64 over 4M hashes
    vals = jnp.asarray(rng.integers(0, 1 << 62, 1 << 22)
                       .astype(np.uint64).view(np.uint32).reshape(-1, 2))
    f = jax.jit(lambda h, lo: hashing.mix64((h, lo)))  # repro: noqa[R005]
    t = _time(f, vals[:, 0], vals[:, 1])
    emit("kernel/mix64_ref_4M", t * 1e6, f"hashes_per_s={(1 << 22) / t:.3g}")

    # CMS build over 1M keys
    cfg = sketches.CMSConfig(depth=4, width=1 << 18)
    key = (vals[: 1 << 20, 0], vals[: 1 << 20, 1])
    m = jnp.ones(1 << 20, bool)
    f = jax.jit(lambda h, lo, m: sketches.cms_build(cfg, (h, lo), m))  # repro: noqa[R005]
    t = _time(f, key[0], key[1], m)
    emit("kernel/cms_build_ref_1M", t * 1e6, f"keys_per_s={(1 << 20) / t:.3g}")

    # bloom build+query 1M
    bcfg = sketches.BloomConfig.for_capacity(1 << 20, 1e-8)
    # once-per-run microbench jit:
    f = jax.jit(lambda h, lo, m: sketches.bloom_build(bcfg, (h, lo), m))  # repro: noqa[R005]
    t = _time(f, key[0], key[1], m)
    emit("kernel/bloom_build_ref_1M", t * 1e6,
         f"slots={bcfg.num_slots};k={bcfg.num_hashes}")


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    run()
