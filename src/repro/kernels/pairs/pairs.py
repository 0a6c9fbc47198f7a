"""Pallas TPU kernel for triangular pair-slot decoding.

Pair materialization (paper §3.1) turns each CSR block of size ``n`` into
its C(n, 2) strictly-upper-triangular pairs. Once the driver has mapped a
flat chunk of pair slots to (block-local slot ``t``, block size ``n``) —
one cheap vectorized searchsorted — the hot loop is the *triangular
decode* ``t -> (i, j)``: an exact integer binary search for the largest
row ``i`` with ``cum(i) = i*(n-1) - i*(i-1)/2 <= t``.

That search is ~17 rounds of pure VPU integer arithmetic per slot with no
gathers and no cross-lane traffic, so the kernel reads each (t, n) lane
from HBM exactly once, runs the whole search in-register, and writes
(i, j) once — the member gathers that follow are memory-bound and stay in
XLA. Row products are computed in uint32: ``i*(n-1) <= 65533*65534 <
2**32``, which is why the engine caps block sizes at ``MAX_BLOCK_N``
(enforced by the host driver; HDB's max_block_size=500 default is three
orders of magnitude below it).

Grid: (rows / block_rows,) over a (rows, 128) lane layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ... import kernels

# Largest block size whose row products fit uint32 (see module docstring).
MAX_BLOCK_N = 65535
# ceil(log2(MAX_BLOCK_N - 1)) = 16 candidate-row halvings always suffice;
# callers pass fewer steps when the layout's max block size is known.
MAX_SEARCH_STEPS = 16


def search_steps_for(max_block: int) -> int:
    """Binary-search depth covering row range [0, max_block - 2]."""
    span = max(2, max_block - 1)
    return min(MAX_SEARCH_STEPS, max(1, (span - 1).bit_length()))


def _tri_decode_kernel(local_ref, n_ref, i_ref, j_ref, *, steps: int):
    t = local_ref[...].astype(jnp.uint32)   # (BR, 128) local slot index
    n = n_ref[...].astype(jnp.uint32)       # (BR, 128) block size
    nm1 = n - 1
    lo = jnp.zeros_like(t)
    hi = jnp.where(n >= 2, n - 2, 0)
    for _ in range(steps):                  # static unroll, all in-register
        mid = (lo + hi + 1) // 2
        cum = mid * nm1 - (mid * (mid - 1)) // 2
        go_right = cum <= t
        lo = jnp.where(go_right, mid, lo)
        hi = jnp.where(go_right, hi, mid - 1)
    i = lo
    cum_i = i * nm1 - (i * (i - 1)) // 2
    j = t - cum_i + i + 1
    i_ref[...] = i.astype(jnp.int32)
    j_ref[...] = j.astype(jnp.int32)


def tri_decode_pallas(local: jnp.ndarray, n: jnp.ndarray, *,
                      steps: int = MAX_SEARCH_STEPS, block_rows: int = 8):
    """(R, 128) int32 local slot + block size -> (i, j) int32, i < j.

    R must divide block_rows (ops.py pads). ``steps`` must cover the
    largest block present (``search_steps_for``). Lanes with ``n < 2``
    produce garbage and must be masked by the caller. Interpreted iff
    ``kernels.use_interpreter()``.
    """
    rows, lanes = local.shape
    assert lanes == 128 and rows % block_rows == 0, (rows, lanes)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, 128), lambda r: (r, 0))
    out = jax.ShapeDtypeStruct((rows, 128), jnp.int32)
    return pl.pallas_call(
        functools.partial(_tri_decode_kernel, steps=steps),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(out, out),
        interpret=kernels.use_interpreter(),
    )(local.astype(jnp.int32), n.astype(jnp.int32))
