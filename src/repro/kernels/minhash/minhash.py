"""Pallas TPU MinHash kernel.

MinHash over every record's token set is the FLOP hot spot of LSH block
building (paper §2.1): R records x T tokens x M hash functions of ~40
integer ops each. A naive jnp implementation materializes an (R, T)
intermediate per hash function in HBM — M round trips. This kernel tiles
(rows x tokens) into VMEM and keeps the (BR, M) running minimum in the
output block across the token-tile grid axis, so each token is read from
HBM exactly once and all M hashes happen in-register.

Grid: (R/BR, T/BT); token axis is the minor (sequential) axis, so the
output block revision pattern is the standard Pallas accumulation idiom.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ... import kernels
from ...core import u64
from ...core.minhash import _MH_SEED

_GAMMA = 0x9E3779B97F4A7C15
_M1 = 0xBF58476D1CE4E5B9
_M2 = 0x94D049BB133111EB
_MASK64 = (1 << 64) - 1


def _mix64_lo(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer, returning the low 32 bits (VPU-only int ops)."""
    x = (hi, lo)
    x = u64.xor(x, u64.shr(x, 30))
    x = u64.mul_const(x, _M1)
    x = u64.xor(x, u64.shr(x, 27))
    x = u64.mul_const(x, _M2)
    x = u64.xor(x, u64.shr(x, 31))
    return x[1]


def _minhash_kernel(tokens_ref, mask_ref, addhi_ref, addlo_ref, out_ref, *,
                    num_hashes: int):
    tok = tokens_ref[...]            # (BR, BT) uint32
    msk = mask_ref[...]              # (BR, BT) bool

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, 0xFFFFFFFF)

    acc = out_ref[...]               # (BR, M) running minima
    for i in range(num_hashes):      # static unroll: all hashes in-register
        a_hi = addhi_ref[0, i]
        a_lo = addlo_ref[0, i]
        lo = tok + a_lo
        carry = (lo < tok).astype(jnp.uint32)
        hi = jnp.broadcast_to(a_hi, tok.shape) + carry
        h = _mix64_lo(hi, lo)        # (BR, BT)
        h = jnp.where(msk, h, np.uint32(0xFFFFFFFF))
        acc = acc.at[:, i].min(jnp.min(h, axis=1))
    out_ref[...] = acc


def minhash_pallas(tokens: jnp.ndarray, mask: jnp.ndarray, num_hashes: int,
                   seed: int = _MH_SEED, *, block_rows: int = 256,
                   block_tokens: int = 128) -> jnp.ndarray:
    """(R, T) uint32 tokens + mask -> (R, M) uint32 MinHashes.

    R must divide block_rows, T must divide block_tokens (ops.py pads).
    """
    r, t = tokens.shape
    assert r % block_rows == 0 and t % block_tokens == 0, (r, t)
    consts = [((seed + 977 * i + 1) * _GAMMA) & _MASK64 for i in range(num_hashes)]
    add_hi = jnp.asarray([[c >> 32 for c in consts]], jnp.uint32)
    add_lo = jnp.asarray([[c & 0xFFFFFFFF for c in consts]], jnp.uint32)
    grid = (r // block_rows, t // block_tokens)
    return pl.pallas_call(
        functools.partial(_minhash_kernel, num_hashes=num_hashes),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_tokens), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, block_tokens), lambda i, j: (i, j)),
            pl.BlockSpec((1, num_hashes), lambda i, j: (0, 0)),
            pl.BlockSpec((1, num_hashes), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, num_hashes), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, num_hashes), jnp.uint32),
        interpret=kernels.use_interpreter(),
    )(tokens.astype(jnp.uint32), mask, add_hi, add_lo)
