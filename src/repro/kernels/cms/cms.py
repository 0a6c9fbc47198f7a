"""Pallas TPU kernel for Count-Min Sketch updates.

The CMS build (paper Alg. 3 line 2) is a depth-way scatter-add over the
sketch rows. TPUs serialize true scatters, so the kernel instead emulates
the scatter with a compare-against-iota histogram: for each width tile
``[w0, w0+BW)`` the per-key one-hot condition ``bucket_index == iota``
reduces over the key tile into the (depth, BW) histogram slab held in
VMEM. This trades scatter serialization for dense VPU compares — the
classic TPU histogram adaptation (DESIGN.md §3; an MXU one-hot-matmul
variant is possible when counts fit bf16's 8-bit mantissa per tile).

Grid: (width_tiles, key_tiles); key axis minor => output accumulation is
the standard revision idiom.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ... import kernels


def _cms_kernel(idx_ref, mask_ref, out_ref, *, depth: int, block_width: int):
    # idx_ref: (depth, BK) int32 bucket indices; mask_ref: (1, BK) bool
    # out_ref: (depth, BW) int32 histogram slab for width tile program_id(0)
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w0 = pl.program_id(0) * block_width
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_width, 1), 0) + w0
    msk = mask_ref[...]  # (1, BK)
    acc = out_ref[...]
    for d in range(depth):  # static, small
        idx = idx_ref[d, :][None, :]               # (1, BK)
        onehot = (iota == idx) & msk               # (BW, BK)
        acc = acc.at[d, :].add(jnp.sum(onehot.astype(jnp.int32), axis=1))
    out_ref[...] = acc


def cms_update_pallas(indices: jnp.ndarray, mask: jnp.ndarray, width: int, *,
                      block_keys: int = 1024, block_width: int = 2048
                      ) -> jnp.ndarray:
    """(depth, N) bucket indices -> (depth, width) int32 sketch."""
    depth, n = indices.shape
    assert n % block_keys == 0 and width % block_width == 0
    grid = (width // block_width, n // block_keys)
    return pl.pallas_call(
        functools.partial(_cms_kernel, depth=depth, block_width=block_width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((depth, block_keys), lambda w, k: (0, k)),
            pl.BlockSpec((1, block_keys), lambda w, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((depth, block_width), lambda w, k: (0, w)),
        out_shape=jax.ShapeDtypeStruct((depth, width), jnp.int32),
        interpret=kernels.use_interpreter(),
    )(indices, mask)
