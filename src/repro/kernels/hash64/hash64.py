"""Pallas TPU kernel for bulk 64-bit key mixing / combining.

The inner loop of IntersectKeys (paper Alg. 2 line 7) combines every pair
of a record's over-sized keys into a new 128-bit hash — here a ~45-op
splitmix64 chain on uint32 limb pairs. Fusing the chain into one VMEM-
resident kernel avoids ~12 HBM round trips for the intermediates that an
op-by-op jnp lowering can incur, turning a memory-bound chain into a
VPU-bound one.

Inputs are 2-D tiles (rows x lanes); ops.py reshapes flat key arrays into
lane-aligned tiles (last dim a multiple of 128).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ... import kernels
from ...core import u64, hashing


def _combine_kernel(ahi_ref, alo_ref, bhi_ref, blo_ref, ohi_ref, olo_ref):
    a = (ahi_ref[...], alo_ref[...])
    b = (bhi_ref[...], blo_ref[...])
    lo_key = u64.minimum(a, b)             # canonical (unordered) combine
    hi_key = u64.where(u64.eq(lo_key, a), b, a)
    hi, lo = hashing.combine(lo_key, hi_key)
    ohi_ref[...] = hi
    olo_ref[...] = lo


def _mix_kernel(ahi_ref, alo_ref, ohi_ref, olo_ref):
    hi, lo = hashing.mix64((ahi_ref[...], alo_ref[...]))
    ohi_ref[...] = hi
    olo_ref[...] = lo


def _launch(kernel, arrays, block_rows: int, block_lanes: int,
            num_out: int):
    r, l = arrays[0].shape
    assert r % block_rows == 0 and l % block_lanes == 0
    grid = (r // block_rows, l // block_lanes)
    spec = pl.BlockSpec((block_rows, block_lanes), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(arrays),
        out_specs=[spec] * num_out,
        out_shape=[jax.ShapeDtypeStruct((r, l), jnp.uint32)] * num_out,
        interpret=kernels.use_interpreter(),
    )(*arrays)


def combine64_pallas(ahi, alo, bhi, blo, *, block_rows=8, block_lanes=512):
    """Order-canonical combine of two u64 key arrays (2-D, tile-aligned)."""
    return _launch(_combine_kernel, [ahi, alo, bhi, blo], block_rows,
                   block_lanes, 2)


def mix64_pallas(ahi, alo, *, block_rows=8, block_lanes=512):
    """Bulk splitmix64 finalizer over a u64 array (2-D, tile-aligned)."""
    return _launch(_mix_kernel, [ahi, alo], block_rows, block_lanes, 2)
