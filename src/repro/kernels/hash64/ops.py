"""Public wrappers: flatten/pad to lane-aligned tiles, dispatch kernel/ref."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .hash64 import combine64_pallas, mix64_pallas
from .ref import combine64_ref, mix64_ref

_LANES = 512


_ROWS = 8


def _tile(x: jnp.ndarray):
    """Flatten to (rows, _LANES), rows padded to the row-block multiple."""
    n = x.size
    flat = x.reshape(-1)
    pad = (-n) % (_LANES * _ROWS)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _LANES), n


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def combine64(ahi, alo, bhi, blo, use_kernel: bool = True):
    """Canonical pairwise key combine; shape-preserving over any rank."""
    if not use_kernel:
        return combine64_ref(ahi, alo, bhi, blo)
    shape = ahi.shape
    ta, n = _tile(ahi)
    tb, _ = _tile(alo)
    tc, _ = _tile(bhi)
    td, _ = _tile(blo)
    hi, lo = combine64_pallas(ta, tb, tc, td, block_rows=_ROWS)
    return hi.reshape(-1)[:n].reshape(shape), lo.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def mix64_bulk(ahi, alo, use_kernel: bool = True):
    if not use_kernel:
        return mix64_ref(ahi, alo)
    shape = ahi.shape
    ta, n = _tile(ahi)
    tb, _ = _tile(alo)
    hi, lo = mix64_pallas(ta, tb, block_rows=_ROWS)
    return hi.reshape(-1)[:n].reshape(shape), lo.reshape(-1)[:n].reshape(shape)
