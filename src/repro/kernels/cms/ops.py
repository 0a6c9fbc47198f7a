"""Public wrapper: pads the key axis, dispatches kernel/ref."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .cms import cms_update_pallas
from .ref import cms_update_ref


@functools.partial(jax.jit, static_argnames=("width", "use_kernel",
                                             "block_keys", "block_width"))
def cms_update(indices: jnp.ndarray, mask: jnp.ndarray, width: int,
               use_kernel: bool = True,
               block_keys: int = 1024, block_width: int = 2048) -> jnp.ndarray:
    """Build a (depth, width) CMS from (depth, N) bucket indices + (N,) mask."""
    if not use_kernel:
        return cms_update_ref(indices, mask, width)
    depth, n = indices.shape
    bk = min(block_keys, max(128, n))
    bw = min(block_width, width)
    pad = (-n) % bk
    if pad:
        indices = jnp.pad(indices, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, (0, pad))
    return cms_update_pallas(indices, mask.reshape(1, -1), width,
                             block_keys=bk, block_width=bw)
