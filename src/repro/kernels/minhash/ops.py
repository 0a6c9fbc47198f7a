"""Public jit'd wrapper: pads to tile multiples, dispatches kernel/ref."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .minhash import minhash_pallas
from .ref import minhash_ref


@functools.partial(jax.jit, static_argnames=("num_hashes", "use_kernel",
                                             "block_rows", "block_tokens"))
def minhash(tokens: jnp.ndarray, mask: jnp.ndarray, num_hashes: int,
            use_kernel: bool = True,
            block_rows: int = 256, block_tokens: int = 128) -> jnp.ndarray:
    """MinHash matrix (R, num_hashes) for padded token sets."""
    if not use_kernel:
        return minhash_ref(tokens, mask, num_hashes)
    r, t = tokens.shape
    br = min(block_rows, max(8, r))
    bt = min(block_tokens, max(128, t))
    pad_r = (-r) % br
    pad_t = (-t) % bt
    if pad_r or pad_t:
        tokens = jnp.pad(tokens, ((0, pad_r), (0, pad_t)))
        mask = jnp.pad(mask, ((0, pad_r), (0, pad_t)))
    out = minhash_pallas(tokens, mask, num_hashes, block_rows=br,
                         block_tokens=bt)
    return out[:r]
