"""Process set-up for entry points: scripts, examples and benchmarks.

Nothing here runs on import. The library and the tests never call it;
an entry point calls ``enable_compilation_cache()`` once, before its
first compile.
"""
from __future__ import annotations

import os

# the checkout's root: src/repro/runtime.py -> ../..
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured by JAX itself and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``
    (git-ignored): a fixed path, because the directory is part of what
    later runs look up, so a per-run or temporary path would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
