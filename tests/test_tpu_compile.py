"""The main dedup path's Pallas kernels compile for a TPU v5e.

Interpret mode (how every other test runs the kernels) cannot see Mosaic
refusals such as unaligned blocks or missing lowerings, so this file
compiles each kernel at its production width for a v5e that is
described, not attached. All of the v5e work happens inside fixtures and
tests of this one file: the topology is never described at import time,
and only the worker that runs these tests loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest

import repro.kernels
from repro.kernels.match.match import match_score_pallas
from repro.kernels.pairs.pairs import tri_decode_pallas
from repro.kernels.sort.sort import radix_pass_pallas

# the synthetic schema the matcher scores: 5 columns, description <= 24
# tokens; 65536 pairs is the match driver's default chunk
MATCH_COLS, MATCH_TOKENS, MATCH_PAIRS = 5, 24, 65536
WEIGHTS = (0.4, 0.3, 0.1, 0.05, 0.15)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to compile for
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(monkeypatch):
    """Compile, not interpret, and keep the persistent cache out of it
    (entries written for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(repro.kernels, "use_interpreter", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _tri_decode():
    f = lambda t, n: tri_decode_pallas(t, n)
    return f, [((8192, 128), jnp.int32), ((8192, 128), jnp.int32)]


def _radix_pass():
    f = lambda hi, lo: radix_pass_pallas(hi, lo, p=3)
    return f, [((8192, 128), jnp.uint32), ((8192, 128), jnp.uint32)]


def _match_score():
    stack = (MATCH_COLS, MATCH_TOKENS, MATCH_PAIRS)
    f = lambda ta, ma, tb, mb, v: match_score_pallas(
        ta, ma, tb, mb, v, weights=WEIGHTS, threshold=0.65)
    return f, [(stack, jnp.uint32), (stack, jnp.int32), (stack, jnp.uint32),
               (stack, jnp.int32), ((MATCH_PAIRS // 128, 1, 128), jnp.int32)]


@pytest.mark.parametrize("build", [_tri_decode, _radix_pass, _match_score],
                         ids=["tri_decode", "radix_pass", "match_score"])
def test_kernel_compiles_for_v5e(build, one_chip, compile_for_tpu):
    f, shapes = build()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
