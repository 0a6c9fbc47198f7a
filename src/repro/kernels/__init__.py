"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel package ships three modules:
  <name>.py  -- pl.pallas_call + BlockSpec VMEM tiling (TPU target)
  ops.py     -- jit'd public wrapper (padding, dispatch)
  ref.py     -- pure-jnp oracle used by the parity tests

Whether a ``pallas_call`` runs compiled or in the Pallas interpreter is
decided by the platform alone (``use_interpreter``): the interpreter on
the CPU backend, where the test suite runs (``JAX_PLATFORMS=cpu``), the
Mosaic compiler everywhere else. Interpreted runs are parity checks
against the oracles, never speed measurements. ``tests/test_tpu_compile.py``
compiles the main-path kernels for a described v5e without a chip, and
``chip_smoke.py`` runs them on one.
"""
import jax


def use_interpreter() -> bool:
    """True iff Pallas kernels must run in interpret mode: on the CPU
    backend only, so no TPU call can fall into the interpreter."""
    return jax.default_backend() == "cpu"
