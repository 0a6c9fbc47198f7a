"""The sharded batch job: ``dedup_corpus`` on columns placed over four
emulated host devices (tests/_sharded_job_worker.py) answers as the plain
reference and the one-device job do, counts a routing overflow's
fallback, and writes its spans and counters.

Each case runs in a subprocess because the device count is fixed when
JAX starts (this process must keep seeing one device)."""
import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "_sharded_job_worker.py")


@pytest.mark.parametrize("case", ["answer", "overflow", "spans"])
def test_sharded_job(case):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, WORKER, case], capture_output=True,
                          text=True, timeout=600, env=env)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert f"OK {case}" in proc.stdout


def test_columns_on_one_device_take_the_one_device_job():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.blocks import TokenColumn
    from repro.data import pipeline

    tok = jnp.zeros((8, 2), jnp.uint32)
    assert pipeline.record_mesh({"f": TokenColumn(tok, tok > 0)}) is None
    # a one-device mesh is still one device
    one = NamedSharding(Mesh(jax.devices()[:1], ("data",)), P("data", None))
    placed = jax.device_put(tok, one)
    assert pipeline.record_mesh({"f": TokenColumn(placed, placed > 0)}) is None
