"""Distributed HDB == single-device HDB, on 8 emulated host devices.

Runs in a subprocess because device count is locked at first jax init
(the main test process must keep seeing exactly 1 device).
"""
import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "_dist_worker.py")
SHARD_WORKER = os.path.join(os.path.dirname(__file__), "_shard_worker.py")


def _run(mesh_kind, worker=WORKER):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the 8 devices are emulated CPU hosts: the child must never take a chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, worker, mesh_kind],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert f"OK {mesh_kind}" in proc.stdout


@pytest.mark.slow
def test_distributed_matches_reference_flat_mesh():
    _run("flat")


@pytest.mark.slow
def test_distributed_matches_reference_pod_mesh():
    _run("pod")


@pytest.mark.slow
def test_distributed_matches_reference_3axis_mesh():
    _run("3axis")


# ---------------------------------------------------------------------------
# sharded streaming store on the same emulated meshes (tests/_shard_worker.py):
# mesh-routed key-table exchange + distributed ledger sync must be
# bit-identical to the single-host DeltaBlocker and to batch HDB
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_store_matches_reference_flat_mesh():
    _run("flat", worker=SHARD_WORKER)


@pytest.mark.slow
def test_sharded_store_matches_reference_pod_mesh():
    _run("pod", worker=SHARD_WORKER)


@pytest.mark.slow
def test_sharded_store_matches_reference_3axis_mesh():
    _run("3axis", worker=SHARD_WORKER)


@pytest.mark.slow
def test_sharded_store_overflow_fallback_is_loud_and_lossless():
    _run("overflow", worker=SHARD_WORKER)
