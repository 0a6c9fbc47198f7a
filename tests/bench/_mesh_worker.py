"""Runs one benchmark cell on four emulated CPU devices and prints what
``bench/run.py`` prints: for the CPU tests of a cell that needs four
chips. The device count is fixed when JAX starts, so each run is a
process of its own.

    python tests/bench/_mesh_worker.py <root> <cell> <seed> <seconds> <trace>
"""
import os
import sys

assert "--xla_force_host_platform_device_count=4" in os.environ.get(
    "XLA_FLAGS", "")

root, cell, seed, seconds, trace = sys.argv[1:6]
sys.path.insert(0, root)

from bench import run  # noqa: E402

sys.exit(run.main(["--workload", cell, "--seed", seed, "--seconds", seconds,
                   "--trace", trace], root=root, require_accelerator=False))
