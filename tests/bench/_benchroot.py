"""Helpers for the benchmark's CPU tests: a copy of the benchmark's files
at tiny sizes in a temporary checkout root, and one run of a cell there."""
from __future__ import annotations

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# at 4,096 records the state and common-name blocks are still over-sized,
# so HDB intersects and probes walk below the first level
TINY = {"records": 4096, "pool": 256, "rate_per_s": 150.0,
        "trace_seconds": 0.5}


def edit_json(path: str, **changes) -> dict:
    with open(path) as f:
        data = json.load(f)
    for key, value in changes.items():
        node = data
        *parents, leaf = key.split(".")
        for p in parents:
            node = node[p]
        node[leaf] = value
    with open(path, "w") as f:
        json.dump(data, f)
    return data


def tiny_root(tmp_path) -> str:
    """A checkout root holding ``BENCHMARK.json`` and ``bench/`` with every
    configuration and traffic mix cut to test size."""
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    edit_json(os.path.join(root, "bench", "configs", "registry.json"),
              records=TINY["records"])
    edit_json(os.path.join(root, "bench", "traffic", "poisson_probes.json"),
              pool=TINY["pool"], rate_per_s=TINY["rate_per_s"],
              trace_seconds=TINY["trace_seconds"])
    return root


def run_cell(root: str, workload: str, monkeypatch, seed: int = 4294967311,
             seconds: float = 1.0, trace: int = 0):
    """One CPU run of a cell: ``(exit code, last stdout line as a dict or
    None, stderr, stdout)``."""
    from bench import run

    # run.main points the compilation cache into the checkout; keep that
    # setting out of the rest of the test run
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(root, ".jax_cache"))
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, require_accelerator=False)
    lines = out.getvalue().strip().splitlines()
    last = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, last, err.getvalue(), out.getvalue()
