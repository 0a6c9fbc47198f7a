"""The four-chip batch cell (``registry-batch-4chip``, mode ``batch_mesh``)
on four emulated CPU devices, its refusal of a program with no sharded
job, and its per-layer readers on recorded and hand-made traces."""
import gzip
import json
import os
import subprocess
import sys
import types

import pytest

from _benchroot import REPO, TINY, edit_json, run_cell, tiny_root

from bench.harness import batch_mesh

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "registry-batch-4chip"
MS = 1_000_000
READERS = ("dist_hdb_device_ms", "dist_pairs_device_ms", "collective_ms",
           "a2a_ici_share", "route_skew", "device_idle_share.batch4")


def _mesh_root(tmp_path) -> str:
    root = tiny_root(tmp_path)
    edit_json(os.path.join(root, "bench", "configs", "registry_4chip.json"),
              records=TINY["records"])
    return root


def _mesh_run(root: str, trace: int, seed: int = 4294967311):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"),
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_mesh_worker.py"), root, CELL,
         str(seed), "1", str(trace)],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_registry_batch_4chip_line(tmp_path):
    root = _mesh_root(tmp_path)
    line, err = _mesh_run(root, trace=0)
    assert line["correct"] is True and line["attempted"] >= 1
    assert set(line["metrics"]) == {"records_per_s", "setup_s"}
    assert line["device"]["count"] == 4
    assert set(line["checks"]) == {"blocks_diff", "pairs_diff",
                                   "matched_diff", "labels_diff",
                                   "survivors_diff", "pair_fallbacks",
                                   "route_overflows", "unsharded_jobs"}
    assert all(c == {"value": 0, "limit": 0} for c in line["checks"].values())

    traced, err = _mesh_run(root, trace=1)
    assert traced["correct"] is True and traced["attempted"] == 1
    # a CPU trace has no device plane: only the program's own numbers read
    assert set(traced["metrics"]) == {"blocking_ms", "back_half_ms",
                                      "route_skew"}
    assert traced["metrics"]["route_skew"]["value"] >= 1.0


def test_program_without_shards_is_refused(tmp_path, monkeypatch):
    import dataclasses

    from repro.data import pipeline

    root = _mesh_root(tmp_path)
    fields = [(f.name, f.type, f) for f in dataclasses.fields(
        pipeline.DedupReport) if f.name != "shards"]
    monkeypatch.setattr(pipeline, "DedupReport",
                        dataclasses.make_dataclass("DedupReport", fields))
    with pytest.raises(SystemExit, match="no 'shards' field"):
        run_cell(root, CELL, monkeypatch)


def _job(shards=1, exchanges=()):
    return types.SimpleNamespace(shards=shards, exchanges=list(exchanges),
                                 blocking_seconds=1.0,
                                 matching_seconds=0.5, partition_seconds=0.25)


def _readers():
    from bench.harness import spec

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = dict((m["name"], read) for m, read in
                 spec.per_layer(REPO, bench, CELL))
    assert set(READERS) <= set(found)
    return found


def test_new_readers_read_nothing_on_a_one_chip_trace():
    with gzip.open(os.path.join(HERE, "data", "trace_v5e_batch.json.gz"),
                   "rt") as f:
        tr = json.load(f)
    readers = _readers()
    for jobs in ([_job()], [types.SimpleNamespace(blocking_seconds=1.0)]):
        ctx = {"trace": tr, "jobs": jobs, "device_kind": "TPU v5 lite"}
        for name in READERS:
            assert readers[name](ctx) is None, name


def _ex(stage, sent, cap, index=0):
    import numpy as np

    return types.SimpleNamespace(stage=stage, index=index,
                                 sent=np.array(sent), cap=cap)


def _two_chip_trace():
    # window 0-10 ms; chip 0: all-to-all 1.0 + 0.5 ms, a psum 0.25 ms and
    # a fusion whose text names an all-reduce; chip 1: all-to-all 1.0 +
    # 0.5 ms, an all-reduce 0.5 ms (names as a TPU trace gives them)
    return {
        "window": [0, 10 * MS],
        "devices": [
            {"ops": [["%all_to_all.1", 1 * MS, 1 * MS],
                     ["%all_to_all.7 = u32[4,1,8200] all-to-all(%x)",
                      4 * MS, MS // 2],
                     ["%psum.2", 6 * MS, MS // 4],
                     ["%fusion.3 = s32[8] fusion(%all-reduce.2)", 7 * MS,
                      2 * MS]],
             "modules": [["jit_local_step(3)", 1 * MS, 4 * MS],
                         ["jit_local_round(4)", 6 * MS, 2 * MS]]},
            {"ops": [["%all_to_all.1", 1 * MS, 1 * MS],
                     ["%all_to_all.7", 4 * MS, MS // 2],
                     ["%all-reduce.5", 8 * MS, MS // 2]],
             "modules": [["jit_local_step(3)", 1 * MS, 4 * MS],
                         ["jit_local_dedupe(5)", 8 * MS, 1 * MS]]},
        ],
        "spans": [["bench.trace", 0, 10 * MS], ["bench.job", 0, 10 * MS]],
    }


def test_a2a_ici_share_on_a_hand_made_trace():
    # HDB: 1,000 entries 0 -> 1 and 2,000 entries 1 -> 0, the latter cut
    # to the 1,500 its bucket holds: 2,500 entries x 12 B = 30,000 B;
    # pairs: 500 words each way x 8 B = 8,000 B. 38,000 B over two chips
    # is 19,000 B a chip, in 1.5 ms of all-to-all a chip at 200 GB/s
    job = _job(shards=2, exchanges=[_ex("hdb", [[10, 1000], [2000, 5]], 1500),
                                    _ex("pairs", [[0, 500], [500, 0]], 1000)])
    ctx = {"trace": _two_chip_trace(), "jobs": [job],
           "device_kind": "TPU v5 lite"}
    assert batch_mesh.off_chip_bytes(job.exchanges) == 38_000
    share = _readers()["a2a_ici_share"](ctx)
    assert share == pytest.approx(19_000 / (1.5e-3 * 200e9) * 100)
    with pytest.raises(KeyError):
        _readers()["a2a_ici_share"](dict(ctx, device_kind="TPU v9"))


def test_mesh_device_readers_on_a_hand_made_trace():
    readers = _readers()
    ctx = {"trace": _two_chip_trace(), "jobs": [_job(shards=2)],
           "device_kind": "TPU v5 lite"}
    # chip 0: 1.0 + 0.5 + 0.25 ms, chip 1: 1.0 + 0.5 + 0.5 ms
    assert readers["collective_ms"](ctx) == pytest.approx(1.875)
    assert readers["dist_hdb_device_ms"](ctx) == pytest.approx(4.0)
    # local_round 2 ms on chip 0, local_dedupe 1 ms on chip 1
    assert readers["dist_pairs_device_ms"](ctx) == pytest.approx(1.5)
    # busy: chip 0 1-2, 4-4.5, 6-6.25, 7-9 = 3.75 ms; chip 1 2.0 ms
    assert readers["device_idle_share.batch4"](ctx) == pytest.approx(
        (1 - 2.875 / 10) * 100)


def test_route_skew_leaves_out_small_exchanges():
    read = _readers()["route_skew"]
    big = _ex("hdb", [[1000, 1000], [3000, 1000]], 4000)      # recv 4000/2000
    small = _ex("hdb", [[0, 0], [1, 0]], 10, index=1)          # recv 1/0
    even = _ex("pairs", [[2000, 2000], [2000, 2000]], 4000)
    assert read({"jobs": [_job(2, [big, small, even])]}) == pytest.approx(
        4000 / 3000)
    assert read({"jobs": [_job(2, [small])]}) is None
