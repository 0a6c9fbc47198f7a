"""The harness end to end on the CPU: the result line's shape, discovery
of a cell added as files alone, and refusal without an accelerator."""
import json
import os
import shutil
import subprocess
import sys

from _benchroot import REPO, edit_json, run_cell, tiny_root


def _assert_line_shape(line, metric_names, traced):
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "checks"
    for key in ("metrics", "device"):
        assert key in line
    assert set(line["metrics"]) <= set(metric_names)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float | int)
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if traced:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for check in line["checks"].values():
        assert set(check) == {"value", "limit"}


def test_registry_batch_line(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    rc, line, err, out = run_cell(root, "registry-batch", monkeypatch)
    assert rc == 0, err
    _assert_line_shape(line, {"records_per_s", "setup_s"}, traced=False)
    assert set(line["metrics"]) == {"records_per_s", "setup_s"}
    assert line["correct"] is True and line["attempted"] >= 1
    assert set(line["checks"]) == {"blocks_diff", "pairs_diff",
                                   "matched_diff", "labels_diff",
                                   "survivors_diff"}
    # every number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)
    assert "compiles inside the window: 0 " in out

    rc, traced, err, out = run_cell(root, "registry-batch", monkeypatch,
                                    trace=1)
    assert rc == 0, err
    _assert_line_shape(traced, {"hdb_device_ms", "pairs_device_ms",
                                "blocking_ms", "back_half_ms",
                                "device_idle_share.batch"}, traced=True)
    # program spans are there; a CPU trace has no device plane to read
    assert {"blocking_ms", "back_half_ms"} <= set(traced["metrics"])
    assert traced["correct"] is True


def test_registry_probe_line(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    rc, line, err, out = run_cell(root, "registry-probe", monkeypatch)
    assert rc == 0, err
    _assert_line_shape(line, {"probe_p50_ms", "setup_s"}, traced=False)
    assert set(line["metrics"]) == {"probe_p50_ms", "setup_s"}
    assert line["correct"] is True
    assert line["attempted"] > 50 and line["failed"] == 0
    assert line["metrics"]["probe_p50_ms"]["value"] > 0
    assert '"p99_ms": ' in err and '"stalls": ' in err


def test_cell_added_as_files_alone_is_found(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    bench = os.path.join(root, "bench")
    shutil.copy(os.path.join(bench, "configs", "registry.json"),
                os.path.join(bench, "configs", "registry_small.json"))
    edit_json(os.path.join(bench, "configs", "registry_small.json"),
              records=1536)
    shutil.copy(os.path.join(bench, "traffic", "closed_jobs.json"),
                os.path.join(bench, "traffic", "closed_jobs_again.json"))
    with open(os.path.join(bench, "metrics", "jobs_traced.py"), "w") as f:
        f.write("def read(ctx):\n    jobs = ctx.get('jobs')\n"
                "    return float(len(jobs)) if jobs else None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append(dict(spec["configs"][0], name="registry_small",
                                file="bench/configs/registry_small.json"))
    spec["workloads"].append({"name": "registry_small-batch",
                              "config": "registry_small",
                              "traffic": "closed_jobs_again", "chips": 1,
                              "why": "a cell added by files alone"})
    spec["end_to_end"][0]["workloads"].append("registry_small-batch")
    spec["per_layer"].append({"name": "jobs_traced", "unit": "jobs",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "records_per_s",
                              "workloads": ["registry_small-batch"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    rc, line, err, out = run_cell(root, "registry_small-batch", monkeypatch)
    assert rc == 0, err
    assert set(line["metrics"]) == {"records_per_s", "setup_s"}
    assert line["correct"] is True
    rc, traced, err, out = run_cell(root, "registry_small-batch", monkeypatch,
                               trace=1)
    assert rc == 0, err
    assert traced["metrics"]["jobs_traced"]["value"] == 1.0


def _command(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "registry-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = _command(REPO, env)
    assert got.returncode != 0
    assert "{" not in got.stdout
    assert "accelerator" in got.stderr


def test_bare_benchmark_files_exit_nonzero(tmp_path):
    root = tmp_path / "bare"
    for path in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(REPO, path), root / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    got = _command(str(root), env)
    assert got.returncode != 0
    assert "{" not in got.stdout
