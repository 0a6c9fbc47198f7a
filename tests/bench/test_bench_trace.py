"""The reduction from a profiler trace to busy time, per-program device
time and named idle gaps, on a small hand-made trace and on one recorded
on a TPU v5e."""
import gzip
import json
import os

import pytest

import _benchroot  # noqa: F401  (puts the benchmark on the path)

from bench.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _hand_made():
    # window 0-100 ms; ops overlap in 10-30 and 25-40, then 60-70 ms
    return {
        "window": [0, 100 * MS],
        "devices": [{
            "ops": [["fusion.1", 10 * MS, 20 * MS], ["sort.2", 25 * MS, 15 * MS],
                    ["fusion.3", 60 * MS, 10 * MS],
                    ["late", 95 * MS, 20 * MS]],
            "modules": [["jit__count_step(12)", 10 * MS, 30 * MS],
                        ["jit_decode_chunk(7)", 60 * MS, 10 * MS]],
        }],
        "spans": [["bench.trace", 0, 100 * MS], ["bench.job", 0, 100 * MS],
                  ["bench.step", 40 * MS, 20 * MS]],
    }


def test_busy_union_and_idle_share():
    tr = _hand_made()
    # union: 10-40, 60-70 and 95-100 (clipped to the window) = 45 ms
    assert trace.busy_s(tr) == pytest.approx(0.045)
    assert trace.window_s(tr) == pytest.approx(0.1)
    assert trace.idle_share(tr) == pytest.approx(0.55)


def test_module_names_and_seconds():
    assert trace.module_name("jit__count_step(12)") == "_count_step"
    assert trace.module_name("jit_decode_chunk") == "decode_chunk"
    secs = trace.module_seconds(_hand_made())
    assert secs == pytest.approx({"_count_step": 0.03, "decode_chunk": 0.01})


def test_idle_gaps_named_by_innermost_span():
    gaps = trace.idle_gaps(_hand_made())
    # gaps: 0-10 (job), 40-60 (step inside job), 70-95 (job)
    assert gaps == [["bench.job", pytest.approx(0.025)],
                    ["bench.step", pytest.approx(0.02)],
                    ["bench.job", pytest.approx(0.01)]]
    br = trace.breakdown(_hand_made())
    assert br["device_ops"][0] == ["_count_step", pytest.approx(0.03)]


def test_no_device_operation_reads_nothing():
    tr = dict(_hand_made(), devices=[])
    assert trace.busy_s(tr) == 0.0
    assert trace.idle_share(tr) is None
    assert trace.idle_gaps(tr) == []


def test_recorded_v5e_trace():
    """400 ms of a traced dedup job (a product catalog of 131,072
    records) on one v5e, around the switch from an HDB count step to its
    intersect step."""
    with gzip.open(os.path.join(HERE, "data", "trace_v5e_batch.json.gz"),
                   "rt") as f:
        tr = json.load(f)
    assert trace.window_s(tr) == pytest.approx(0.4)
    assert trace.busy_s(tr) == pytest.approx(0.384884494)
    assert trace.module_seconds(tr) == pytest.approx(
        {"_count_step": 0.14046275, "_intersect_step": 0.244424292})
    gaps = trace.idle_gaps(tr)
    assert gaps[0] == ["bench.job", pytest.approx(0.00953883)]
    assert all(name == "bench.job" for name, _ in gaps)
