"""Program spans (``repro.obs``): what a ``jax.profiler`` trace of the batch
job and of the probe service holds — names, nesting, arguments — and the
step counters the service keeps for operators."""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from test_streaming import _random_keys

from repro import obs
from repro.core import blocks, hdb
from repro.data import pipeline, synthetic
from repro.serving import DedupeService, ServiceConfig


def _profiled(trace_dir: str, fn):
    """Run ``fn`` under the profiler; returns its result and the trace's
    ``repro.*`` host events as ``(name, start_ns, end_ns, args)``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                          dict(e.stats))
                         for e in line.events if e.name.startswith("repro."))
    return out, sorted(spans, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def _each_inside(spans, child: str, parent: str) -> list:
    """Every ``child`` span lies inside one ``parent`` span; returns the
    children (at least one)."""
    kids = _named(spans, child)
    assert kids, f"no {child} span"
    for k in kids:
        assert any(_inside(k, p) for p in _named(spans, parent)), \
            f"{child} at {k[1]} outside every {parent}"
    return kids


def test_span_records_args_and_its_own_wall_time(tmp_path):
    def work():
        with obs.span("repro.test.outer", rows=3) as outer:
            with obs.span("repro.test.inner"):
                np.sort(np.random.default_rng(0).random(200_000))
            obs.mark("repro.test.inner.counts", n=7)
        return outer.seconds

    seconds, spans = _profiled(str(tmp_path), work)
    (outer,) = _named(spans, "repro.test.outer")
    assert outer[3] == {"rows": 3}
    _each_inside(spans, "repro.test.inner", "repro.test.outer")
    (counts,) = _each_inside(spans, "repro.test.inner.counts",
                             "repro.test.outer")
    assert counts[3] == {"n": 7} and counts[2] - counts[1] < 1e6
    # the clock the program reads is the span's own, to within its edges
    assert abs((outer[2] - outer[1]) / 1e9 - seconds) < 1e-3


def test_thread_usage_counts_cpu_time():
    before = obs.thread_usage()
    np.sort(np.random.default_rng(1).random(500_000))
    after = obs.thread_usage()
    assert len(before) == 5
    assert after[0] > before[0]
    assert all(b >= a for a, b in zip(before, after))


@pytest.fixture(scope="module")
def batch_trace(tmp_path_factory):
    """One traced ``dedup_corpus`` over a corpus with two HDB levels and
    enough pair slots for the device pair path, after a warm-up job."""
    corpus = synthetic.generate(synthetic.SyntheticSpec(num_entities=600,
                                                        seed=5))
    cfg = hdb.HDBConfig(max_block_size=40)
    pipeline.dedup_corpus(corpus, cfg)
    rep, spans = _profiled(str(tmp_path_factory.mktemp("batch")),
                           lambda: pipeline.dedup_corpus(corpus, cfg))
    _, valid = blocks.build_keys(corpus.columns, corpus.blocking)
    return (rep, valid.shape[1]), spans


def test_batch_spans_nest_by_stage(batch_trace):
    _, spans = batch_trace
    for child, parent in [
            ("repro.pipeline.keys", "repro.pipeline.blocking"),
            ("repro.hdb.iteration", "repro.pipeline.blocking"),
            ("repro.hdb.count", "repro.hdb.iteration"),
            ("repro.hdb.reps", "repro.hdb.iteration"),
            ("repro.hdb.intersect", "repro.hdb.iteration"),
            ("repro.hdb.accept", "repro.hdb.iteration"),
            ("repro.hdb.iteration.counts", "repro.hdb.iteration"),
            ("repro.pairs.blocks", "repro.pipeline.blocking"),
            ("repro.pairs.decode", "repro.pipeline.blocking"),
            ("repro.pairs.sort", "repro.pipeline.blocking"),
            ("repro.pairs.sort.counts", "repro.pipeline.blocking"),
            ("repro.match.compact", "repro.pipeline.match"),
            ("repro.components.cc", "repro.pipeline.partition"),
            ("repro.components.cc.counts", "repro.pipeline.partition")]:
        _each_inside(spans, child, parent)
    stages = [_named(spans, f"repro.pipeline.{s}")
              for s in ("blocking", "match", "partition")]
    assert [len(s) for s in stages] == [1, 1, 1]
    # the stages follow one another
    assert stages[0][0][2] <= stages[1][0][1] <= stages[1][0][2] \
        <= stages[2][0][1]


def test_batch_counts_ride_on_spans(batch_trace):
    (rep, width), spans = batch_trace
    iters = _named(spans, "repro.hdb.iteration")
    assert [s[3] for s in iters] == [{"iteration": i}
                                     for i in range(len(iters))]
    assert len(iters) >= 2
    counts = _named(spans, "repro.hdb.iteration.counts")
    assert len(counts) == len(iters)
    # the first iteration counts densely over every top-level key of
    # every record; later ones over a compaction of their live entries
    assert counts[0][3]["slots"] == rep.num_records * width
    assert counts[0][3]["lanes"] == counts[0][3]["slots"]
    for _, _, _, args in counts:
        assert set(args) == {"slots", "live", "lanes", "reps", "surviving"}
        assert 0 <= args["surviving"] <= args["reps"] <= args["live"] \
            <= args["lanes"] <= args["slots"]
    (sort,) = _named(spans, "repro.pairs.sort")
    assert sort[3] == {"slots": rep.pairs.total_slots}
    (pairs,) = _named(spans, "repro.pairs.sort.counts")
    assert pairs[3] == {"pairs": rep.num_candidate_pairs}
    (cc,) = _named(spans, "repro.components.cc.counts")
    assert cc[3]["converged"] and 1 <= cc[3]["rounds"] <= 64


def test_stage_seconds_are_the_pipeline_spans(batch_trace):
    (rep, _), spans = batch_trace
    for stage, seconds in [("blocking", rep.blocking_seconds),
                           ("match", rep.matching_seconds),
                           ("partition", rep.partition_seconds)]:
        (s,) = _named(spans, f"repro.pipeline.{stage}")
        assert abs((s[2] - s[1]) / 1e9 - seconds) < 1e-3, stage


@pytest.fixture(scope="module")
def service_trace(tmp_path_factory):
    """A few traced service steps: probes that walk at least two levels,
    then one ingest."""
    cfg = hdb.HDBConfig(max_block_size=8, max_iterations=5,
                        max_oversize_keys=6, cms_width=1 << 10)
    keys, valid = _random_keys(np.random.default_rng(9), n=140, k=6,
                               card=15)
    svc = DedupeService(cfg, ServiceConfig(probe_slots=8, min_bucket=4))
    svc.add_tenant("t")
    svc.submit_ingest("t", keys[:100], valid[:100])
    svc.run()

    def steps():
        for off in range(100, 124, 4):
            svc.submit_probe("t", keys[off:off + 4], valid[off:off + 4])
        svc.submit_ingest("t", keys[124:140], valid[124:140])
        svc.run()
        return svc

    svc.submit_probe("t", keys[100:104], valid[100:104])
    svc.run()                                   # compile the walk
    return _profiled(str(tmp_path_factory.mktemp("service")), steps)


def test_service_spans_nest_by_stage(service_trace):
    _, spans = service_trace
    for child, parent in [
            ("repro.service.read", "repro.service.step"),
            ("repro.service.write", "repro.service.step"),
            ("repro.walk", "repro.service.read"),
            ("repro.walk.level", "repro.walk"),
            ("repro.walk.cms", "repro.walk.level"),
            ("repro.walk.classify", "repro.walk.level"),
            ("repro.walk.lookup", "repro.walk.level"),
            ("repro.walk.gather", "repro.walk.level"),
            ("repro.walk.intersect", "repro.walk.level"),
            ("repro.walk.results", "repro.walk")]:
        _each_inside(spans, child, parent)
    levels = {s[3]["level"] for s in _named(spans, "repro.walk.level")}
    assert {0, 1} <= levels


def test_service_spans_carry_batch_and_queue_args(service_trace):
    svc, spans = service_trace
    reads = _named(spans, "repro.service.read")
    assert sum(s[3]["requests"] for s in reads) == 6
    assert sum(s[3]["rows"] for s in reads) == 24
    for _, _, _, args in reads:
        assert args["bucket"] >= args["rows"] and args["queued_s_sum"] >= 0
    (write,) = _named(spans, "repro.service.write")
    assert write[3] == {"rows": 16}
    steps = _named(spans, "repro.service.step")
    usage = _named(spans, "repro.service.step.usage")
    assert len(usage) == len(steps) == len(reads)
    for step, (_, start, _, args) in zip(steps, usage):
        assert step[2] <= start
        assert set(args) == {"cpu_us", "involuntary", "voluntary",
                             "major_faults", "minor_faults"}
        assert 0 <= args["cpu_us"] <= (step[2] - step[1]) / 1e3


def test_step_usage_accumulates_in_counters(service_trace):
    svc, _ = service_trace
    counters = svc.snapshot()["counters"]
    assert 0 < counters["step_cpu_seconds_total"] \
        <= counters["step_wall_seconds_total"]
    assert counters["step_involuntary_switches_total"] >= 0
    assert counters["step_major_faults_total"] >= 0
