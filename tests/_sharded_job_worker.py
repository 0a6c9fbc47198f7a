"""Subprocess worker: ``dedup_corpus`` on columns placed over a 4-device
mesh runs the sharded job, and its answer is the plain reference's and
the one-device job's. Invoked by test_sharded_job.py with
XLA_FLAGS=--xla_force_host_platform_device_count=4 set in the child env.

Cases (argv[1]):
  answer    two registry corpora (content seeds 3 and 7), and seed 3 with
            a sketch narrow enough that the exact counts decide: blocks,
            candidate pairs, matched-pair count, labels and survivors
            equal ``bench/reference`` and the one-device job; the report
            reads 4 shards, no overflow, no fallback
  overflow  a route_slack too small for the pair rounds: the routed
            dedupe falls back, the report counts it, the answer holds
  spans     the sharded job's program spans and counters in a profile
"""
import functools
import glob
import json
import os
import sys
import tempfile

assert "--xla_force_host_platform_device_count=4" in os.environ.get(
    "XLA_FLAGS", "")

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.harness import batch, common, corpus
from repro.core import distributed
from repro.core.blocks import TokenColumn
from repro.data import pipeline, synthetic

RECORDS = 4096
ORDER_SEED = 4294967311


def registry(content_seed: int, cms_width: int = 0) -> dict:
    with open(os.path.join(REPO, "bench", "configs", "registry.json")) as f:
        cfg = json.load(f)
    cfg["records"] = RECORDS
    cfg["content_seed"] = content_seed
    if cms_width:
        cfg["hdb"]["cms_width"] = cms_width
    return cfg


def mesh4() -> Mesh:
    return Mesh(np.array(jax.devices()[:4]), ("data",))


def job(cfg: dict, columns: dict, entity_id, mesh=None):
    """One ``dedup_corpus`` job: over ``mesh`` by rows, or on one device."""
    if mesh is None:
        placed = common.program_columns(columns)
    else:
        rows = NamedSharding(mesh, P("data", None))
        placed = {name: TokenColumn(jax.device_put(tok, rows),
                                    jax.device_put(mask, rows))
                  for name, (tok, mask) in columns.items()}
    data = synthetic.Corpus(columns=placed,
                            blocking=common.program_blocking(cfg),
                            entity_id=entity_id, num_records=len(entity_id))
    return pipeline.dedup_corpus(
        data, common.hdb_config(cfg), batch._matcher_config(cfg),
        pair_budget=cfg["pairs"]["budget"], cc_max_rounds=cfg["cc_max_rounds"])


def assert_same(got: dict, want: dict, label: str) -> None:
    diff = batch.compare(got, want)
    assert not any(diff.values()), (label, diff)


def exact_count_accepts(cfg: dict, columns: dict) -> int:
    """Entries the one-device HDB accepts on the exact count (not the
    sketch) for this corpus: where the sharded job's counts map decides."""
    from repro.core import blocks, hdb

    keys, valid = blocks.build_keys(common.program_columns(columns),
                                    common.program_blocking(cfg))
    result = hdb.hashed_dynamic_blocking(keys, valid, common.hdb_config(cfg))
    return sum(st.n_right_exact for st in result.stats)


def case_answer() -> None:
    # content seeds 3 and 7 at the configuration's sketch, which over-counts
    # no key here; then a 256-wide sketch, which over-counts most, so the
    # counts map of over-sized keys decides what is accepted
    for content_seed, cms_width in ((3, 0), (7, 0), (3, 256)):
        cfg = registry(content_seed, cms_width)
        columns, entity_id = corpus.records(cfg, ORDER_SEED)
        want = batch.reference_answer(cfg, columns)
        assert want["pairs_exact"] and len(want["pairs"]) > 1000
        if cms_width:
            assert exact_count_accepts(cfg, columns) > 10_000
        sharded = job(cfg, columns, entity_id, mesh4())
        one = job(cfg, columns, entity_id)
        assert (sharded.shards, sharded.route_overflows,
                sharded.pair_fallbacks) == (4, 0, 0), sharded
        assert one.shards == 1 and not one.exchanges
        label = f"content seed {content_seed}, sketch width {cms_width}"
        assert_same(batch.answer_of(sharded), want, f"sharded, {label}")
        assert_same(batch.answer_of(one), want, f"one device, {label}")
        assert_same(batch.answer_of(sharded), batch.answer_of(one),
                    f"sharded vs one device, {label}")
        stages = [ex.stage for ex in sharded.exchanges]
        assert stages[0] == "hdb" and stages[-1] == "pairs", stages
        words = sum(int(ex.sent.sum()) for ex in sharded.exchanges
                    if ex.stage == "pairs")
        assert words == want["pairs_total"], (words, want["pairs_total"])
    print("OK answer")


def case_overflow() -> None:
    cfg = registry(3)
    columns, entity_id = corpus.records(cfg, ORDER_SEED)
    want = batch.reference_answer(cfg, columns)
    routed = distributed.dedupe_pairs_distributed
    distributed.dedupe_pairs_distributed = functools.partial(
        routed, route_slack=0.05)
    try:
        rep = job(cfg, columns, entity_id, mesh4())
    finally:
        distributed.dedupe_pairs_distributed = routed
    assert rep.shards == 4 and rep.pair_fallbacks == 1, rep
    assert "overflowed" in rep.pairs.fallback
    assert any(ex.fill > ex.cap for ex in rep.exchanges
               if ex.stage == "pairs")
    assert_same(batch.answer_of(rep), want, "after the fallback")
    print("OK overflow")


def _spans(trace_dir: str) -> list:
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, dict(e.stats)) for e in line.events
                           if e.name.startswith("repro."))
    return out


def case_spans() -> None:
    cfg = registry(3)
    columns, entity_id = corpus.records(cfg, ORDER_SEED)
    mesh = mesh4()
    job(cfg, columns, entity_id, mesh)    # compile outside the profile
    with tempfile.TemporaryDirectory() as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            rep = job(cfg, columns, entity_id, mesh)
        finally:
            jax.profiler.stop_trace()
        spans = _spans(trace_dir)
    names = {n for n, _ in spans}
    assert {"repro.pipeline.blocking", "repro.pipeline.keys",
            "repro.dist.hdb.iteration", "repro.dist.hdb.step",
            "repro.dist.hdb.accept", "repro.dist.hdb.iteration.counts",
            "repro.pairs.blocks", "repro.dist.pairs.round",
            "repro.dist.pairs.round.counts", "repro.pipeline.match",
            "repro.dist.match", "repro.pipeline.partition",
            "repro.dist.gather", "repro.components.cc"} <= names, names
    assert "repro.hdb.iteration" not in names   # not the one-device loop
    assert "repro.dist.pairs.fallback" not in names
    hdb = [a for n, a in spans if n == "repro.dist.hdb.iteration.counts"]
    ex_hdb = [ex for ex in rep.exchanges if ex.stage == "hdb"]
    assert len(hdb) == len(ex_hdb) == len(
        [n for n, _ in spans if n == "repro.dist.hdb.iteration"])
    assert int(hdb[0]["live"]) > 0
    for args, ex in zip(hdb, ex_hdb):
        assert int(args["slots"]) >= int(args["live"])
        assert (int(args["fill"]), int(args["cap"])) == (ex.fill, ex.cap)
        assert int(args["rep_overflow"]) == 0
        assert [int(args[f"off_chip_{i}"]) for i in range(4)] == \
            list(ex.off_chip)
    rounds = [a for n, a in spans if n == "repro.dist.pairs.round.counts"]
    ex_pairs = [ex for ex in rep.exchanges if ex.stage == "pairs"]
    assert len(rounds) == len(ex_pairs) >= 1
    for args, ex in zip(rounds, ex_pairs):
        assert int(args["words"]) == int(ex.sent.sum())
        assert int(args["overflow"]) == 0 and ex.fill <= ex.cap
    print("OK spans")


if __name__ == "__main__":
    {"answer": case_answer, "overflow": case_overflow,
     "spans": case_spans}[sys.argv[1]]()
