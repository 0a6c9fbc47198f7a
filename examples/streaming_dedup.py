"""Streaming dedup service demo: micro-batch ingest + candidate queries.

Feeds a synthetic corpus through the StreamingEngine in arrival order,
printing what each micro-batch changed (new candidate pairs, retracted
pairs, dirty rows per HDB level), then issues serving-style probe queries,
and finally verifies the incrementally-maintained candidate-pair ledger
against one batch HDB run on the union.

    PYTHONPATH=src python examples/streaming_dedup.py [--entities 2000]
    PYTHONPATH=src python examples/streaming_dedup.py --smoke   # CI-sized
"""
import argparse
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.core import blocks as blocks_mod
from repro.core import hdb, pairs
from repro.data import matcher, synthetic
from repro.streaming import RecordBatch, StreamingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=2_000)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--max-block-size", type=int, default=50)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus + parity assert (CI smoke step)")
    args = ap.parse_args()
    if args.smoke:
        args.entities, args.batches = 120, 4

    corpus = synthetic.generate(synthetic.SyntheticSpec(
        num_entities=args.entities, seed=7))
    n = corpus.num_records
    cfg = hdb.HDBConfig(max_block_size=args.max_block_size, max_iterations=6,
                        cms_width=1 << (12 if args.smoke else 16))
    print(f"corpus: {n} records arriving in {args.batches} micro-batches")

    eng = StreamingEngine(corpus.blocking, cfg, ingest_slots=4096,
                          matcher_cfg=matcher.MatcherConfig())
    for part in np.array_split(np.arange(n), args.batches):
        eng.submit_ingest(RecordBatch.from_corpus(corpus, part))
        eng.step()
        r = eng.ingest_results[-1]
        rep = r.report
        dirty = ",".join(str(lv.n_dirty_rows) for lv in rep.levels)
        n_match = (int((r.match_scores >= 0.65).sum())
                   if r.match_scores is not None else 0)
        print(f"  ingest +{rep.num_records:5d} records: "
              f"+{len(rep.pairs_added[0]):6d}/-{len(rep.pairs_retracted[0]):4d} "
              f"pairs ({n_match} matched) dirty_rows/level=[{dirty}] "
              f"{rep.seconds:.2f}s")

    # serving-style probes: re-present the first few records as queries
    probe_ids = np.arange(min(4, n))
    eng.submit_query(RecordBatch.from_corpus(corpus, probe_ids))
    eng.run()
    for pid, pr in zip(probe_ids, eng.probe_results):
        r = pr.result
        print(f"  query record {pid}: {len(r.candidates)} candidates from "
              f"{r.n_blocks_hit} blocks ({r.levels_walked} levels walked)")

    got = eng.store.candidate_pairs()
    stats = eng.store.memory_stats()
    print(f"store: {stats['accepted_blocks']} blocks, "
          f"{stats['accepted_assignments']} assignments, "
          f"{stats['ledger_pairs']} candidate pairs")

    # verify against one batch run on the union
    keys, valid = blocks_mod.build_keys(corpus.columns, corpus.blocking)
    res = hdb.hashed_dynamic_blocking(keys, valid, cfg)
    blk = pairs.build_blocks(res)
    want = pairs.dedupe_pairs(blk, budget=blk.num_pair_slots + 1)
    same = (np.array_equal(got.a, want.a) and np.array_equal(got.b, want.b)
            and np.array_equal(got.src_size, want.src_size))
    print(f"batch-parity: {'EXACT' if same else 'MISMATCH'} "
          f"({len(got.a)} vs {len(want.a)} pairs)")
    if not same:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    main()
