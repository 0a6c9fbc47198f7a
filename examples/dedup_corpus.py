"""End-to-end 4-stage dedup pipeline (paper §1): blocking -> pairwise
matching -> graph partitioning -> canonical records, with a blocking-stage
comparison (HDB vs threshold baseline).

    PYTHONPATH=src python examples/dedup_corpus.py [--entities 5000]
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.core import hdb
from repro.data import pipeline, synthetic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=5_000)
    ap.add_argument("--max-block-size", type=int, default=100)
    args = ap.parse_args()

    corpus = synthetic.generate(synthetic.SyntheticSpec(
        num_entities=args.entities, seed=7))
    print(f"corpus: {corpus.num_records} records")

    for blocker in ("threshold", "hdb"):
        rep = pipeline.dedup_corpus(
            corpus, hdb.HDBConfig(max_block_size=args.max_block_size),
            blocker=blocker)
        q = pipeline.dedup_quality(rep, corpus)
        print(f"\n[{blocker}] candidates={rep.num_candidate_pairs} "
              f"matched={rep.num_matched_pairs} "
              f"components={rep.num_components}")
        print(f"[{blocker}] block={rep.blocking_seconds:.2f}s "
              f"match={rep.matching_seconds:.2f}s "
              f"partition={rep.partition_seconds:.2f}s")
        print(f"[{blocker}] pair_recall={q['pair_recall']:.4f} "
              f"pair_precision={q['pair_precision']:.4f} "
              f"dedup_ratio={q['dedup_ratio']:.3f}")


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    main()
