"""Fused on-device dedup demo: records -> blocks -> pairs -> clusters.

Runs the full 4-stage pipeline twice over the same synthetic corpus —
once on the host match/cluster baseline and once on the fused device
path (``match_backend="auto"``: score+threshold+compaction in
kernels/match, bounded-round connected components + survivor extraction
on device) — prints per-stage timings and cluster quality, and asserts
the two back halves are bit-identical (same matched pairs, labels, and
survivors; the docs/PIPELINE.md contract).

    PYTHONPATH=src python examples/fused_dedup.py [--entities 2000]
    PYTHONPATH=src python examples/fused_dedup.py --smoke   # CI-sized
"""
import argparse
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.core import hdb
from repro.data import pipeline, synthetic
from repro.data.pipeline import dedup_quality


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=2_000)
    ap.add_argument("--max-block-size", type=int, default=50)
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "jnp", "pallas"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus + parity assert (CI smoke step)")
    args = ap.parse_args()
    if args.smoke:
        args.entities = 150

    corpus = synthetic.generate(synthetic.SyntheticSpec(
        num_entities=args.entities, seed=7))
    cfg = hdb.HDBConfig(max_block_size=args.max_block_size, max_iterations=6,
                        cms_width=1 << (12 if args.smoke else 16))
    print(f"corpus: {corpus.num_records} records, "
          f"{args.entities} true entities")

    def show(name, rep):
        print(f"  {name:>6}: block {rep.blocking_seconds:6.3f}s | "
              f"match {rep.matching_seconds:6.3f}s | "
              f"cluster {rep.partition_seconds:6.3f}s | "
              f"{rep.num_candidate_pairs} pairs -> "
              f"{rep.num_matched_pairs} matched -> "
              f"{rep.num_components} clusters")

    host = pipeline.dedup_corpus(corpus, cfg, match_backend="host")
    show("host", host)
    fused = pipeline.dedup_corpus(corpus, cfg, match_backend=args.backend)
    show(args.backend, fused)

    # the fused-path contract: bit-identical, not merely close
    assert fused.num_matched_pairs == host.num_matched_pairs
    np.testing.assert_array_equal(fused.component_of, host.component_of)
    np.testing.assert_array_equal(fused.survivors, host.survivors)
    print("fused back half is bit-identical to the host baseline")

    q = dedup_quality(fused, corpus)
    print(f"quality: pair_recall={q['pair_recall']:.3f} "
          f"pair_precision={q['pair_precision']:.3f} "
          f"dedup_ratio={q['dedup_ratio']:.3f}")
    print("OK")


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    main()
