"""Quickstart: block a small synthetic product catalog with Hashed Dynamic
Blocking and inspect the quality metrics.

    PYTHONPATH=src python examples/quickstart.py
"""
import logging
import sys

sys.path.insert(0, "src")

# per-iteration [hdb] stats flow through logging (verbose=True -> INFO)
logging.basicConfig(level=logging.INFO, format="%(message)s")

from repro.core import blocks, hdb, pairs
from repro.data import metrics, synthetic


def main():
    # 1) a corpus with planted duplicates + complete ground truth
    corpus = synthetic.generate(synthetic.SyntheticSpec(num_entities=3_000,
                                                        seed=42))
    print(f"corpus: {corpus.num_records} records "
          f"({corpus.num_records - 3_000} duplicates planted)")

    # 2) top-level blocking keys: LSH(6,4) on text columns, identity on
    #    scalar columns (paper §2)
    keys, valid = blocks.build_keys(corpus.columns, corpus.blocking)
    print(f"top-level keys: {keys.shape[1]} per record")

    # 3) Hashed Dynamic Blocking (paper §3, Algorithms 1-4)
    cfg = hdb.HDBConfig(max_block_size=100)
    result = hdb.hashed_dynamic_blocking(keys, valid, cfg, verbose=True)

    # 4) blocks -> deduplicated candidate pairs
    blk = pairs.build_blocks(result)
    pset = pairs.dedupe_pairs(blk)
    print(f"\nblocks: {blk.num_blocks}, largest {int(blk.size.max())}, "
          f"distinct pairs: {len(pset.a)}")

    # 5) quality vs ground truth
    m = metrics.evaluate(result, corpus)
    print(f"PQ (precision) = {m.pq:.4f}   PC (recall) = {m.pc:.4f}")
    assert m.pc > 0.8, "quickstart expects healthy recall"


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    main()
