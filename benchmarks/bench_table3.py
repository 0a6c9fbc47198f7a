"""Paper Table 3: pairs produced by Naive / THR / PMB / HDB."""
from __future__ import annotations

from .common import emit, get_corpus, get_keys

from repro.core import baselines, hdb, metablocking, pairs as pairs_mod


def run(datasets=("SYN10K", "VOTERSYN", "SYN100K"), max_block_size=200):
    print("# table3: dataset,naive,thr,pmb,hdb (distinct pairs)")
    out = []
    for ds in datasets:
        keys, valid = get_keys(ds)
        naive = baselines.naive_pair_count(keys, valid)
        thr = baselines.threshold_blocking(keys, valid, max_block_size)
        thr_pairs = pairs_mod.dedupe_pairs(pairs_mod.build_blocks(thr))
        res = hdb.hashed_dynamic_blocking(
            keys, valid, hdb.HDBConfig(max_block_size=max_block_size))
        hdb_pairs = pairs_mod.dedupe_pairs(pairs_mod.build_blocks(res))
        try:
            a, b = metablocking.meta_blocking(keys, valid)
            pmb_n = len(a)
        except metablocking.MetaBlockingBudgetError:
            pmb_n = -1
        print(f"table3,{ds},{naive},{len(thr_pairs.a)},{pmb_n},{len(hdb_pairs.a)}")
        emit(f"table3/{ds}", 0.0,
             f"naive={naive};thr={len(thr_pairs.a)};pmb={pmb_n};hdb={len(hdb_pairs.a)}")
        out.append((ds, naive, len(thr_pairs.a), pmb_n, len(hdb_pairs.a)))
    return out


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    run()
