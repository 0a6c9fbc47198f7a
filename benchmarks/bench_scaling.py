"""Paper §1/abstract claim: HDB scales ~linearly in record count.

Measures wall time across SYN sizes and fits time = a*N^p; the paper
demonstrates p ~= 1 between 1M and 530M rows on a Spark cluster; here the
same algorithm (one CPU core, jit'd fixed-shape iterations) should show
p ~= 1 over 10k -> 1M-record synthetic corpora.
"""
from __future__ import annotations

import numpy as np

from .common import emit, get_corpus, get_keys, timed

from repro.core import hdb


def run(datasets=("SYN10K", "SYN30K", "SYN100K", "SYN300K"),
        max_block_size=200, include_1m=False):
    if include_1m:
        datasets = tuple(datasets) + ("SYN1M",)
    print("# scaling: dataset,records,seconds,pairs")
    ns, ts = [], []
    cfg = hdb.HDBConfig(max_block_size=max_block_size)
    for ds in datasets:
        corpus = get_corpus(ds)
        keys, valid = get_keys(ds)
        # warm the jit caches on the first dataset shape, then measure
        res, t = timed(hdb.hashed_dynamic_blocking, keys, valid, cfg)
        res, t = timed(hdb.hashed_dynamic_blocking, keys, valid, cfg)
        print(f"scaling,{ds},{corpus.num_records},{t:.2f},{len(res.rids)}")
        ns.append(corpus.num_records)
        ts.append(t)
    p, log_a = np.polyfit(np.log(ns), np.log(ts), 1)
    print(f"scaling,fit,exponent,{p:.3f},")
    emit("scaling/fit", 0.0, f"exponent={p:.3f}")
    return p


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    run()
