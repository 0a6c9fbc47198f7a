"""Paper Fig 1b / Fig 3: PQ vs PC across LSH(b, w) settings (+ token
blocking reference point)."""
from __future__ import annotations



from .common import emit, get_corpus, timed

from repro.core import blocks, hdb
from repro.core.blocks import ColumnBlocking
from repro.data import metrics


def run(dataset="SYN10K", settings=((3, 8), (6, 4), (8, 4), (14, 4), (16, 3),
                                    (1, 1)),
        max_block_size=200, include_token_blocking=True):
    corpus = get_corpus(dataset)
    labeled = corpus.labeled_pairs()
    print("# fig1b: dataset,blocking,pq,pc,pairs")
    rows = []

    def eval_blocking(tag, blocking):
        keys, valid = blocks.build_keys(corpus.columns, blocking)
        res, t = timed(hdb.hashed_dynamic_blocking, keys, valid,
                       hdb.HDBConfig(max_block_size=max_block_size))
        m = metrics.evaluate(res, corpus, labeled)
        print(f"fig1b,{dataset},{tag},{m.pq:.4g},{m.pc:.4g},{m.distinct_pairs}")
        rows.append((tag, m.pq, m.pc, m.distinct_pairs))
        return m

    for b, w in settings:
        blocking = dict(corpus.blocking)
        for col in ("name", "description"):
            blocking[col] = ColumnBlocking.lsh(b, w)
        eval_blocking(f"LSH({b},{w})", blocking)

    if include_token_blocking:
        blocking = {c: ColumnBlocking.token() for c in corpus.columns}
        eval_blocking("TOKEN", blocking)

    emit(f"fig1b/{dataset}", 0.0, f"settings={len(rows)}")
    return rows


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    run()
