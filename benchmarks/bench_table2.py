"""Paper Table 2: PQ / PC / elapsed time for THR vs PMB vs HDB."""
from __future__ import annotations

from .common import emit, get_corpus, get_keys, timed

from repro.core import baselines, hdb, metablocking
from repro.data import metrics


def run(datasets=("SYN10K", "VOTERSYN", "SYN100K"), max_block_size=200):
    print("# table2: dataset,method,pq,pc,pairs,seconds")
    rows = []
    for ds in datasets:
        corpus = get_corpus(ds)
        keys, valid = get_keys(ds)
        labeled = corpus.labeled_pairs()

        thr, t_thr = timed(baselines.threshold_blocking, keys, valid,
                           max_block_size)
        m_thr = metrics.evaluate(thr, corpus, labeled)

        cfg = hdb.HDBConfig(max_block_size=max_block_size)
        res, t_hdb = timed(hdb.hashed_dynamic_blocking, keys, valid, cfg)
        m_hdb = metrics.evaluate(res, corpus, labeled)

        try:
            pmb, t_pmb = timed(metablocking.meta_blocking_result, keys, valid)
            m_pmb = metrics.evaluate(pmb, corpus, labeled)
            pmb_row = (m_pmb.pq, m_pmb.pc, m_pmb.distinct_pairs // 2, t_pmb)
        except metablocking.MetaBlockingBudgetError as e:
            pmb_row = (float("nan"), float("nan"), 0, float("nan"))
            print(f"# PMB failed on {ds}: {e} (mirrors paper §5.3)")

        for method, (pq, pc, pairs, t) in [
            ("THR", (m_thr.pq, m_thr.pc, m_thr.distinct_pairs, t_thr)),
            ("PMB", pmb_row),
            ("HDB", (m_hdb.pq, m_hdb.pc, m_hdb.distinct_pairs, t_hdb)),
        ]:
            print(f"table2,{ds},{method},{pq:.4g},{pc:.4g},{pairs},{t:.2f}")
            rows.append((ds, method, pq, pc, pairs, t))
        emit(f"table2/{ds}/hdb", t_hdb * 1e6,
             f"pq={m_hdb.pq:.4g};pc={m_hdb.pc:.4g}")
    return rows


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    run()
