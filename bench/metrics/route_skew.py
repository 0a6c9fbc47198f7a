"""Exchanges between chips: how unevenly the routed exchanges load the
chips. For each all_to_all of a job (``DedupReport.exchanges``: HDB's
exact-count exchange per iteration, the pair rounds), the most live
entries one chip received over the mean over chips; the job's largest
such ratio, averaged over the jobs. Exchanges in which a chip receives
fewer than ``MIN_MEAN`` entries on average are left out: a handful of
entries reads as skew by counting alone (one entry over four chips reads
4.0)."""
import numpy as np

MIN_MEAN = 1024


def read(ctx):
    jobs = ctx.get("jobs")
    if not jobs:
        return None
    worst = []
    for r in jobs:
        ratios = []
        for ex in getattr(r, "exchanges", None) or []:
            received = np.asarray(ex.sent).sum(axis=0)
            if received.mean() >= MIN_MEAN:
                ratios.append(received.max() / received.mean())
        if ratios:
            worst.append(max(ratios))
    return float(np.mean(worst)) if worst else None
