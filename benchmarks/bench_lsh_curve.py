"""Paper Fig 1a: P[share a band key] vs Jaccard for LSH(b, w) — analytic
curve validated against empirical band collisions."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .common import emit

from repro.core import minhash
from repro.data import synthetic


def run(settings=((6, 4), (14, 4), (3, 8), (1, 1)),
        jaccards=(0.2, 0.4, 0.6, 0.8)):
    print("# fig1a: b,w,jaccard,analytic,empirical")
    rows = []
    for b, w in settings:
        for j in jaccards:
            a, bb, true_j = synthetic.jaccard_pair_corpus(400, j, set_size=60,
                                                          seed=17)
            m = jnp.ones(a.shape, bool)
            ka, _ = minhash.lsh_keys(jnp.asarray(a), m, b, w)
            kb, _ = minhash.lsh_keys(jnp.asarray(bb), m, b, w)
            share = ((np.asarray(ka[0]) == np.asarray(kb[0]))
                     & (np.asarray(ka[1]) == np.asarray(kb[1]))).any(axis=1)
            analytic = float(minhash.lsh_probability(b, w, true_j))
            print(f"fig1a,{b},{w},{true_j:.3f},{analytic:.4f},{share.mean():.4f}")
            rows.append((b, w, true_j, analytic, float(share.mean())))
    worst = max(abs(r[3] - r[4]) for r in rows)
    emit("fig1a/lsh_curve", 0.0, f"max_abs_err={worst:.4f}")
    return rows


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    run()
