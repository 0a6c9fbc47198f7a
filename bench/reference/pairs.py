"""Plain reference of the candidate pairs, the matcher and the clusters.

- Blocks are the accepted blocks of two or more records. Their pair
  slots are numbered block by block in key order, and within a block of
  members sorted by record id row by row: (0,1), (0,2), ..., (1,2), ...
- Within the budget every slot is a candidate; beyond it a uniform
  sample of ``budget`` slots is drawn with numpy's generator seeded by
  ``sample_seed``: a permutation when the budget is at least half the
  slots, otherwise rounds of draws with replacement, deduplicated, then
  subsampled to the budget.
- Each distinct pair keeps the size of the largest block that gave it.
- A pair matches when its weighted token-overlap score reaches the
  threshold: per column, shared = the record's valid token positions whose
  token the other record holds, score = shared / (n_a + n_b - shared),
  columns where either side has no token drop out of the weighted mean.
- Clusters are the connected components of the matched pairs, labelled
  by their smallest record id; a component's label is its survivor.
"""
from __future__ import annotations

import functools

import numpy as np

from .hdb import run_starts


def blocks(blocking):
    """(start, size, members) of accepted blocks with two or more records."""
    _, start, size = blocking.members()
    keep = size >= 2
    return start[keep], size[keep], blocking.rid


def sample_slots(total: int, budget: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    budget = max(0, min(budget, total))
    if budget == 0:
        return np.zeros(0, np.int64)
    if 2 * budget >= total:
        return np.sort(rng.permutation(total)[:budget]).astype(np.int64)
    uniq = np.zeros(0, np.int64)
    while len(uniq) < budget:
        need = budget - len(uniq)
        draws = rng.integers(0, total, size=int(need * 1.1) + 16,
                             dtype=np.int64)
        uniq = np.unique(np.concatenate([uniq, draws]))
    if len(uniq) > budget:
        uniq = np.sort(uniq[rng.choice(len(uniq), budget, replace=False)])
    return uniq


def _triangles(max_n: int):
    """Row-major (i, j), i < j < n, of every n <= max_n, concatenated,
    with the offset of each n's triangle."""
    ii, jj, off = [], [], np.zeros(max_n + 2, np.int64)
    for n in range(max_n + 1):
        i, j = np.triu_indices(n, 1)
        ii.append(i)
        jj.append(j)
        off[n + 1] = off[n] + len(i)
    return np.concatenate(ii), np.concatenate(jj), off


def candidate_pairs(blocking, budget: int, sample_seed: int):
    """(a, b, src_size, exact, total_slots), pairs sorted by (a, b)."""
    start, size, members = blocks(blocking)
    per = size * (size - 1) // 2
    total = int(per.sum())
    first = np.r_[0, np.cumsum(per)]
    if total <= budget:
        slots = np.arange(total, dtype=np.int64)
    else:
        slots = sample_slots(total, budget, sample_seed)
    blk = np.searchsorted(first, slots, side="right") - 1
    n_of = size[blk]
    ti, tj, off = _triangles(int(size.max(initial=0)))
    t = off[n_of] + slots - first[blk]
    a = members[start[blk] + ti[t]]
    b = members[start[blk] + tj[t]]
    pair = (a.astype(np.uint64) << np.uint64(32)) | b.astype(np.uint64)
    order = np.argsort(pair)
    pair, src = pair[order], n_of[order].astype(np.int64)
    first_of = np.flatnonzero(run_starts(pair))
    src = np.maximum.reduceat(src, first_of) if len(src) else src
    pair = pair[first_of]
    return ((pair >> np.uint64(32)).astype(np.int64),
            (pair & np.uint64(0xFFFFFFFF)).astype(np.int64), src,
            total <= budget, total)


def _score(toks, masks, a, b, *, weights, threshold, dtype):
    """Match decision of pair lanes (a, b), in ``dtype``."""
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    total = jnp.zeros(a.shape, dt)
    norm = jnp.zeros(a.shape, dt)
    for tok, mask, w in zip(toks, masks, weights):
        ta, ma, tb, mb = tok[a], mask[a], tok[b], mask[b]
        held = jnp.any((ta[:, :, None] == tb[:, None, :])
                       & mb[:, None, :], axis=2) & ma
        shared = held.sum(axis=1)
        na, nb = ma.sum(axis=1), mb.sum(axis=1)
        both = (na > 0) & (nb > 0)
        jac = jnp.where(both, (shared / jnp.maximum(na + nb - shared, 1)
                               ).astype(dt), 0).astype(dt)
        total = total + jnp.asarray(w, dt) * jac
        norm = norm + jnp.where(both, jnp.asarray(w, dt), 0).astype(dt)
    s = jnp.where(norm > 0, total / jnp.maximum(norm, jnp.asarray(1e-6, dt)),
                  0).astype(dt)
    return s >= jnp.asarray(threshold, dt)


@functools.cache
def _score_jit():
    import jax

    return jax.jit(_score, static_argnames=("weights", "threshold", "dtype"))


def match(columns: dict, weights, threshold: float, a: np.ndarray,
          b: np.ndarray, dtype: str = "float32",
          chunk: int = 1 << 19) -> np.ndarray:
    """Boolean match decision per pair, scored on the default device in
    ``dtype`` (float32 as configured; bfloat16 is the control)."""
    import jax.numpy as jnp

    names = [n for n, _ in weights if n in columns]
    ws = tuple(float(w) for n, w in weights if n in columns)
    toks = [jnp.asarray(columns[n][0]) for n in names]
    masks = [jnp.asarray(columns[n][1]) for n in names]
    score = _score_jit()
    out = np.zeros(len(a), bool)
    for off in range(0, len(a), chunk):
        n = min(chunk, len(a) - off)
        pa = np.zeros(chunk, np.int32)
        pb = np.zeros(chunk, np.int32)
        pa[:n], pb[:n] = a[off:off + n], b[off:off + n]
        got = score(toks, masks, jnp.asarray(pa), jnp.asarray(pb),
                    weights=ws, threshold=float(threshold), dtype=dtype)
        out[off:off + n] = np.asarray(got)[:n]
    return out


def clusters(n: int, a: np.ndarray, b: np.ndarray):
    """(label per record, sorted survivors) of the graph's components."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    g = coo_matrix((np.ones(len(a), np.int8), (a, b)), shape=(n, n))
    _, comp = connected_components(g, directed=False)
    low = np.full(comp.max() + 1 if n else 0, n, np.int64)
    np.minimum.at(low, comp, np.arange(n))
    label = low[comp]
    return label, np.unique(label)
