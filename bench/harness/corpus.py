"""Seeded person-registry corpora with planted duplicates, shaped as the
Febrl data set generator's.

A record holds the configuration's fields (Febrl's ten: names, address,
date of birth, social security id), each a short list of hashed tokens
drawn from a bounded Zipf distribution over the field's cardinality, and
possibly missing. A share of the records are originals; every other
record duplicates one of them. Originals are taken in a seeded order and
each is given 1 to ``max_dups`` duplicates (bounded Zipf) until the
duplicates fill the count, so every seed yields exactly the requested
number of records and no Python loop runs per record. A duplicate
modifies 1 to ``max_modified_fields`` of its original's fields: the
field goes missing, or one of its tokens is mistyped (a fresh token that
no other record holds).
"""
from __future__ import annotations

import numpy as np

from ..reference.keys import MASK64

_SPLIT_GAMMA = 0x9E3779B97F4A7C15
_TYPO_NAMESPACE = 1000


def token_hash(ids: np.ndarray, namespace: int) -> np.ndarray:
    """Stable uint32 token per vocabulary id (splitmix64, low 32 bits)."""
    x = ids.astype(np.uint64) + np.uint64((namespace * _SPLIT_GAMMA) & MASK64)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return (x & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def bounded_zipf(rng, shape, card: int, a: float) -> np.ndarray:
    """Ids in ``[0, card)`` with P(k) proportional to (k + 1) ** -a
    (uniform for ``a == 0``)."""
    if a == 0:
        return rng.integers(0, card, shape)
    cdf = np.cumsum(np.arange(1, card + 1, dtype=np.float64) ** -a)
    return np.minimum(np.searchsorted(cdf, rng.random(shape) * cdf[-1],
                                      side="right"), card - 1)


def originals(rng, gen: dict, count: int) -> dict:
    """``count`` fresh records: ``{field: (tokens (n, T) uint32, mask)}``."""
    out = {}
    for ns, name in enumerate(sorted(gen["fields"]), start=1):
        f = gen["fields"][name]
        lo, hi = f["tokens"]
        n_tok = rng.integers(lo, hi + 1, count)
        present = rng.random(count) < f["present"]
        tok = token_hash(bounded_zipf(rng, (count, hi), f["card"],
                                      f["zipf_a"]), ns)
        out[name] = (tok, (np.arange(hi)[None, :] < n_tok[:, None])
                     & present[:, None])
    return out


def modify(rng, gen: dict, columns: dict) -> dict:
    """Duplicates of the given records: each changes 1 to
    ``max_modified_fields`` distinct fields, each change a missing value
    (``blank_share``) or one mistyped token."""
    names = sorted(columns)
    n = len(columns[names[0]][0])
    count = rng.integers(1, gen["max_modified_fields"] + 1, n)
    rank = np.argsort(np.argsort(rng.random((n, len(names))), axis=1), axis=1)
    chosen = rank < count[:, None]
    out = {}
    for ns, name in enumerate(names, start=1):
        tok, mask = columns[name][0].copy(), columns[name][1].copy()
        rows = np.flatnonzero(chosen[:, ns - 1])
        blank = rng.random(len(rows)) < gen["blank_share"]
        mask[rows[blank]] = False
        typo = rows[~blank]
        pos = np.argmax(mask[typo] * rng.random((len(typo), mask.shape[1])),
                        axis=1)
        tok[typo, pos] = np.where(
            mask[typo, pos],
            token_hash(rng.integers(0, 1 << 62, len(typo)),
                       _TYPO_NAMESPACE + ns), tok[typo, pos])
        out[name] = (tok, mask)
    return out


def generate(gen: dict, num_records: int, seed):
    """``({field: (tokens (N, T) uint32, mask (N, T) bool)}, entity_id)``
    with exactly ``num_records`` records.

    ``gen`` holds the distribution parameters of a configuration file;
    ``seed`` is anything ``numpy.random.default_rng`` takes.
    """
    rng = np.random.default_rng(seed)
    n_orig = int(round(num_records * gen["originals_share"]))
    n_dup = num_records - n_orig
    order = rng.permutation(n_orig)
    dups = 1 + bounded_zipf(rng, n_orig, gen["max_dups"], gen["dups_zipf_a"])
    total = np.cumsum(dups)
    if n_dup and total[-1] < n_dup:
        raise ValueError("the originals cannot hold the duplicates: raise "
                         "max_dups or originals_share")
    last = int(np.searchsorted(total, n_dup)) if n_dup else -1
    dups[last + 1:] = 0
    if n_dup:
        dups[last] -= total[last] - n_dup
    copies = np.ones(n_orig, np.int64)
    copies[order] += dups

    base = originals(rng, gen, n_orig)
    src = np.repeat(np.arange(n_orig), copies)
    rows = {k: (t[src], m[src]) for k, (t, m) in base.items()}
    is_dup = np.arange(len(src)) - np.repeat(np.cumsum(copies) - copies,
                                             copies) > 0
    d = np.flatnonzero(is_dup)
    changed = modify(rng, gen, take_rows(rows, d))
    for k, (t, m) in changed.items():
        rows[k][0][d], rows[k][1][d] = t, m

    perm = rng.permutation(num_records)
    return take_rows(rows, perm), src[perm]


def records(cfg: dict, seed: int):
    """A run's records under configuration ``cfg``: ``(columns, entity_id)``.

    Every run holds the records drawn from the configuration's
    ``content_seed``, in an order drawn from ``seed``, so that every seed
    does the same work.
    """
    columns, entity = generate(cfg["generator"], cfg["records"],
                               [cfg["content_seed"], 0])
    order = np.random.default_rng([seed, 4]).permutation(cfg["records"])
    return take_rows(columns, order), entity[order]


def take_rows(columns: dict, rows: np.ndarray) -> dict:
    return {k: (t[rows], m[rows]) for k, (t, m) in columns.items()}
