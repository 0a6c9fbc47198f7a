"""Plain numpy reference of Hashed Dynamic Blocking and of its probe walk.

Written from the algorithm (Algorithms 1-4 of arXiv:2008.08285) over flat
``(record, key)`` entry lists, with the configuration's sketch, caps and
heuristics:

- a Count-Min Sketch (depth rows, power-of-two width, bucket = low 32 bits
  of a seeded splitmix64 of the key) estimates each key's block size;
  keys estimated at most ``max_block_size`` are accepted, the others are
  kept only while ``float32(estimate) <= float32(max_similarity) *
  float32(parent size)``;
- kept keys are counted exactly; those within the size cap are accepted,
  over-sized ones with identical (membership fingerprint XOR, size) are
  duplicates of which the smallest key survives;
- each record intersects its ``max_oversize_keys`` smallest surviving
  over-sized keys (ties by key) pairwise; a record holding more than
  ``max_keys`` of them stops.

``hdb`` also returns per-level tables (sketch counts, exact counts and
survivor flags of the kept keys), which ``walk`` reads to answer probes
as a store holding these records would.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .keys import combine, fingerprint, hash64

INT32_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass
class Level:
    cms: np.ndarray        # (depth, width) int64 bucket counts
    tab_key: np.ndarray    # sorted uint64 keys of the kept entries
    tab_cnt: np.ndarray    # exact count of each
    tab_surv: np.ndarray   # survivor flag of each (over-sized ones only)


@dataclasses.dataclass
class Blocking:
    rid: np.ndarray        # accepted assignments, sorted by (key, rid)
    key: np.ndarray
    levels: list

    def members(self):
        """(unique keys, start, size) of the accepted blocks."""
        starts = np.flatnonzero(run_starts(self.key))
        sizes = np.diff(np.r_[starts, len(self.key)])
        return self.key[starts], starts, sizes


def run_starts(*cols) -> np.ndarray:
    """True where a sorted run of equal ``cols`` tuples starts."""
    first = np.zeros(len(cols[0]), bool)
    first[:1] = True
    for c in cols:
        first[1:] |= c[1:] != c[:-1]
    return first


def cms_buckets(cfg: dict, key: np.ndarray) -> np.ndarray:
    width = np.uint64(cfg["cms_width"] - 1)
    return np.stack([(hash64(key, 0xC0DE + j) & np.uint64(0xFFFFFFFF)
                      & width).astype(np.int64)
                     for j in range(cfg["cms_depth"])])


def classify(cfg: dict, est: np.ndarray, psize: np.ndarray):
    """(accepted by estimate, kept) for entries with estimate ``est``."""
    right = est <= cfg["max_block_size"]
    progress = (est.astype(np.float32)
                <= np.float32(cfg["max_similarity"]) * psize.astype(np.float32))
    return right, ~right & progress


def _lookup(sorted_keys: np.ndarray, key: np.ndarray):
    """(position, found) of each ``key`` in ``sorted_keys``."""
    if not len(sorted_keys):
        return np.zeros(key.shape, np.int64), np.zeros(key.shape, bool)
    pos = np.minimum(np.searchsorted(sorted_keys, key), len(sorted_keys) - 1)
    return pos, sorted_keys[pos] == key


def intersect(cfg: dict, rid: np.ndarray, key: np.ndarray, size: np.ndarray):
    """Algorithm 2 over surviving entries: child (rid, key, psize)."""
    order = np.lexsort((key, size, rid))
    rid, key, size = rid[order], key[order], size[order]
    starts = np.flatnonzero(run_starts(rid))
    counts = np.diff(np.r_[starts, len(rid)])
    rank = np.arange(len(rid)) - np.repeat(starts, counts)
    alive = np.repeat(counts <= cfg["max_keys"], counts)
    take = alive & (rank < cfg["max_oversize_keys"])
    rid, key, size = rid[take], key[take], size[take]
    starts = np.flatnonzero(run_starts(rid))
    counts = np.diff(np.r_[starts, len(rid)])
    out_r, out_k, out_p = [], [], []
    for m in range(2, int(counts.max(initial=0)) + 1):
        s = starts[counts == m]
        if not len(s):
            continue
        idx = s[:, None] + np.arange(m)[None, :]
        ii, jj = np.triu_indices(m, 1)
        a, b = key[idx][:, ii], key[idx][:, jj]
        out_k.append(combine(np.minimum(a, b), np.maximum(a, b)).ravel())
        out_p.append(np.minimum(size[idx][:, ii], size[idx][:, jj]).ravel())
        out_r.append(np.repeat(rid[s], len(ii)))
    if not out_r:
        z = np.zeros(0, np.int64)
        return z, np.zeros(0, np.uint64), z
    r, k, p = (np.concatenate(out_r), np.concatenate(out_k),
               np.concatenate(out_p))
    # per-record set semantics: one entry per (rid, key)
    order = np.lexsort((p, k, r))
    r, k, p = r[order], k[order], p[order]
    first = run_starts(r, k)
    return r[first], k[first], p[first]


def hdb(cfg: dict, keys: np.ndarray, valid: np.ndarray) -> Blocking:
    """Blocking of records with (N, K) uint64 ``keys`` under ``cfg``."""
    rid, kidx = np.nonzero(valid)
    key = keys[rid, kidx]
    psize = np.full(len(rid), INT32_MAX, np.int64)
    acc_r, acc_k, levels = [], [], []
    cap = cfg["max_block_size"]
    for _ in range(cfg["max_iterations"]):
        if not len(rid):
            break
        buckets = cms_buckets(cfg, key)
        cms = np.stack([np.bincount(b, minlength=cfg["cms_width"])
                        for b in buckets])
        est = np.take_along_axis(cms, buckets, axis=1).min(axis=0)
        right, keep = classify(cfg, est, psize)
        tab_key, inv, tab_cnt = np.unique(key[keep], return_inverse=True,
                                          return_counts=True)
        fp = np.zeros(len(tab_key), np.uint64)
        np.bitwise_xor.at(fp, inv, fingerprint(rid[keep]))
        over = tab_cnt > cap
        tab_surv = np.zeros(len(tab_key), bool)
        o = np.flatnonzero(over)
        o = o[np.lexsort((tab_key[o], tab_cnt[o], fp[o]))]
        dup = np.zeros(len(o), bool)
        dup[1:] = (fp[o][1:] == fp[o][:-1]) & (tab_cnt[o][1:] == tab_cnt[o][:-1])
        tab_surv[o[~dup]] = True
        levels.append(Level(cms, tab_key, tab_cnt, tab_surv))

        ent_cnt = np.zeros(len(rid), np.int64)
        ent_cnt[keep] = tab_cnt[inv]
        ent_surv = np.zeros(len(rid), bool)
        ent_surv[keep] = tab_surv[inv]
        accepted = right | (keep & (ent_cnt <= cap))
        acc_r.append(rid[accepted])
        acc_k.append(key[accepted])
        survive = keep & (ent_cnt > cap) & ent_surv
        if not survive.any():
            break
        rid, key, psize = intersect(cfg, rid[survive], key[survive],
                                    ent_cnt[survive])
    r = np.concatenate(acc_r) if acc_r else np.zeros(0, np.int64)
    k = np.concatenate(acc_k) if acc_k else np.zeros(0, np.uint64)
    order = np.lexsort((r, k))
    return Blocking(r[order], k[order], levels)


def walk(cfg: dict, blocking: Blocking, keys: np.ndarray, valid: np.ndarray,
         max_levels: int | None = None):
    """Answer probes against a store holding ``blocking``'s records.

    ``keys``/``valid`` are the probes' (Q, K) top-level keys. Returns, per
    probe, ``(candidates, block_sizes)``: the sorted distinct members of
    every accepted block the probe's walk reaches, and the sorted sizes
    of those blocks. ``max_levels`` truncates the walk (a control).
    """
    q = len(keys)
    bkey, bstart, bsize = blocking.members()
    rid, kidx = np.nonzero(valid)
    key = keys[rid, kidx]
    psize = np.full(len(rid), INT32_MAX, np.int64)
    hit_probe, hit_block = [], []
    cap = cfg["max_block_size"]
    n_levels = cfg["max_iterations"] if max_levels is None else max_levels
    for lev in range(n_levels):
        if not len(rid) or lev >= len(blocking.levels):
            break
        level = blocking.levels[lev]
        est = np.take_along_axis(level.cms, cms_buckets(cfg, key),
                                 axis=1).min(axis=0)
        right, keep = classify(cfg, est, psize)
        pos, found = _lookup(level.tab_key, key)
        if len(level.tab_key):
            cnt = np.where(found, level.tab_cnt[pos], 0)
            surv = found & level.tab_surv[pos]
        else:
            cnt, surv = np.zeros(len(key), np.int64), found
        accept = right | (keep & (cnt <= cap))
        bpos, bfound = _lookup(bkey, key)
        hit = accept & bfound
        hit_probe.append(rid[hit])
        hit_block.append(bpos[hit])
        survive = keep & (cnt > cap) & surv
        if not survive.any():
            break
        rid, key, psize = intersect(cfg, rid[survive], key[survive],
                                    cnt[survive])
    hp = np.concatenate(hit_probe) if hit_probe else np.zeros(0, np.int64)
    hb = np.concatenate(hit_block) if hit_block else np.zeros(0, np.int64)
    order = np.argsort(hp, kind="stable")
    hp, hb = hp[order], hb[order]
    bounds = np.searchsorted(hp, np.arange(q + 1))
    out = []
    for p in range(q):
        blocks = hb[bounds[p]:bounds[p + 1]]
        if len(blocks):
            mem = np.concatenate([blocking.rid[bstart[b]:bstart[b] + bsize[b]]
                                  for b in blocks])
            out.append((np.unique(mem), np.sort(bsize[blocks])))
        else:
            out.append((np.zeros(0, np.int64), np.zeros(0, np.int64)))
    return out

