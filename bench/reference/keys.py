"""Plain numpy reference of the blocking keys.

64-bit arithmetic on numpy ``uint64`` (which wraps mod 2**64), written
from the key definitions: splitmix64 mixing, identity keys as a sponge
over a column's tokens, LSH keys as a sponge over each band of MinHashes,
per-record set semantics. It shares no code with the system under test.
"""
from __future__ import annotations

import numpy as np

GAMMA = 0x9E3779B97F4A7C15
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
MASK64 = (1 << 64) - 1
SENTINEL = np.uint64(MASK64)


def u64(x) -> np.ndarray:
    return np.asarray(x).astype(np.uint64)


def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer."""
    x = u64(x)
    x = x ^ (x >> np.uint64(30))
    x = x * _M1
    x = x ^ (x >> np.uint64(27))
    x = x * _M2
    return x ^ (x >> np.uint64(31))


def hash64(x: np.ndarray, seed: int) -> np.ndarray:
    """Seeded hash: mix(x + (seed + 1) * gamma)."""
    return mix64(u64(x) + np.uint64(((seed + 1) * GAMMA) & MASK64))


def rotl(x: np.ndarray, n: int) -> np.ndarray:
    return (x << np.uint64(n)) | (x >> np.uint64(64 - n))


def combine(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Key of an intersection of two keys, ``lo < hi``."""
    h = mix64(lo) ^ rotl(u64(hi), 29)
    return mix64(h + np.uint64(GAMMA))


def fingerprint(rid: np.ndarray) -> np.ndarray:
    """64-bit membership fingerprint of a record id."""
    return hash64(np.asarray(rid).astype(np.uint32), 0xB10C)


def _sponge_step(h: np.ndarray, tok: np.ndarray) -> np.ndarray:
    return mix64((h ^ u64(tok)) + np.uint64(GAMMA))


def identity_keys(tokens: np.ndarray, mask: np.ndarray, column_seed: int):
    n, t = tokens.shape
    h = hash64(np.full(n, t, np.uint64), 0x1DE0 + column_seed)
    for k in range(t):
        tok = u64(np.where(mask[:, k], tokens[:, k], 0).astype(np.uint32))
        tok = tok + (u64(mask[:, k]) << np.uint64(31))
        h = _sponge_step(h, tok)
    return h[:, None], mask.any(axis=1)[:, None]


def minhashes(tokens: np.ndarray, mask: np.ndarray, num_hashes: int,
              seed: int = 0x3141) -> np.ndarray:
    """(N, num_hashes) uint32 MinHash values; 0xFFFFFFFF for no token."""
    tok = u64(tokens.astype(np.uint32))
    out = np.empty((tokens.shape[0], num_hashes), np.uint32)
    for i in range(num_hashes):
        add = np.uint64(((seed + 977 * i + 1) * GAMMA) & MASK64)
        lo = (mix64(tok + add) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        out[:, i] = np.where(mask, lo, np.uint32(0xFFFFFFFF)).min(axis=1)
    return out


def lsh_keys(tokens: np.ndarray, mask: np.ndarray, bands: int,
             rows_per_band: int, column_seed: int):
    n = tokens.shape[0]
    mh = minhashes(tokens, mask, bands * rows_per_band).reshape(
        n, bands, rows_per_band)
    h = hash64(np.zeros((n, bands), np.uint64), 0x15A4 + column_seed)
    for k in range(rows_per_band):
        h = _sponge_step(h, mh[:, :, k])
    h = mix64(h ^ np.arange(bands, dtype=np.uint64)[None, :])
    valid = np.broadcast_to(mask.any(axis=1)[:, None], (n, bands))
    return h, valid


def build_keys(columns: dict, blocking: dict):
    """(N, K) uint64 keys and (N, K) validity, one row per record.

    Columns are taken in name order; column ``i`` of that order seeds
    its keys with ``i``. Each row holds a set: repeated keys and
    invalid lanes are marked invalid.
    """
    keys, valid = [], []
    for seed, name in enumerate(sorted(columns)):
        tokens, mask = columns[name]
        spec = blocking[name]
        if spec["kind"] == "identity":
            k, v = identity_keys(tokens, mask, seed)
        elif spec["kind"] == "lsh":
            k, v = lsh_keys(tokens, mask, spec["bands"],
                            spec["rows_per_band"], seed)
        else:
            raise ValueError(f"unknown blocking kind {spec['kind']!r}")
        keys.append(k)
        valid.append(v)
    keys = np.concatenate(keys, axis=1)
    valid = np.concatenate(valid, axis=1) & (keys != SENTINEL)
    keys = np.where(valid, keys, SENTINEL)
    order = np.argsort(keys, axis=1, kind="stable")
    keys = np.take_along_axis(keys, order, axis=1)
    valid = np.take_along_axis(valid, order, axis=1)
    valid[:, 1:] &= keys[:, 1:] != keys[:, :-1]
    return keys, valid
