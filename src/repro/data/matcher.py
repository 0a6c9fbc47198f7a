"""Pairwise matching — stage 3 of the 4-stage dedup pipeline (paper §1).

The paper treats pairwise matching as downstream of blocking (their
production system uses a trained model [6]; their evaluation uses a
pre-trained "oracle"). Here the oracle is a weighted token-overlap scorer
over the same padded token columns used for blocking: it is vectorized
over candidate pairs in JAX and is deliberately much more expensive per
pair than blocking — preserving the economics that make blocking matter.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.blocks import TokenColumn
from ..kernels.match import ops as match_ops

# fused-match backend knob: "host" is the score-on-host parity baseline,
# "jnp"/"pallas" keep the matched pair set on device (kernels/match);
# "auto" resolves to the jnp mirror on every platform until a chip
# measurement shows the kernel ahead (the pairs "auto" policy)
MATCH_BACKENDS = ("auto", "host", "jnp", "pallas")


def resolve_match_backend(backend: str) -> str:
    if backend not in MATCH_BACKENDS:
        raise ValueError(
            f"match_backend {backend!r} not in {MATCH_BACKENDS}")
    return "jnp" if backend == "auto" else backend


@dataclasses.dataclass(frozen=True)
class MatcherConfig:
    threshold: float = 0.65
    # per-column weights; text columns dominate, scalar agreement helps
    weights: tuple = (("name", 0.4), ("description", 0.3), ("brand", 0.1),
                      ("category", 0.05), ("model_no", 0.15))


def _pair_jaccard(tok: jnp.ndarray, mask: jnp.ndarray, a: jnp.ndarray,
                  b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jaccard of padded token sets for record index pairs (a, b).

    Returns ``(jaccard, present)``. Single-sourced from the fused match
    kernel package so host scoring and the on-device fused path share
    one float op sequence (the bit-identity contract, docs/PIPELINE.md).
    """
    return match_ops.pair_jaccard_jnp(tok, mask, a, b)


@functools.partial(jax.jit, static_argnames=("bucket",))
def _gather_bucket(x: jnp.ndarray, start: jnp.ndarray, *,
                   bucket: int) -> jnp.ndarray:
    """Device-side bucket slice by clamped gather: one compile per bucket
    size (bounded), any start offset, no implicit transfers."""
    idx = start + jnp.arange(bucket, dtype=jnp.int32)
    idx = jnp.clip(idx, 0, x.shape[0] - 1)
    return x[idx].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("weights",))
def _score_batch(tokens, masks, weights, a, b):
    # weights is a static tuple of python floats: traced scalars would be
    # one implicit host->device upload apiece (repro.analysis R001).
    # Delegates to the kernel package's mirror — one scoring source.
    return match_ops.score_lanes_jnp(tokens, masks, weights, a, b)


def _schema(columns: Dict[str, TokenColumn], cfg: MatcherConfig):
    """Config-ordered (tokens, masks, weights) for the columns present."""
    names = [n for n, _ in cfg.weights if n in columns]
    tokens = tuple(columns[n].tokens for n in names)
    masks = tuple(columns[n].mask for n in names)
    weights = tuple(w for n, w in cfg.weights if n in columns)
    return tokens, masks, weights


def score_pairs(columns: Dict[str, TokenColumn], a, b,
                cfg: MatcherConfig = MatcherConfig(),
                batch: int = 65536) -> np.ndarray:
    """Similarity in [0,1] for each candidate pair.

    ``a``/``b`` may be host numpy arrays OR device jax arrays — e.g. the
    pair engine's ``PairSet.pair_buffers()`` or a streaming ingest's new
    pair buffer. Device inputs are sliced device-side (no forced host
    copy of the full pair list); only the scores come back to the host.
    Slices are padded to power-of-two buckets (capped at ``batch``) so a
    long-running service compiles a bounded set of kernels per column
    schema instead of one per pair-count.
    """
    tokens, masks, weights = _schema(columns, cfg)
    n_pairs = int(a.shape[0])
    out = np.empty(n_pairs, np.float32)
    on_device = isinstance(a, jax.Array)
    for off in range(0, n_pairs, batch):
        sl = slice(off, min(off + batch, n_pairs))
        m = sl.stop - sl.start
        bucket = 256
        while bucket < m:
            bucket *= 2
        bucket = min(bucket, batch)
        if on_device:
            # device inputs stay device-side: a jitted clamped gather
            # slices the bucket (eager slicing/padding would be implicit
            # transfers — repro.analysis R001); pad lanes replicate the
            # tail element and are discarded by the [:m] crop below
            start = jax.device_put(np.int32(off))
            aa = _gather_bucket(a, start, bucket=bucket)
            bb = _gather_bucket(b, start, bucket=bucket)
        else:
            pad = (0, bucket - m)
            aa = jnp.asarray(np.pad(np.asarray(a[sl], np.int32), pad))
            bb = jnp.asarray(np.pad(np.asarray(b[sl], np.int32), pad))
        got = _score_batch(tokens, masks, weights, aa, bb)
        out[sl] = np.asarray(got)[:m]
    return out


def match_pairs(columns, a, b, cfg: MatcherConfig = MatcherConfig()) -> np.ndarray:
    """Boolean match decision per candidate pair (host parity baseline).

    Compares in float32: a bare python-float threshold would promote the
    numpy comparison to f64 and could flip pairs that sit exactly on the
    threshold relative to the device paths (which compare in f32).
    """
    return score_pairs(columns, a, b, cfg) >= np.float32(cfg.threshold)


def match_compact(columns: Dict[str, TokenColumn], a, b,
                  cfg: MatcherConfig = MatcherConfig(), *,
                  backend: str = "auto",
                  chunk: int = match_ops.DEFAULT_CHUNK):
    """Fused on-device match: score + threshold + compaction, no host hop.

    ``a``/``b`` are the candidate pair list — device buffers
    (``PairSet.pair_buffers()``, a streaming ingest's pair buffer) stay
    on device; host numpy is pre-cast and uploaded explicitly once.
    Returns device ``(ca, cb, count)``: the first ``count`` lanes of
    ``ca``/``cb`` are the matched pairs in candidate order — the device
    limb form of the packed ``a<<32|b`` ledger words
    (``kernels.match.packed_host`` reassembles them) — and the tail is
    (0, 0) padding that feeds straight into ``cluster_pairs_device`` as
    frontier no-ops. Backend "pallas" runs the fused Pallas kernel
    (interpreted on the CPU backend only), "jnp"/"auto" the XLA mirror;
    both are bit-identical to ``match_pairs``.
    """
    resolved = resolve_match_backend(backend)
    if resolved == "host":
        raise ValueError("match_compact is the device path; use "
                         "match_pairs for the host baseline")
    tokens, masks, weights = _schema(columns, cfg)
    n_real = int(a.shape[0])
    if not isinstance(a, jax.Array):
        # pre-cast host-side then upload explicitly: dtype-coercing
        # jnp.asarray is an implicit transfer (repro.analysis R001)
        a = jnp.asarray(np.asarray(a, np.int32))
        b = jnp.asarray(np.asarray(b, np.int32))
    return match_ops.fused_match_pairs(
        tokens, masks, weights, a, b, threshold=cfg.threshold,
        n_real=n_real, chunk=chunk, use_kernel=(resolved == "pallas"))


def match_compact_sharded(columns: Dict[str, TokenColumn], a: np.ndarray,
                          b: np.ndarray, cfg: MatcherConfig, mesh, *,
                          backend: str = "auto",
                          chunk: int = match_ops.DEFAULT_CHUNK):
    """``match_compact`` over every device of ``mesh``: each matches an
    equal slice of the host candidate pairs ``a``/``b`` against its own
    replica of the columns (replicated here). Returns device ``(ca, cb,
    counts)``: ``ca``/``cb`` split over the devices, each device's slice
    laid out as ``match_compact``'s output (its matched pairs first, in
    candidate order, then (0, 0) padding), and ``counts`` each device's
    matched count. One program for the mesh, whatever its size."""
    resolved = resolve_match_backend(backend)
    if resolved == "host":
        raise ValueError("match_compact_sharded is the device path")
    tokens, masks, weights = _schema(columns, cfg)
    n_dev = mesh.devices.size
    per = -(-len(a) // n_dev)
    chunk = max(match_ops.CHUNK_QUANTUM,
                min(chunk, match_ops.round_up(max(per, 1),
                                              match_ops.CHUNK_QUANTUM)))
    per = match_ops.round_up(max(per, 1), chunk)
    n_real = np.clip(len(a) - per * np.arange(n_dev), 0, per).astype(np.int32)
    split = NamedSharding(mesh, P(mesh.axis_names))
    everywhere = NamedSharding(mesh, P())

    def lanes(x):
        out = np.zeros((n_dev * per,), np.int32)
        out[:len(x)] = x
        return jax.device_put(out, split)

    step = _sharded_match(mesh, per, chunk, weights, float(cfg.threshold),
                          resolved == "pallas")
    return step(jax.device_put(tokens, everywhere),
                jax.device_put(masks, everywhere), lanes(a), lanes(b),
                jax.device_put(n_real, split))


@functools.lru_cache(maxsize=16)
def _sharded_match(mesh, per: int, chunk: int, weights: tuple,
                   threshold: float, use_kernel: bool):
    """The shard-mapped fused match of ``match_compact_sharded``: the
    chunks of a device's ``per`` lanes in one ``lax.map``, then one
    compaction."""
    axes = mesh.axis_names

    def local(tokens, masks, a, b, n_real):
        def one(base):
            return match_ops._match_chunk(
                tokens, masks, a, b, base, n_real[0], chunk=chunk,
                weights=weights, threshold=threshold, use_kernel=use_kernel)

        parts = jax.lax.map(one, jnp.arange(0, per, chunk, dtype=jnp.int32))
        ca, cb, count = match_ops.compact_matched(
            *(x.reshape(-1) for x in parts))
        return ca, cb, count[None]

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(), P(axes), P(axes), P(axes)),
        out_specs=(P(axes), P(axes), P(axes)), check_vma=False))
