"""End-to-end dedup pipeline: the paper's 4 stages feeding LM training.

    normalize -> BLOCK (HDB, the paper's contribution) -> pairwise match
    -> graph partition -> canonical records -> token stream -> batches

``dedup_corpus`` runs stages 2-4 batch-mode and returns one surviving
record per entity-component. Where the corpus' columns are placed over a
mesh of several devices (``jax.Array``s whose ``NamedSharding`` splits
the record axis), the job runs sharded: keys stay on their records'
devices, HDB and the pair dedupe are ``core.distributed``'s routed
dataflow, every device matches a slice of the candidate pairs, and one
device clusters; the answer is the one-device job's (docs/PIPELINE.md,
"The sharded job"). ``DedupPipeline`` is the streaming-consistent form:
it holds a persistent ``streaming.BlockStore`` so ``extend(delta)``
absorbs new records incrementally — blocking work proportional to the
delta, matching only the new candidate pairs (scored from the device pair
buffer), retraction-aware — and exposes the current survivors for the
training-batch stream (see loader.py).

Both run the back half (match -> filter -> cluster) behind a
``match_backend`` knob: "host" is the original score-on-host parity
baseline; "jnp"/"pallas" (and "auto") route through the fused
``kernels/match`` + ``cluster_pairs_device`` path, where the pair list
never crosses to the host — only final labels/survivors do. The two
paths are bit-identical (docs/PIPELINE.md).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import blocks as blocks_mod
from ..core import distributed
from ..core import hdb as hdb_mod
from ..core import pairs as pairs_mod
from ..distributed import sharding as sharding_lib
from .. import obs
from . import components, matcher
from .synthetic import Corpus


def _sync(*vals) -> None:
    """Block on device work so ``perf_counter`` windows attribute stage
    time to the stage that did the work (the repro.analysis R004 hazard:
    async dispatch bleeds matching time into partition time)."""
    for v in vals:
        for leaf in jax.tree_util.tree_leaves(v):
            if isinstance(leaf, jax.Array):
                leaf.block_until_ready()


@dataclasses.dataclass
class DedupReport:
    num_records: int
    num_candidate_pairs: int
    num_matched_pairs: int
    num_components: int
    num_survivors: int
    blocking_seconds: float
    matching_seconds: float
    partition_seconds: float
    survivors: np.ndarray       # (S,) record ids, one per component
    component_of: np.ndarray    # (N,) component label per record
    # batch runs only: the blocking stage's blocks and candidate pairs,
    # for callers that check them against a reference
    blocks: Optional[pairs_mod.Blocks] = None
    pairs: Optional[pairs_mod.PairSet] = None
    # the devices the records were sharded over (1: the one-device job);
    # HDB entries dropped for a fixed buffer's capacity (on one device
    # only the representative buffer can overflow); routed pair dedupes
    # that fell back to the single-device engine; and the sharded job's
    # exchanges (``core.distributed.Exchange``), HDB's then the pairs'
    shards: int = 1
    route_overflows: int = 0
    pair_fallbacks: int = 0
    exchanges: list = dataclasses.field(default_factory=list)


def record_mesh(columns: Dict[str, blocks_mod.TokenColumn]):
    """``(mesh, record axes)`` where the columns' records are split over
    several devices of a mesh, else None (the one-device job)."""
    sharding = getattr(next(iter(columns.values())).tokens, "sharding", None)
    if not isinstance(sharding, NamedSharding) or not sharding.spec:
        return None
    axes = sharding.spec[0]
    if axes is None:
        return None
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if sharding_lib.axis_size(sharding.mesh, axes) < 2:
        return None
    return sharding.mesh, axes


def dedup_corpus(corpus: Corpus,
                 cfg: hdb_mod.HDBConfig = hdb_mod.HDBConfig(max_block_size=100),
                 match_cfg: matcher.MatcherConfig = matcher.MatcherConfig(),
                 pair_budget: int = 20_000_000,
                 blocker: str = "hdb",
                 verbose: bool = False,
                 match_backend: str = "auto",
                 cc_max_rounds: int = 64) -> DedupReport:
    placed = record_mesh(corpus.columns)
    if placed is not None:
        return _dedup_sharded(corpus, *placed, cfg, match_cfg, pair_budget,
                              blocker, verbose, match_backend, cc_max_rounds)
    n = corpus.num_records
    with obs.span("repro.pipeline.blocking") as blocking:
        with obs.span("repro.pipeline.keys"):
            keys, valid = blocks_mod.build_keys(corpus.columns,
                                                corpus.blocking)
        if blocker == "hdb":
            result = hdb_mod.hashed_dynamic_blocking(keys, valid, cfg,
                                                     verbose=verbose)
        elif blocker == "threshold":
            from ..core.baselines import threshold_blocking
            result = threshold_blocking(keys, valid, cfg.max_block_size)
        else:
            raise ValueError(blocker)
        with obs.span("repro.pairs.blocks"):
            blk = pairs_mod.build_blocks(result)
        pset = pairs_mod.dedupe_pairs(blk, budget=pair_budget)
        # feed the matcher the device pair buffer directly (no host round
        # trip of the pair list when the device dedupe path produced it)
        _sync(pset.pair_buffers())
    num_matched, label, survivors, matching_s, partition_s = \
        match_and_cluster(corpus, pset, match_cfg, match_backend,
                          cc_max_rounds)
    return DedupReport(
        num_records=n,
        num_candidate_pairs=len(pset.a),
        num_matched_pairs=num_matched,
        num_components=len(survivors),
        num_survivors=len(survivors),
        blocking_seconds=blocking.seconds,
        matching_seconds=matching_s,
        partition_seconds=partition_s,
        survivors=survivors,
        component_of=label,
        blocks=blk,
        pairs=pset,
        route_overflows=result.rep_overflow_total,
    )


def _dedup_sharded(corpus: Corpus, mesh, axes, cfg, match_cfg, pair_budget,
                   blocker, verbose, match_backend, cc_max_rounds
                   ) -> DedupReport:
    """``dedup_corpus`` over columns whose records are split over
    ``mesh``'s ``axes``: the same answer, from the routed dataflow."""
    if blocker != "hdb":
        raise ValueError(f"the sharded job blocks with HDB only, not "
                         f"{blocker!r}")
    backend = matcher.resolve_match_backend(match_backend)
    if backend == "host":
        raise ValueError("the sharded job matches on its devices; "
                         "match_backend='host' is the one-device baseline")
    n = corpus.num_records
    with obs.span("repro.pipeline.blocking") as blocking:
        with obs.span("repro.pipeline.keys"):
            keys, valid = blocks_mod.build_keys(corpus.columns,
                                                corpus.blocking)
        result = distributed.distributed_hashed_dynamic_blocking(
            keys, valid, cfg, mesh, axes, verbose=verbose)
        with obs.span("repro.pairs.blocks"):
            blk = pairs_mod.build_blocks(result)
        pset = distributed.dedupe_pairs_distributed(blk, mesh, axes,
                                                    budget=pair_budget)
    with obs.span("repro.pipeline.match") as matching:
        with obs.span("repro.dist.match"):
            ca, cb, counts = matcher.match_compact_sharded(
                corpus.columns, pset.a, pset.b, match_cfg, mesh,
                backend=backend)
            _sync(ca, cb, counts)
    with obs.span("repro.pipeline.partition") as partition:
        with obs.span("repro.dist.gather"):
            ca, cb, num_matched = _gather_matched(mesh, ca, cb, counts,
                                                  mesh.devices.flat[0])
        label, survivors = _cluster(n, ca, cb, cc_max_rounds)
    return DedupReport(
        num_records=n,
        num_candidate_pairs=len(pset.a),
        num_matched_pairs=num_matched,
        num_components=len(survivors),
        num_survivors=len(survivors),
        blocking_seconds=blocking.seconds,
        matching_seconds=matching.seconds,
        partition_seconds=partition.seconds,
        survivors=survivors,
        component_of=label,
        blocks=blk,
        pairs=pset,
        shards=sharding_lib.axis_size(mesh, axes),
        route_overflows=result.rep_overflow_total,
        pair_fallbacks=int(pset.fallback is not None),
        exchanges=result.exchanges + pset.exchanges,
    )


@functools.lru_cache(maxsize=16)
def _heads(mesh, size: int):
    """Shard-mapped: the first ``size`` lanes of each device's slice,
    zero-padded past its end."""
    axes = mesh.axis_names

    def local(ca, cb):
        m = min(size, ca.shape[0])
        return tuple(jnp.zeros((size,), x.dtype).at[:m].set(x[:m])
                     for x in (ca, cb))

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(axes),) * 2,
                                 out_specs=(P(axes),) * 2, check_vma=False))


def _gather_matched(mesh, ca, cb, counts, device):
    """The devices' matched pairs on ``device``, as one buffer
    ``cluster_pairs_device`` reads: each device's compacted head, cut to
    a power-of-two bucket over the largest count. Zero lanes are (0, 0)
    self-edges, which clustering ignores. Returns ``(ca, cb, matched)``."""
    counts = np.asarray(counts)
    ha, hb = _heads(mesh, components.pow2_cap(int(counts.max())))(ca, cb)
    return (jax.device_put(ha, device), jax.device_put(hb, device),
            int(counts.sum()))


def _cluster(n: int, ca, cb, cc_max_rounds: int):
    """Connected components of a device matched-pair buffer: host
    ``(label, survivors)``, under the ``repro.components.cc`` span."""
    with obs.span("repro.components.cc"):
        label_d, surv_d, n_surv, converged, rounds = \
            components.cluster_pairs_device(n, ca, cb,
                                            max_rounds=cc_max_rounds)
        _sync(label_d, surv_d)
    converged, rounds = jax.device_get((converged, rounds))
    obs.mark("repro.components.cc.counts", rounds=int(rounds),
             converged=bool(converged))
    if not converged:
        components._warn_truncated(cc_max_rounds)
    label = np.asarray(label_d)[:n].astype(np.int64)
    survivors = np.asarray(surv_d)[:int(np.asarray(n_surv))].astype(np.int64)
    return label, survivors


def match_and_cluster(corpus: Corpus, pset: pairs_mod.PairSet,
                      match_cfg: matcher.MatcherConfig = matcher.MatcherConfig(),
                      match_backend: str = "auto", cc_max_rounds: int = 64):
    """The batch back half: match the candidate pairs, then cluster.

    Returns ``(num_matched, component_of, survivors, matching_seconds,
    partition_seconds)``; the two times are those of the
    ``repro.pipeline.match`` and ``repro.pipeline.partition`` spans.
    """
    n = corpus.num_records
    with obs.span("repro.pipeline.match") as matching:
        backend = ("host" if match_backend == "host"
                   else matcher.resolve_match_backend(match_backend))
        dev_a, dev_b = pset.pair_buffers()
        if backend == "host":
            # parity baseline: scores + matched mask land host-side, the
            # matched pair list is gathered in numpy and re-uploaded for CC
            matched = matcher.match_pairs(corpus.columns, dev_a, dev_b,
                                          match_cfg)
            ma, mb = pset.a[matched], pset.b[matched]
            num_matched = int(matched.sum())
        else:
            # fused path: matched pairs stay device-resident end to end —
            # the compacted (0,0)-padded buffer flows straight into CC and
            # only labels/survivors/counters ever cross to the host
            with obs.span("repro.match.compact"):
                ca, cb, cnt = matcher.match_compact(
                    corpus.columns, dev_a, dev_b, match_cfg, backend=backend)
            _sync(ca, cb, cnt)
    with obs.span("repro.pipeline.partition") as partition:
        if backend == "host":
            label = components.connected_components(
                n, ma, mb, max_rounds=cc_max_rounds)
            # canonical survivor = min record id per component == the label
            survivors = np.unique(label)
        else:
            label, survivors = _cluster(n, ca, cb, cc_max_rounds)
            num_matched = int(np.asarray(cnt))
    return (num_matched, label, survivors, matching.seconds,
            partition.seconds)


class DedupPipeline:
    """Incremental dedup: persistent blocking state + delta matching.

    ``extend(corpus_delta)`` ingests a record delta through the streaming
    blocker (exact-incremental HDB over the union), scores ONLY the new
    candidate pairs with the matcher — reading the pair buffer directly —
    drops matches whose candidate pair was retracted, and re-partitions.
    The returned ``DedupReport`` always describes the full union.
    """

    def __init__(self, cfg: hdb_mod.HDBConfig = hdb_mod.HDBConfig(max_block_size=100),
                 match_cfg: matcher.MatcherConfig = matcher.MatcherConfig(),
                 match_backend: str = "auto",
                 cc_max_rounds: int = 64):
        from ..streaming import BlockStore, DeltaBlocker  # local: optional dep cycle
        from ..streaming.engine import ColumnCache
        self.cfg = cfg
        self.match_cfg = match_cfg
        self.match_backend = ("host" if match_backend == "host"
                              else matcher.resolve_match_backend(match_backend))
        self.cc_max_rounds = cc_max_rounds
        self.store = BlockStore(cfg)
        self.blocker = DeltaBlocker(self.store)
        self.blocking: Optional[Dict[str, blocks_mod.ColumnBlocking]] = None
        self._columns = ColumnCache()
        # matched pairs as packed a<<32|b, sorted
        self._matched = np.zeros((0,), np.uint64)

    def extend(self, corpus_delta: Corpus) -> DedupReport:
        from ..kernels.match import packed_host
        from ..streaming.store import pack_pair, searchsorted_mask, unpack_pair
        t0 = time.perf_counter()
        if self.blocking is None:
            self.blocking = corpus_delta.blocking
        self._columns.append({name: (np.asarray(col.tokens),
                                     np.asarray(col.mask))
                              for name, col in corpus_delta.columns.items()})
        keys, valid = blocks_mod.build_keys(corpus_delta.columns, self.blocking)
        report = self.blocker.ingest_keys(np.asarray(keys), np.asarray(valid))
        # ingest returns host arrays, so device work is already drained
        # here; the explicit barrier keeps the stage windows honest if
        # that ever changes (repro.analysis R004)
        _sync(report)
        t1 = time.perf_counter()
        a, b, _ = report.pairs_added
        ra, rb = report.pairs_retracted
        if len(ra):
            # retraction against the packed ledger: blocks dissolved by
            # this delta withdraw their pairs before the union re-forms
            pos, hit = searchsorted_mask(self._matched, pack_pair(ra, rb))
            keep = np.ones(len(self._matched), bool)
            keep[pos[hit]] = False
            self._matched = self._matched[keep]
        if len(a):
            cols = self._columns.columns()
            if self.match_backend == "host":
                # pre-cast host-side then upload explicitly: dtype-coercing
                # jnp.asarray is an implicit transfer (repro.analysis R001)
                matched = matcher.match_pairs(
                    cols, jnp.asarray(np.asarray(a, np.int32)),
                    jnp.asarray(np.asarray(b, np.int32)), self.match_cfg)
                new = pack_pair(a[matched], b[matched])
            else:
                # fused delta match: score+threshold+compact on device,
                # pull only the packed matched words for the ledger
                ca, cb, cnt = matcher.match_compact(
                    cols, a, b, self.match_cfg, backend=self.match_backend)
                _sync(ca, cb, cnt)
                new = packed_host(ca, cb, int(np.asarray(cnt)))
            self._matched = np.union1d(self._matched, new)
        t2 = time.perf_counter()
        n = self.store.num_records
        ma, mb = unpack_pair(self._matched)
        if self.match_backend == "host":
            label = components.connected_components(
                n, ma, mb, max_rounds=self.cc_max_rounds)
            survivors = np.unique(label)
        else:
            # pow-2 bucketed device CC: bounded compiles as the union grows
            cres = components.cluster_edges(
                n, ma, mb, max_rounds=self.cc_max_rounds)
            label, survivors = cres.label, cres.survivors
        t3 = time.perf_counter()
        return DedupReport(
            num_records=n,
            num_candidate_pairs=len(self.store.led_pack),
            num_matched_pairs=len(self._matched),
            num_components=len(survivors),
            num_survivors=len(survivors),
            blocking_seconds=t1 - t0,
            matching_seconds=t2 - t1,
            partition_seconds=t3 - t2,
            survivors=survivors,
            component_of=label,
        )


def dedup_quality(report: DedupReport, corpus: Corpus) -> dict:
    """Cluster-level quality vs ground truth entity ids."""
    # pairwise precision/recall of the final components on the labeled pairs
    la, lb = corpus.labeled_pairs()
    same_comp = report.component_of[la] == report.component_of[lb]
    recall = float(same_comp.mean()) if len(la) else 0.0
    # sampled precision: pairs within components
    rng = np.random.default_rng(0)
    order = np.argsort(report.component_of, kind="stable")
    lab = report.component_of[order]
    starts = np.flatnonzero(np.concatenate([[True], lab[1:] != lab[:-1]]))
    sizes = np.diff(np.concatenate([starts, [len(lab)]]))
    multi = np.flatnonzero(sizes >= 2)
    correct = total = 0
    for ci in multi[:20000]:
        s, m = starts[ci], sizes[ci]
        mem = order[s : s + m]
        if m > 12:
            mem = rng.choice(mem, 12, replace=False)
        ii, jj = np.triu_indices(len(mem), 1)
        correct += int((corpus.entity_id[mem[ii]] == corpus.entity_id[mem[jj]]).sum())
        total += len(ii)
    precision = correct / total if total else 1.0
    return {"pair_recall": recall, "pair_precision": precision,
            "dedup_ratio": report.num_survivors / report.num_records}
