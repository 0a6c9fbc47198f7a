"""Bring-up smoke: the entity-resolution engine's main path on a TPU.

Runs in ONE process (a chip belongs to one process) through the entry
points a user calls, and checks every result against the repo's own
references. Phases:

1. batch dedup: ``data.pipeline.dedup_corpus`` over a synthetic corpus
   of ~254k records (135k entities: a quarter of the paper's smallest
   scaling tier, ~1.0M records, whose HDB iterations took ~125 s each on
   one v5e, more than the 1200 s run limit holds), fused back half vs
   the ``match_backend="host"`` baseline, and the device ``PairSet`` vs
   the numpy reference on the same blocks;
2. kernel parity at that size: the Pallas tri-decode + radix sort and
   the fused match kernel, compiled (never interpreted) on the chip,
   against the default jnp paths;
3. stream and service: a ``DedupeService`` tenant ingests one record per
   entity of a 20k-entity corpus in a few write batches, then answers
   the rest as ``include_probe=True`` probe waves; the pair ledger must
   equal batch HDB + pair dedupe, and sampled probes answered one at a
   time must equal their batched answers;
4. ``--chips 4`` runs ONLY the sharded path: distributed HDB and the
   fingerprint-routed pair dedupe on a 4-device mesh, bit for bit
   against the single-device engine.

Any repo fallback warning (all are ``RuntimeWarning`` subclasses) is an
error. The last stdout line is the JSON contract line
``{"ok": true, "device": {...}}``; without a TPU the script prints the
platform it found and exits non-zero, unless ``--rehearse`` (tiny
sizes, for a CPU dry run) is given.

    python chip_smoke.py [--seed N]          # one chip, phases 1-3
    python chip_smoke.py --chips 4           # four chips, phase 4 only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --chips 4 --rehearse
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

FULL = dict(batch_entities=135_000, stream_entities=20_000,
            write_batches=4, probe_wave=1024, solo_probes=64)
REHEARSE = dict(batch_entities=1_500, stream_entities=300,
                write_batches=3, probe_wave=64, solo_probes=16)
MAX_BLOCK_SIZE = 100
PAIR_BUDGET = 20_000_000     # dedup_corpus's default pair budget


_T0 = time.perf_counter()


def log(msg: str) -> None:
    """One progress line, stamped with seconds since start: the driver
    sees only the end of the output, and a cut run must show where."""
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def assert_pairsets_equal(got, want, label: str) -> None:
    import numpy as np
    assert got.exact == want.exact, label
    assert got.total_slots == want.total_slots, label
    np.testing.assert_array_equal(got.a, want.a, err_msg=label)
    np.testing.assert_array_equal(got.b, want.b, err_msg=label)
    np.testing.assert_array_equal(got.src_size, want.src_size, err_msg=label)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


def block_keys(keys, valid, cfg):
    """Single-device HDB and its blocks: the sharded path's reference."""
    from repro.core import hdb, pairs
    t0 = time.perf_counter()
    res = hdb.hashed_dynamic_blocking(keys, valid, cfg)
    log(f"  hdb: {len(res.stats)} iterations, {len(res.rids)} assignments "
        f"({time.perf_counter() - t0!r}s)")
    for st in res.stats:
        log(f"  hdb iter {st.iteration}: live_keys={st.n_live_keys} "
            f"right_cms={st.n_right_cms} right_exact={st.n_right_exact} "
            f"surviving_oversized={st.n_surviving_oversized} "
            f"rep_overflow={st.rep_overflow}")
    return res, pairs.build_blocks(res)


def phase_batch(sizes, seed):
    import jax
    import numpy as np

    from repro.core import hdb, pairs
    from repro.data import matcher, pipeline, synthetic

    log("== phase 1: batch dedup")
    t0 = time.perf_counter()
    corpus = synthetic.generate(synthetic.SyntheticSpec(
        num_entities=sizes["batch_entities"], seed=seed))
    log(f"  corpus: {corpus.num_records} records, "
        f"{sizes['batch_entities']} entities "
        f"(generated in {time.perf_counter() - t0:.1f}s)")
    cfg = hdb.HDBConfig(max_block_size=MAX_BLOCK_SIZE)

    fused = pipeline.dedup_corpus(corpus, cfg, pair_budget=PAIR_BUDGET)
    blk, got = fused.blocks, fused.pairs
    log(f"  blocks={blk.num_blocks} pair_slots={blk.num_pair_slots} "
        f"budget={PAIR_BUDGET} resolved: pairs_backend="
        f"{pairs.resolve_backend('auto', blk, PAIR_BUDGET)} "
        f"sort={pairs.resolve_sort_backend('auto', blk)} "
        f"match_backend={matcher.resolve_match_backend('auto')}")
    log(f"  fused: records={fused.num_records} "
        f"candidate_pairs={fused.num_candidate_pairs} "
        f"matched_pairs={fused.num_matched_pairs} "
        f"components={fused.num_components} "
        f"blocking_s={fused.blocking_seconds!r} "
        f"matching_s={fused.matching_seconds!r} "
        f"partition_s={fused.partition_seconds!r}")
    q = pipeline.dedup_quality(fused, corpus)
    log(f"  pair_recall={q['pair_recall']!r} "
        f"pair_precision={q['pair_precision']!r} "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")

    t0 = time.perf_counter()
    want = pairs.dedupe_pairs(blk, budget=PAIR_BUDGET, backend="numpy")
    log(f"  numpy dedupe ({time.perf_counter() - t0!r}s)")
    assert_pairsets_equal(got, want, "device PairSet vs numpy")
    del want
    log("  check: device PairSet == numpy reference")

    t0 = time.perf_counter()
    n_host, label, survivors, _, _ = pipeline.match_and_cluster(
        corpus, got, match_backend="host")
    log(f"  host back half: matched_pairs={n_host} "
        f"components={len(survivors)} ({time.perf_counter() - t0!r}s)")
    assert fused.num_matched_pairs == n_host > 0
    np.testing.assert_array_equal(fused.component_of, label)
    np.testing.assert_array_equal(fused.survivors, survivors)
    log("  check: fused back half == host baseline (bit-identical)")
    return corpus, blk, got


def phase_kernels(corpus, blk, default):
    import numpy as np

    from repro.core import pairs
    from repro.data import matcher

    log("== phase 2: kernel parity (Pallas compiled on the chip)")
    got = pairs.dedupe_pairs(blk, budget=PAIR_BUDGET, backend="pallas",
                             sort_backend="radix")
    assert_pairsets_equal(got, default, "pallas+radix vs default")
    log("  check: dedupe_pairs(pallas, radix) == default path")
    a, b = default.pair_buffers()
    ref = matcher.match_compact(corpus.columns, a, b, backend="jnp")
    ker = matcher.match_compact(corpus.columns, a, b, backend="pallas")
    n_ref, n_ker = int(np.asarray(ref[2])), int(np.asarray(ker[2]))
    assert n_ker == n_ref and n_ref > 0, (n_ker, n_ref)
    for r, k in zip(ref[:2], ker[:2]):
        np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
    log(f"  check: match_compact(pallas) == jnp ({n_ref} matched of "
        f"{len(default.a)})")


def phase_service(sizes, seed):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import blocks, hdb, pairs
    from repro.data import synthetic
    from repro.serving.service import DedupeService, ServiceConfig

    log("== phase 3: stream and service")
    corpus = synthetic.generate(synthetic.SyntheticSpec(
        num_entities=sizes["stream_entities"], seed=seed + 1))
    keys, valid = blocks.build_keys(corpus.columns, corpus.blocking)
    keys, valid = np.asarray(keys), np.asarray(valid)
    _, first = np.unique(corpus.entity_id, return_index=True)
    canon = np.sort(first)
    probes = np.setdiff1d(np.arange(corpus.num_records), canon)
    cfg = hdb.HDBConfig(max_block_size=MAX_BLOCK_SIZE)
    svc = DedupeService(cfg, ServiceConfig(
        probe_slots=256, max_read_queue=sizes["probe_wave"]))
    tenant = "catalog"
    t0 = time.perf_counter()
    for part in np.array_split(canon, sizes["write_batches"]):
        svc.submit_ingest(tenant, keys[part], valid[part])
    svc.run()
    store = svc.tenant(tenant).store
    log(f"  ingested {store.num_records} records in "
        f"{sizes['write_batches']} write batches "
        f"({time.perf_counter() - t0!r}s, ledger "
        f"{len(store.led_pack)} pairs)")

    res = hdb.hashed_dynamic_blocking(jnp.asarray(keys[canon]),
                                      jnp.asarray(valid[canon]), cfg)
    blk = pairs.build_blocks(res)
    want = pairs.dedupe_pairs(blk, budget=blk.num_pair_slots + 1)
    assert_pairsets_equal(store.candidate_pairs(), want,
                          "service ledger vs batch HDB + dedupe")
    assert len(want.a) > 0
    log("  check: pair ledger == batch HDB + pair dedupe")

    row_of = {}
    t0 = time.perf_counter()
    for wave in np.array_split(
            probes, max(1, -(-len(probes) // sizes["probe_wave"]))):
        for i in wave:
            uid = svc.submit_probe(tenant, keys[i:i + 1], valid[i:i + 1],
                                   include_probe=True)
            row_of[uid] = int(i)
        svc.run()
    responses = {r.uid: r for r in svc.probe_responses}
    assert len(responses) == len(probes)
    assert all(r.status == "ok" for r in responses.values())
    hits = sum(len(r.results[0].candidates) > 0 for r in responses.values())
    snap = svc.snapshot()
    lat = snap["histograms"]["probe_latency_s"]
    log(f"  answered {len(responses)} probes in "
        f"{time.perf_counter() - t0!r}s ({hits} with candidates, "
        f"probe_batches={snap['counters']['probe_batches_total']}, "
        f"bucket_compiles={snap['counters']['bucket_compiles_total']}, "
        f"queued-wave latency p50={lat['p50']!r}s p99={lat['p99']!r}s)")

    rng = np.random.default_rng(seed)
    blocker = svc.tenant(tenant).blocker
    for uid in rng.choice(sorted(responses), sizes["solo_probes"],
                          replace=False):
        i = row_of[int(uid)]
        solo = blocker.query_keys(keys[i:i + 1], valid[i:i + 1],
                                  include_probe=True)[0]
        batched = responses[int(uid)].results[0]
        np.testing.assert_array_equal(solo.candidates, batched.candidates)
        np.testing.assert_array_equal(solo.block_sizes, batched.block_sizes)
        assert solo.n_blocks_hit == batched.n_blocks_hit
        assert solo.levels_walked == batched.levels_walked
    log(f"  check: {sizes['solo_probes']} probes answered alone == "
        "their batched answers")


def phase_mesh(sizes, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import blocks, distributed, hdb, pairs
    from repro.data import synthetic

    devices = jax.devices()
    log(f"== phase 4: sharded path on {len(devices)} devices")
    assert len(devices) == 4, "--chips 4 needs exactly four devices"
    corpus = synthetic.generate(synthetic.SyntheticSpec(
        num_entities=sizes["batch_entities"], seed=seed))
    cfg = hdb.HDBConfig(max_block_size=MAX_BLOCK_SIZE)
    keys, valid = blocks.build_keys(corpus.columns, corpus.blocking)
    pad = (-valid.shape[0]) % len(devices)
    if pad:
        # sentinel rows: no valid key, so they join no block
        keys = jnp.concatenate(
            [keys, jnp.full((pad,) + keys.shape[1:], 0xFFFFFFFF, jnp.uint32)])
        valid = jnp.concatenate([valid, jnp.zeros((pad, valid.shape[1]),
                                                  bool)])
    ref, blk = block_keys(keys, valid, cfg)
    mesh = jax.make_mesh((len(devices),), ("data",))
    axes = ("data",)
    log(f"  records={corpus.num_records} (+{pad} padding rows), "
        f"mesh axis_types={mesh.axis_types}")

    # the mesh's shards must land on all four devices, not the first
    # (same shapes as the driver's first iteration below, so it reuses
    # this compile)
    placed = jax.device_put(valid, NamedSharding(mesh, P(axes, None)))
    on = {s.device for s in placed.addressable_shards}
    assert on == set(devices), on
    step = distributed.make_hdb_step(cfg, mesh, axes)
    keys_d = jax.device_put(keys, NamedSharding(mesh, P(axes, None, None)))
    psize = jax.device_put(np.full(placed.shape, hdb.INT32_MAX, np.int32),
                           NamedSharding(mesh, P(axes, None)))
    accepted = step(keys_d, placed, psize)[0]
    on = {s.device for s in accepted.addressable_shards}
    assert on == set(devices), on
    log("  check: step inputs and outputs are sharded over all "
        f"{len(devices)} devices")

    t0 = time.perf_counter()
    got = distributed.distributed_hashed_dynamic_blocking(
        keys, valid, cfg, mesh, axes)
    log(f"  distributed HDB: {len(got.rids)} assignments "
        f"({time.perf_counter() - t0!r}s)")

    def rows(r):
        order = np.lexsort((r.key_lo, r.key_hi, r.rids))
        return np.stack([r.rids[order], r.key_hi[order].astype(np.int64),
                         r.key_lo[order].astype(np.int64)])

    np.testing.assert_array_equal(rows(got), rows(ref))
    log(f"  check: distributed HDB == single-device HDB "
        f"({len(ref.rids)} assignments, bit-identical)")

    want = pairs.dedupe_pairs(blk, budget=PAIR_BUDGET)
    t0 = time.perf_counter()
    routed = distributed.dedupe_pairs_distributed(blk, mesh, axes,
                                                  budget=PAIR_BUDGET)
    log(f"  routed dedupe: {len(routed.a)} pairs, exact={routed.exact} "
        f"({time.perf_counter() - t0!r}s)")
    assert_pairsets_equal(routed, want, "routed vs single-device dedupe")
    log("  check: routed dedupe == single-device pair engine")
    log("  peak_bytes_in_use per device: "
        + " ".join(f"{d.id}:{peak_bytes(d)}" for d in devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on four devices")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform (CPU dry run)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    log(f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 1

    from repro.runtime import enable_compilation_cache
    log(f"compilation cache: {enable_compilation_cache()}")
    # per-iteration HDB stats, timestamped (ms since start)
    logging.basicConfig(format="[%(relativeCreated)9.0fms] %(message)s")
    logging.getLogger("repro.core").setLevel(logging.DEBUG)
    # every repo fallback (pairs/distributed/streaming, RepCapacityWarning,
    # CC truncation) is a RuntimeWarning: none may pass silently here
    warnings.simplefilter("error", RuntimeWarning)
    sizes = REHEARSE if args.rehearse else FULL
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(sizes, args.seed)
    else:
        corpus, blk, default = phase_batch(sizes, args.seed)
        phase_kernels(corpus, blk, default)
        del corpus, blk, default
        phase_service(sizes, args.seed)
    log(f"all phases passed in {time.perf_counter() - t0!r}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
