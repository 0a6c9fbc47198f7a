"""Streaming ingest: delta cost vs full re-blocking, and throughput.

The acceptance workload: a >=100k-record BlockStore absorbs a 1% record
delta; the ingest (incremental HDB + delta pair materialization, i.e.
everything needed to keep the candidate-pair ledger exact) must be >=5x
faster than re-running batch ``hashed_dynamic_blocking`` + ``build_blocks``
+ ``dedupe_pairs`` on the union — the work a batch system would redo per
arrival wave. Both paths are compile-warmed first; the comparison is
steady-state wall clock on the same backend.

    PYTHONPATH=src python -m benchmarks.bench_streaming [--check] [--records N]
"""
from __future__ import annotations

import time

import numpy as np

from .common import emit, sync

from repro.core import blocks as blocks_mod
from repro.core import hdb, pairs
from repro.streaming import BlockStore, DeltaBlocker

import jax.numpy as jnp


def _make_stream_keys(rng, n, k_small=8, card_ratio=0.25, k_hot=2,
                      hot_card=24):
    """Key layout shaped like production blocking: mostly discriminative
    keys (small blocks) plus a few hot keys (over-sized -> intersections)."""
    small = rng.integers(0, max(int(n * card_ratio), 4), (n, k_small))
    hot = rng.integers(0, hot_card, (n, k_hot)) + (1 << 40)
    ids = np.concatenate([small, hot], axis=1).astype(np.uint64)
    k64 = ids * np.uint64(0x9E3779B97F4A7C15)
    keys = np.stack([(k64 >> np.uint64(32)).astype(np.uint32),
                     (k64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)], -1)
    valid = np.ones(ids.shape, bool)
    h, l, v = blocks_mod.dedupe_row_keys(
        jnp.asarray(keys[..., 0]), jnp.asarray(keys[..., 1]),
        jnp.asarray(valid))
    return np.stack([np.asarray(h), np.asarray(l)], -1), np.asarray(v)


def _full_reblock(keys, valid, cfg):
    res = hdb.hashed_dynamic_blocking(jnp.asarray(keys), jnp.asarray(valid),
                                      cfg)
    blk = pairs.build_blocks(res)
    return sync(pairs.dedupe_pairs(blk, budget=max(blk.num_pair_slots, 1) + 1))


def bench_delta_vs_full(n_records: int = 100_000, delta_frac: float = 0.01,
                        check_speedup: bool = False, seed: int = 0):
    cfg = hdb.HDBConfig(max_block_size=64, max_iterations=6,
                        cms_width=1 << 18)
    rng = np.random.default_rng(seed)
    n_delta = max(int(n_records * delta_frac), 1)
    # two deltas: the first warms the delta-sized jit shapes (one-time
    # compiles), the second measures the steady-state serving cost
    keys, valid = _make_stream_keys(rng, n_records + 2 * n_delta)
    base_k, base_v = keys[:n_records], valid[:n_records]

    # --- streaming: build the base store ---
    store = BlockStore(cfg)
    blocker = DeltaBlocker(store)
    t0 = time.perf_counter()
    sync(blocker.ingest_keys(base_k, base_v))
    t_base = time.perf_counter() - t0
    print(f"# base store: {n_records} records, "
          f"{len(store.led_pack)} candidate pairs, built in {t_base:.2f}s")
    blocker.ingest_keys(keys[n_records:n_records + n_delta],
                        valid[n_records:n_records + n_delta])  # warm

    # --- batch: warm the compile cache, then time the union re-block ---
    _full_reblock(base_k[:4096], base_v[:4096], cfg)
    t0 = time.perf_counter()
    full = sync(_full_reblock(keys, valid, cfg))
    t_full = time.perf_counter() - t0

    # --- streaming: time the steady-state 1% delta ingest ---
    t0 = time.perf_counter()
    report = sync(blocker.ingest_keys(keys[n_records + n_delta:],
                                      valid[n_records + n_delta:]))
    t_delta = time.perf_counter() - t0

    want_pack = ((full.a.astype(np.uint64) << np.uint64(32))
                 | full.b.astype(np.uint64))
    assert np.array_equal(store.led_pack, want_pack), (
        "streaming ledger diverged from batch union "
        f"({len(store.led_pack)} vs {len(full.a)} pairs)")
    speedup = t_full / t_delta
    emit("streaming/delta_ingest", t_delta * 1e6,
         f"records={n_delta};pairs_added={report.num_pairs_added}")
    emit("streaming/full_reblock", t_full * 1e6, f"records={n_records + n_delta}")
    print(f"streaming,delta_ingest,{t_delta:.4f}s,{n_delta} records,"
          f"{report.num_pairs_added} new pairs")
    print(f"streaming,full_reblock,{t_full:.4f}s,{n_records + n_delta} records")
    print(f"streaming,speedup,{speedup:.2f}x (delta vs full re-block)")
    if check_speedup:
        assert speedup >= 5.0, (
            f"delta ingest only {speedup:.2f}x faster than full re-block "
            "(acceptance: >=5x)")
        print(f"# acceptance OK: {speedup:.2f}x >= 5x")
    return speedup


def bench_ingest_throughput(n_records: int = 20_000, seed: int = 1):
    cfg = hdb.HDBConfig(max_block_size=64, max_iterations=6,
                        cms_width=1 << 16)
    rng = np.random.default_rng(seed)
    keys, valid = _make_stream_keys(rng, n_records)
    print("# streaming: micro_batch,records_per_sec")
    for mb in (256, 1024, 4096):
        store = BlockStore(cfg)
        blocker = DeltaBlocker(store)
        # warm with the first batch, time the rest
        blocker.ingest_keys(keys[:mb], valid[:mb])
        t0 = time.perf_counter()
        for off in range(mb, n_records, mb):
            sync(blocker.ingest_keys(keys[off:off + mb], valid[off:off + mb]))
        dt = time.perf_counter() - t0
        rate = (n_records - mb) / dt
        emit(f"streaming/ingest_mb{mb}", dt * 1e6 / max(n_records - mb, 1),
             f"records_per_s={rate:.3g}")
        print(f"streaming,ingest,mb={mb},{rate:.3g} records/s")


def bench_sharded_ingest(n_shards: int, n_records: int = 20_000,
                         mb: int = 1024, seed: int = 2):
    """Sharded-store ingest (host-routing mirror) vs single-host, with
    ledger parity asserted and the per-shard occupancy/skew gauges from
    ``memory_stats`` emitted into the JSON record."""
    from repro.streaming import ShardedBlockStore

    cfg = hdb.HDBConfig(max_block_size=64, max_iterations=6,
                        cms_width=1 << 16)
    rng = np.random.default_rng(seed)
    keys, valid = _make_stream_keys(rng, n_records)
    flat = BlockStore(cfg)
    fb = DeltaBlocker(flat)
    st = ShardedBlockStore(cfg, n_shards=n_shards)
    sb = DeltaBlocker(st)
    fb.ingest_keys(keys[:mb], valid[:mb])   # warm
    sb.ingest_keys(keys[:mb], valid[:mb])
    times = {}
    for name, blocker in (("flat", fb), (f"shards{n_shards}", sb)):
        t0 = time.perf_counter()
        for off in range(mb, n_records, mb):
            sync(blocker.ingest_keys(keys[off:off + mb],
                                     valid[off:off + mb]))
        times[name] = time.perf_counter() - t0
    assert np.array_equal(flat.led_pack, st.led_pack), (
        f"sharded (n={n_shards}) ledger diverged from single-host")
    ms = st.memory_stats()
    n_done = n_records - mb
    emit(f"streaming/sharded_ingest_n{n_shards}",
         times[f"shards{n_shards}"] * 1e6 / max(n_done, 1),
         f"records_per_s={n_done / times[f'shards{n_shards}']:.3g};"
         f"shard_skew={ms['shard_skew']:.3f};"
         f"keytab_bytes={ms['keytab_bytes']};"
         f"csr_bytes={ms['csr_bytes']};ledger_bytes={ms['ledger_bytes']}")
    print(f"streaming,sharded_ingest,n_shards={n_shards},"
          f"{n_done / times[f'shards{n_shards}']:.3g} records/s,"
          f"skew={ms['shard_skew']:.3f} "
          f"(single-host {n_done / times['flat']:.3g} records/s)")
    for s in range(n_shards):
        print(f"streaming,shard{s},keytab={ms[f'shard{s}_keytab_bytes']},"
              f"csr={ms[f'shard{s}_csr_bytes']},"
              f"ledger={ms[f'shard{s}_ledger_bytes']}")


def run(check_speedup: bool = False, n_records: int = 100_000,
        n_shards: int = 0):
    bench_ingest_throughput()
    if n_shards > 0:
        bench_sharded_ingest(n_shards)
    bench_delta_vs_full(n_records=n_records, check_speedup=check_speedup)


if __name__ == "__main__":  # PYTHONPATH=src python -m benchmarks.bench_streaming
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="assert the >=5x delta-vs-full acceptance")
    ap.add_argument("--records", type=int, default=100_000)
    ap.add_argument("--json", nargs="?", const="BENCH_streaming.json",
                    default=None, metavar="PATH",
                    help="write the BENCH_streaming.json perf record")
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="also bench an N-shard ShardedBlockStore ingest "
                    "(parity-checked; per-shard bytes + skew in the JSON)")
    args = ap.parse_args()
    run(check_speedup=args.check, n_records=args.records,
        n_shards=args.shards)
    if args.json:
        from .common import write_json
        write_json(args.json, "streaming", records=args.records,
                   shards=args.shards)
