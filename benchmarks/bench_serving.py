"""Serving bench: probe QPS and p50/p99 latency vs client batch size.

The workload: a hot single-tenant store serves a stream of probe requests
through the ``DedupeService`` front-end. Clients submit micro-batches of
``--batch-sizes`` rows; the service collates them up to ``probe_slots``
and pads to the power-of-two bucket ladder, so every batch size rides the
same few compiled walk shapes. The acceptance gate (``--check``) asserts
the recompile trajectory: after a one-round warmup, running every batch
size adds ZERO compiled variants to the shared jitted probe steps
(measured via ``probe_jit_cache_sizes``, i.e. real jit cache sizes, not a
proxy) — the bucket ladder is what makes mixed batch sizes servable.

Latency percentiles come from the service's own metrics histograms — the
same numbers a dashboard would scrape — and QPS from wall clock over
served rows.

    PYTHONPATH=src python -m benchmarks.bench_serving [--check] \
        [--records N] [--probes N] [--json [PATH]]
"""
from __future__ import annotations

import time

import numpy as np

from .bench_streaming import _make_stream_keys
from .common import emit, sync

from repro.core import hdb
from repro.serving import DedupeService, ServiceConfig
from repro.streaming.delta import probe_jit_cache_sizes


def run(n_records: int = 50_000, n_probes: int = 2_048,
        batch_sizes=(1, 8, 64), check: bool = False, seed: int = 0):
    cfg = hdb.HDBConfig(max_block_size=64, max_iterations=6,
                        cms_width=1 << 16)
    rng = np.random.default_rng(seed)
    keys, valid = _make_stream_keys(rng, n_records + n_probes)
    svc = DedupeService(cfg, ServiceConfig(
        probe_slots=64, ingest_slots=1 << 20,
        max_read_queue=1 << 20, max_write_queue=64))
    svc.add_tenant("t")

    t0 = time.perf_counter()
    svc.submit_ingest("t", keys[:n_records], valid[:n_records])
    sync(svc.run())
    t_build = time.perf_counter() - t0
    store = svc.tenant("t").store
    print(f"# store: {n_records} records, {len(store.led_pack)} candidate "
          f"pairs, built in {t_build:.2f}s")

    probe_k, probe_v = keys[n_records:], valid[n_records:]

    # warmup: one drained round per batch size compiles that size's bucket
    # rung (and the walk's descent shapes); measured rounds then replay the
    # exact same shapes
    for b in batch_sizes:
        svc.submit_probe("t", probe_k[:b], probe_v[:b])
        sync(svc.run())
    cache_warm = probe_jit_cache_sizes()
    compiles_warm = svc.snapshot()["counters"]["bucket_compiles_total"]
    print(f"# warmup: {compiles_warm} bucket shapes compiled, "
          f"jit cache {cache_warm}")

    for b in batch_sizes:
        svc.metrics.reset()
        svc.probe_responses.clear()
        t0 = time.perf_counter()
        for off in range(0, n_probes, b):
            svc.submit_probe("t", probe_k[off:off + b], probe_v[off:off + b])
        sync(svc.run())
        dt = time.perf_counter() - t0
        snap = svc.snapshot()
        rows = snap["counters"]["probe_rows_total"]
        lat = snap["histograms"]["probe_latency_s"]
        occ = snap["histograms"]["batch_occupancy"]
        qps = rows / dt
        emit(f"serving/probe_b{b}", dt / rows * 1e6,
             f"qps={qps:.4g};p50_ms={lat['p50'] * 1e3:.4g};"
             f"p99_ms={lat['p99'] * 1e3:.4g};occupancy={occ['mean']:.3f};"
             f"batches={snap['counters']['probe_batches_total']}")
        print(f"serving,b={b},{qps:.4g} probes/s,"
              f"p50={lat['p50'] * 1e3:.3g}ms,p99={lat['p99'] * 1e3:.3g}ms,"
              f"occupancy={occ['mean']:.2f}")
        if check:
            assert rows == n_probes, f"served {rows} of {n_probes} probes"
            assert all(r.status == "ok" for r in svc.probe_responses)

    cache_end = probe_jit_cache_sizes()
    recompiles = sum(cache_end.values()) - sum(cache_warm.values())
    emit("serving/recompiles_after_warmup", float(recompiles),
         f"jit_cache={cache_end};bucket_shapes={compiles_warm}")
    print(f"# recompiles after warmup across {len(batch_sizes)} batch "
          f"sizes: {recompiles} (jit cache {cache_end})")
    if check:
        assert recompiles == 0, (
            f"bucket ladder leaked {recompiles} recompiles across batch "
            f"sizes {tuple(batch_sizes)}: {cache_warm} -> {cache_end}")
        print("# acceptance OK: recompile count constant after warmup")


if __name__ == "__main__":  # PYTHONPATH=src python -m benchmarks.bench_serving
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="assert full service + zero recompiles after warmup")
    ap.add_argument("--records", type=int, default=50_000)
    ap.add_argument("--probes", type=int, default=2_048)
    ap.add_argument("--batch-sizes", type=int, nargs="+", default=[1, 8, 64])
    ap.add_argument("--json", nargs="?", const="BENCH_serving.json",
                    default=None, metavar="PATH",
                    help="write the BENCH_serving.json perf record")
    args = ap.parse_args()
    run(n_records=args.records, n_probes=args.probes,
        batch_sizes=tuple(args.batch_sizes), check=args.check)
    if args.json:
        from .common import write_json
        write_json(args.json, "serving", records=args.records,
                   probes=args.probes, batch_sizes=list(args.batch_sizes))
