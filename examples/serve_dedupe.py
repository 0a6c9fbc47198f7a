"""Gazetteer-mode dedupe serving demo: canonical store + streamed probes.

The dedupe-examples gazetteer workload: a canonical reference table is
ingested once (write lane); messy duplicate records then stream in as
probe queries (read lane, ``include_probe=True``) and are matched against
the canonical store WITHOUT joining it. The demo builds a synthetic
corpus with ground-truth entity ids, ingests the first record of each
entity as the canonical table, streams every remaining duplicate through
the ``DedupeService`` in waves, and reports blocking recall (how often
the true entity's canonical record appears among a probe's candidates)
plus the service's own metrics snapshot.

    PYTHONPATH=src python examples/serve_dedupe.py [--entities 1500]
    PYTHONPATH=src python examples/serve_dedupe.py --smoke   # CI-sized
"""
import argparse
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.core import blocks as blocks_mod
from repro.core import hdb
from repro.data import synthetic
from repro.serving import DedupeService, ServiceConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=1_500)
    ap.add_argument("--wave", type=int, default=48,
                    help="probe records per submitted request")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus + recall assert (CI smoke step)")
    args = ap.parse_args()
    if args.smoke:
        args.entities = 150

    corpus = synthetic.generate(synthetic.SyntheticSpec(
        num_entities=args.entities, dup_rate=0.5, seed=13))
    keys, valid = blocks_mod.build_keys(corpus.columns, corpus.blocking)
    keys, valid = np.asarray(keys), np.asarray(valid)
    ent = corpus.entity_id

    # canonical table = first record of each entity; probes = the duplicates
    _, first_idx = np.unique(ent, return_index=True)
    is_canon = np.zeros(len(ent), bool)
    is_canon[first_idx] = True
    canon = np.flatnonzero(is_canon)
    probes = np.flatnonzero(~is_canon)
    print(f"gazetteer: {len(canon)} canonical records, "
          f"{len(probes)} streamed probes")

    cfg = hdb.HDBConfig(max_block_size=50, max_iterations=6,
                        cms_width=1 << (12 if args.smoke else 16))
    svc = DedupeService(cfg, ServiceConfig(
        probe_slots=64, ingest_slots=1 << 16, max_read_queue=1 << 16))
    svc.add_tenant("gazetteer")
    svc.submit_ingest("gazetteer", keys[canon], valid[canon])
    svc.run()
    # store rids 0..len(canon)-1 were assigned in canon order
    canon_ent = ent[canon]

    uid_rows = {}
    for off in range(0, len(probes), args.wave):
        idx = probes[off:off + args.wave]
        uid = svc.submit_probe("gazetteer", keys[idx], valid[idx],
                               include_probe=True)
        uid_rows[uid] = idx
    svc.run()

    hit = total = 0
    for resp in svc.probe_responses:
        assert resp.status == "ok"
        for row, qr in zip(uid_rows[resp.uid], resp.results):
            total += 1
            if len(qr.candidates):
                hit += ent[row] in canon_ent[qr.candidates]
    recall = hit / max(total, 1)
    print(f"blocking recall vs canonical store: {hit}/{total} "
          f"({recall:.1%})")

    snap = svc.snapshot()
    counters, hists = snap["counters"], snap["histograms"]
    lat = hists["probe_latency_s"]
    print(f"metrics: {counters['probe_rows_total']} probe rows in "
          f"{counters['probe_batches_total']} padded batches "
          f"({counters['bucket_compiles_total']} bucket shapes), "
          f"p50={lat['p50'] * 1e3:.2f}ms p99={lat['p99'] * 1e3:.2f}ms, "
          f"occupancy={hists['batch_occupancy']['mean']:.2f}")
    if args.smoke and recall < 0.6:
        raise SystemExit(f"smoke recall {recall:.1%} < 60%")


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    main()
