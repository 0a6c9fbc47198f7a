"""End-to-end dedup pipeline: host vs fused match->filter->cluster.

Times every stage of ``dedup_corpus`` (blocking / matching / partition,
all ``block_until_ready``-synced inside the pipeline) for the host
baseline and the fused device backends, and accounts the per-call
host<->device transit each back-half incurs:

- **host**: the full per-pair score vector and matched mask cross to the
  host, the matched pair list is gathered in numpy and re-uploaded for
  connected components — transit scales with the CANDIDATE pair count.
- **jnp / pallas** (kernels/match): score+threshold+compaction and the
  CC rounds stay on device; only final labels, survivors, and three
  scalars cross — transit scales with the RECORD count.

Both paths must produce bit-identical survivors/labels (asserted every
run; ``--check`` makes a failure fatal for CI). Pallas rows off-TPU are
interpret-mode parity checks, not perf numbers (the bench_pairs caveat).
"""
from __future__ import annotations

import argparse

import numpy as np

from .common import emit, get_corpus, write_json

from repro.core import hdb
from repro.data import pipeline

# stage seconds -> derived transit bytes: see module docstring
_F32 = 4
_I32 = 4
_I64 = 8


def _transit_bytes(rep: pipeline.DedupReport, backend: str) -> int:
    p = rep.num_candidate_pairs
    m = rep.num_matched_pairs
    n = rep.num_records
    s = rep.num_survivors
    down = n * _I32 + s * _I32 + 3 * _I32        # labels + survivors + scalars
    if backend == "host":
        # scores down, matched mask down, matched pairs back up for CC
        return p * _F32 + p * 1 + 2 * m * _I64 + down
    return down


def run(dataset: str = "SYN30K", backends=("host", "jnp"),
        max_block_size: int = 100, check: bool = False) -> bool:
    corpus = get_corpus(dataset)
    cfg = hdb.HDBConfig(max_block_size=max_block_size)
    print("# match: backend,stage,seconds + derived counters")
    reports = {}
    for backend in backends:
        pipeline.dedup_corpus(corpus, cfg, match_backend=backend)  # warm
        rep = pipeline.dedup_corpus(corpus, cfg, match_backend=backend)
        reports[backend] = rep
        total = (rep.blocking_seconds + rep.matching_seconds
                 + rep.partition_seconds)
        emit(f"match/block/{backend}", rep.blocking_seconds * 1e6,
             f"pairs={rep.num_candidate_pairs}")
        emit(f"match/match/{backend}", rep.matching_seconds * 1e6,
             f"matched={rep.num_matched_pairs}")
        emit(f"match/cluster/{backend}", rep.partition_seconds * 1e6,
             f"components={rep.num_components}")
        emit(f"match/e2e/{backend}", total * 1e6,
             f"records={rep.num_records} transit_bytes="
             f"{_transit_bytes(rep, backend)}")
    ok = True
    base = reports.get("host")
    if base is not None:
        for backend, rep in reports.items():
            same = (np.array_equal(rep.survivors, base.survivors)
                    and np.array_equal(rep.component_of, base.component_of)
                    and rep.num_matched_pairs == base.num_matched_pairs)
            ok = ok and same
            emit(f"match/parity/{backend}", 0.0,
                 f"bit_identical={'yes' if same else 'NO'}")
    if check and not ok:
        raise SystemExit("fused path is NOT bit-identical to host baseline")
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="SYN30K")
    ap.add_argument("--backends", default="host,jnp",
                    help="comma list from host,jnp,pallas")
    ap.add_argument("--max-block-size", type=int, default=100)
    ap.add_argument("--check", action="store_true",
                    help="fail the process if bit-identity breaks")
    ap.add_argument("--json", metavar="PATH",
                    help="write a BENCH_match.json perf record")
    args = ap.parse_args()
    backends = tuple(b for b in args.backends.split(",") if b)
    ok = run(dataset=args.dataset, backends=backends,
             max_block_size=args.max_block_size, check=args.check)
    if args.json:
        write_json(args.json, "match", dataset=args.dataset,
                   backends=list(backends), bit_identical=ok)


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    main()
