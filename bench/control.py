"""The control of each cell's check: the plain reference put in the
program's place with one stated guarantee broken, compared as a run
compares the program. It has to come out not correct; the benchmark's
own runs never run it.

- ``registry-batch``: the reference's matcher scores in bfloat16, the
  precision below the configured float32.
- ``registry-probe``: the reference walk stops after the first level,
  breaking "a probe's answer covers every level of the walk", over the
  pool rows that a window at the cell's rate would probe.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds S]

Prints one JSON line per seed with the numbers compared and ``correct``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def batch_control(cfg: dict, seed: int) -> dict:
    from bench.harness import batch, corpus

    columns, _ = corpus.records(cfg, seed)
    got = batch.reference_answer(cfg, columns, precision="bfloat16")
    return batch.check(cfg, columns, [got])


def probe_control(cfg: dict, traffic: dict, seed: int, seconds: float) -> dict:
    from bench.harness import corpus, probe
    from bench.reference import hdb as ref_hdb
    from bench.reference import keys as ref_keys

    stored, _ = corpus.records(cfg, seed)
    pool = probe.make_pool(cfg, traffic, stored, seed)
    _, rows = probe.arrivals(traffic["rate_per_s"], seconds, seed,
                             traffic["pool"])
    keys, valid = ref_keys.build_keys(stored, cfg["blocking"])
    blocking = ref_hdb.hdb(cfg["hdb"], keys, valid)
    qk, qv = ref_keys.build_keys(pool, cfg["blocking"])
    truncated = ref_hdb.walk(cfg["hdb"], blocking, qk, qv, max_levels=1)
    answers = {}
    for k, r in enumerate(rows):
        cand, sizes = truncated[int(r)]
        answers[k] = types.SimpleNamespace(candidates=cand, block_sizes=sizes)
    return probe.check(cfg, stored, pool, rows, answers)


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    for path in (os.path.join(root, "src"), root):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench.harness import spec

    bench = spec.load(root)
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(root, bench, cell["config"])
    traffic = spec.traffic(root, cell["traffic"])
    seconds = args.seconds or bench["run_seconds"]
    for seed in (int(s) % (1 << 64) for s in args.seeds.split(",")):
        if traffic["mode"] == "batch":
            checks = batch_control(cfg, seed)
        else:
            checks = probe_control(cfg, traffic, seed, seconds)
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "correct": all(v <= lim for v, lim in checks.values()),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
