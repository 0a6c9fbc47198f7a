"""Find the knee of an open-loop probe cell: the highest Poisson rate at
which the service answers every probe and its queue does not grow.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 600,800,1000

Sets the cell up once (as a run does), then drives a window at each rate
in turn and prints one JSON line per rate: latency percentiles, failed
probes, rows a step, and the median latency of the first and the last
fifth of the window (a queue that grows shows as the last fifth's
median far above the first's). The cell's traffic file then fixes its
rate at about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, open_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    opened = open_run(args, ROOT, require_accelerator=True)
    if opened is None:
        return 2
    _, _, run, _ = opened

    from bench.harness import probe
    from bench.harness.stats import percentile

    ctx = probe.prepare(run)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        snap = run.compiles.snapshot()
        got = probe.window(run, ctx, rate, args.seconds, run.seed + i)
        lat = got["latency"] * 1e3          # in due order
        fifth = max(1, len(lat) // 5)
        print(json.dumps(dict(
            rate=rate, **probe.summary(got),
            first_fifth_p50_ms=percentile(lat[:fifth].tolist(), 50),
            last_fifth_p50_ms=percentile(lat[-fifth:].tolist(), 50),
            compiles=run.compiles.since(snap)["compiles"])), flush=True)
    run.compiles.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
