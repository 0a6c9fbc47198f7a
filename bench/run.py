"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` and the files under ``bench/``; the traffic's
``mode`` picks the traffic loop (``bench/harness/<mode>.py``). With
``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiled window. Every
run ends by comparing what the timed path produced with the plain
reference under ``bench/reference``; each number compared is printed
beside its limit, as the last lines on standard error and under
``checks`` in the result line.

The run needs an accelerator with as many chips as the cell asks for:
without one it exits non-zero and prints no result. JAX's persistent
compilation cache lives in ``.jax_cache`` at the checkout's root.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INFINITE_MS = 1e12   # how a percentile that falls on a failed request prints


def process_age() -> float:
    """Seconds since this process started, as of ``T0`` (0 off Linux)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK")
                   - (time.perf_counter() - T0))
    except (OSError, ValueError, IndexError):
        return 0.0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def finite(x: float) -> float:
    return INFINITE_MS if math.isinf(x) else x


def open_run(args, root: str, require_accelerator: bool):
    """Find the cell and its files, check the chips, turn on the compile
    cache: ``(bench, cell, run, devices)``, or None without the chips."""
    for path in (os.path.join(root, "src"), root):
        if path not in sys.path:
            sys.path.insert(0, path)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")

    from bench.harness import common, spec

    bench = spec.load(root)
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(root, bench, cell["config"])
    traffic = spec.traffic(root, cell["traffic"])

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_accelerator and (dev.platform == "cpu"
                                or len(devices) < cell["chips"]):
        print(f"cell {cell['name']!r} needs {cell['chips']} accelerator "
              f"chip(s); JAX found {len(devices)} {dev.platform!r} "
              "device(s)", file=sys.stderr)
        return None

    from repro.runtime import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run = common.Run(root=root, cell=cell, cfg=cfg, traffic=traffic,
                     seed=args.seed % (1 << 64), seconds=args.seconds,
                     trace=bool(getattr(args, "trace", 0)), t0=T0,
                     compiles=common.Compiles(), cache_dir=cache_dir)
    run.log(f"cell {cell['name']} on {len(devices)} x {dev.device_kind} "
            f"({dev.platform}); compilation cache {cache_dir}")
    return bench, cell, run, devices


def main(argv=None, root: str = ROOT, require_accelerator: bool = True) -> int:
    """Run a cell. ``require_accelerator=False`` lets the harness's own
    tests drive it on the CPU; the command line never sets it."""
    args = parse(argv)
    age0 = process_age()
    opened = open_run(args, root, require_accelerator)
    if opened is None:
        return 2
    bench, cell, run, devices = opened
    loop = importlib.import_module(f"bench.harness.{run.traffic['mode']}")
    try:
        return report(bench, cell, run, loop.run(run), devices[0],
                      len(devices), age0, run.cache_dir)
    finally:
        run.compiles.close()


def report(bench, cell, run, out, dev, n_devices, age0, cache_dir) -> int:
    """Print the compile count, the checks and the result line."""
    from bench.harness import spec
    from bench.harness import trace as trace_mod

    root = run.root

    result = {"correct": False, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}}
    if not run.trace:
        values = dict(out["metrics"],
                      setup_s=age0 + out["setup_end"] - T0)
        for m in spec.end_to_end(bench, cell["name"]):
            result["metrics"][m["name"]] = {"value": finite(values[m["name"]]),
                                            "unit": m["unit"]}
    else:
        for m, read in spec.per_layer(root, bench, cell["name"]):
            value = read(out["layer"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": n_devices,
                        "memory_peak_bytes": out["peak_bytes"]}
    if run.trace:
        tr = out["trace"]
        result["device"].update(busy_s=trace_mod.busy_s(tr),
                                window_s=trace_mod.window_s(tr))
        result["breakdown"] = trace_mod.breakdown(tr)
    window = out["window_compiles"]
    print(f"compiles inside the window: {window['compiles']} "
          f"(programs {window['programs']}); set-up cache hits "
          f"{out['setup_compiles']['cache_hits']}, misses "
          f"{out['setup_compiles']['cache_misses']}; cache {cache_dir}",
          flush=True)
    t = time.perf_counter()
    checks = out["finish"]()
    run.log(f"reference check {time.perf_counter() - t:.3f}s")
    result["correct"] = all(v <= lim for v, lim in checks.values())
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
