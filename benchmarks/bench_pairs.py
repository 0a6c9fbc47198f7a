"""Pair materialization throughput: numpy vs JAX vs Pallas backends.

Measures end-to-end ``dedupe_pairs`` (enumerate + largest-block-wins
dedupe) in pairs/sec across block-size distributions — the numpy shift
method degrades on many-small-block layouts (one pass per diagonal
offset), while the device engine's cost is distribution-independent
(O(1) integer decode per slot + one sort). The acceptance workload is
~1M pair slots, where the JAX backend must report >=5x the numpy path.

Pallas timings here are interpret-mode (CPU container) and are parity
checks, not perf numbers — see bench_kernels.py for the same caveat.
"""
from __future__ import annotations

import time

import numpy as np

from .common import emit, sync

from repro.core import pairs


def _make_blocks(dist: str, target_slots: int, seed: int = 0) -> pairs.Blocks:
    """Synthesize a CSR block layout with ~target_slots pair slots."""
    rng = np.random.default_rng(seed)
    if dist == "small":        # many tiny blocks (shift method's worst case)
        size_draw = lambda: rng.integers(2, 9)
    elif dist == "medium":
        size_draw = lambda: rng.integers(16, 65)
    elif dist == "large":      # few big blocks (meshgrid path)
        size_draw = lambda: rng.integers(300, 501)
    else:                      # zipf-ish mix
        size_draw = lambda: min(500, 2 + int(rng.zipf(1.5)))
    sizes = []
    slots = 0
    while slots < target_slots:
        n = int(size_draw())
        sizes.append(n)
        slots += n * (n - 1) // 2
    sizes = np.asarray(sizes, np.int64)
    start = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    # overlapping membership so the dedupe actually removes pairs
    universe = int(sizes.sum())
    members = np.concatenate(
        [np.sort(rng.choice(universe, n, replace=False)) for n in sizes]
    ).astype(np.int64)
    zu = np.zeros(len(sizes), np.uint32)
    return pairs.Blocks(zu, zu, start, sizes, members)


def _time_backend(blk: pairs.Blocks, backend: str, iters: int = 3,
                  sort_backend: str = "auto") -> float:
    pairs.dedupe_pairs(blk, backend=backend,
                       sort_backend=sort_backend)  # warm / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = sync(pairs.dedupe_pairs(blk, backend=backend,
                                      sort_backend=sort_backend))
    dt = (time.perf_counter() - t0) / iters
    assert out.exact
    return dt


def run(distributions=("small", "medium", "large", "zipf"),
        target_slots: int = 1_000_000, check_speedup: bool = False,
        sort_backend: str = "auto"):
    """Backend axis (numpy/jax/pallas) x dedupe-sort axis.

    ``sort_backend`` measures the dedupe-sort knob the same way the
    numpy-vs-JAX axis is measured: "auto" keeps the per-platform default
    (the legacy rows); "comparator"/"radix" force that device sort in
    the jax backend and ALSO emit the comparator baseline, so the
    comparator-vs-radix crossover lands in the same record.
    """
    sort_axes = (["auto"] if sort_backend == "auto"
                 else sorted({"comparator", sort_backend}))
    print("# pairs: distribution,backend,sort,seconds,pairs_per_sec,"
          "speedup_vs_numpy")
    accept_ratio = None
    for dist in distributions:
        blk = _make_blocks(dist, target_slots)
        total = blk.num_pair_slots
        t_np = _time_backend(blk, "numpy")
        rows = [("numpy", "auto", t_np)]
        for sb in sort_axes:
            rows.append(("jax", sb, _time_backend(blk, "jax",
                                                  sort_backend=sb)))
        # the pallas row stays on the default sort: its interpret-mode
        # timing is a parity check, not a perf number (see module doc)
        rows.append(("pallas", "auto", _time_backend(blk, "pallas")))
        for backend, sb, t in rows:
            rate = total / t
            speedup = t_np / t
            tag = "" if sb == "auto" else f"_sort-{sb}"
            emit(f"pairs/{dist}_{backend}{tag}", t * 1e6,
                 f"pairs_per_s={rate:.3g};speedup={speedup:.2f}x;"
                 f"slots={total};sort={sb}")
            print(f"pairs,{dist},{backend},{sb},{t:.4f},{rate:.3g},"
                  f"{speedup:.2f}")
            if dist == "small" and backend == "jax" and accept_ratio is None:
                accept_ratio = speedup
    if check_speedup and sort_backend != "auto":
        # the >=5x gate is defined for the per-platform default sort; a
        # forced device sort measures a different axis — say so loudly
        # instead of exiting green as if the gate had held
        print("# acceptance check SKIPPED: --check gates the auto sort "
              f"backend, not sort_backend={sort_backend!r}")
    elif check_speedup and accept_ratio is not None:
        assert accept_ratio >= 5.0, (
            f"JAX backend only {accept_ratio:.2f}x over numpy on the "
            "1M-slot small-block workload (acceptance: >=5x)")
        print(f"# acceptance OK: jax {accept_ratio:.2f}x >= 5x")


def run_mesh(target_slots: int = 1_200_000,
             distributions=("small", "zipf"),
             chunk_per_shard: int = 1 << 16,
             check_speedup: bool = False,
             sort_backend: str = "auto"):
    """Routed vs global-sort distributed dedupe on an emulated host mesh.

    Requires >= 2 devices (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; ``--mesh``
    re-execs with that set). Measures ``materialize_pairs_distributed``
    end-to-end in both dedupe modes: "global" gathers every shard's
    decoded pairs into ONE device sort (the pre-routing bottleneck),
    "routed" fingerprint-routes packed sort words with an all_to_all per
    round and dedupes shard-locally, so the per-shard peak buffer stays
    at ~total/n_shards * route_slack words instead of total.
    """
    import math

    import jax

    from repro.core.distributed import materialize_pairs_distributed

    n_shards = jax.device_count()
    assert n_shards >= 2, "mesh bench needs emulated devices (use --mesh)"
    mesh = jax.make_mesh((n_shards,), ("data",))
    route_slack = 2.0
    print("# pairs-mesh: distribution,mode,seconds,pairs_per_sec,speedup_vs_global")
    accept = None
    for dist in distributions:
        blk = _make_blocks(dist, target_slots)
        total = blk.num_pair_slots
        results = {}
        times = {}
        for mode in ("global", "routed"):
            kw = dict(axis_names=("data",), chunk_per_shard=chunk_per_shard,
                      dedupe=mode, route_slack=route_slack,
                      sort_backend=sort_backend)
            results[mode] = materialize_pairs_distributed(blk, mesh, **kw)
            # best-of-3: min de-noises shared-runner scheduler contention
            # (this timing gates the CI slow lane)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                results[mode] = sync(
                    materialize_pairs_distributed(blk, mesh, **kw))
                best = min(best, time.perf_counter() - t0)
            times[mode] = best
        # bit-identical contract between the two dedupe modes
        np.testing.assert_array_equal(results["routed"].a, results["global"].a)
        np.testing.assert_array_equal(results["routed"].b, results["global"].b)
        np.testing.assert_array_equal(results["routed"].src_size,
                                      results["global"].src_size)
        # per-shard peak pair-buffer of the routed path (words), vs the
        # full pair set the global path funnels through one device
        cap = math.ceil(chunk_per_shard / n_shards * route_slack)
        rounds = math.ceil(total / (n_shards * chunk_per_shard))
        per_shard = rounds * n_shards * cap
        assert per_shard < total, (per_shard, total)
        for mode in ("global", "routed"):
            speedup = times["global"] / times[mode]
            emit(f"pairs_mesh/{dist}_{mode}", times[mode] * 1e6,
                 f"pairs_per_s={total/times[mode]:.3g};speedup={speedup:.2f}x;"
                 f"slots={total};shards={n_shards}")
            print(f"pairs-mesh,{dist},{mode},{times[mode]:.4f},"
                  f"{total/times[mode]:.3g},{speedup:.2f}")
        print(f"#   per-shard peak buffer {per_shard} words "
              f"({per_shard/total:.2f}x of {total} total slots)")
        if dist == distributions[0]:
            accept = times["global"] / times["routed"]
    if check_speedup and accept is not None:
        assert accept > 1.0, (
            f"routed dedupe only {accept:.2f}x vs the global sort on "
            f"{n_shards} emulated hosts (acceptance: >1x at >=1M slots)")
        print(f"# acceptance OK: routed {accept:.2f}x > 1x vs global sort")


if __name__ == "__main__":  # PYTHONPATH=src python -m benchmarks.bench_pairs
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    import argparse
    import os
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="assert the acceptance speedups")
    ap.add_argument("--mesh", action="store_true",
                    help="routed-vs-global bench on 8 emulated hosts")
    ap.add_argument("--slots", type=int, default=None,
                    help="target pair slots per layout")
    ap.add_argument("--sort-backend", default="auto",
                    choices=("auto", "comparator", "radix"),
                    help="dedupe-sort knob; non-auto adds the "
                         "comparator-vs-radix axis to the jax rows")
    ap.add_argument("--json", nargs="?", const="BENCH_pairs.json",
                    default=None, metavar="PATH",
                    help="write the BENCH_pairs.json perf record")
    args = ap.parse_args()
    if args.mesh:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            # the 8 "hosts" are emulated CPU devices; on an accelerator
            # this would silently time whatever chips exist under that name
            sys.exit("--mesh emulates 8 hosts on the CPU backend; run it "
                     "with JAX_PLATFORMS=cpu")
        if "--xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            env = dict(os.environ)
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_count=8").strip()
            os.execve(sys.executable,
                      [sys.executable, "-m", "benchmarks.bench_pairs"]
                      + sys.argv[1:], env)
        run_mesh(check_speedup=args.check, sort_backend=args.sort_backend,
                 **({"target_slots": args.slots} if args.slots else {}))
    else:
        run(check_speedup=args.check, sort_backend=args.sort_backend,
            **({"target_slots": args.slots} if args.slots else {}))
    if args.json:
        from .common import write_json
        write_json(args.json, "pairs", mesh=args.mesh,
                   sort_backend=args.sort_backend)
