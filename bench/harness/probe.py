"""Open-loop probes: single-row lookups into ``DedupeService`` at a fixed
Poisson rate.

Set-up generates the stored records and a pool of probes from the seed
(corrupted copies of stored records and records of new entities, in the
traffic's proportion), builds their keys with the program's
``blocks.build_keys``, bulk-loads the store with one ingest, and warms
every bucket rung the traffic can reach with probes that walk the most
levels. The window submits each probe at its due time on a seeded
Poisson schedule and otherwise steps the service; a probe's latency runs
from its due time to the return of the ``step()`` that answered it. A
probe that the full read lane rejects is offered again after the next
step, as a client honours backpressure; one still not admitted, shed or
unanswered by the end of the drain is failed and infinitely late. The
check walks every answered probe's pool row through the plain reference
over the same stored records.
"""
from __future__ import annotations

import json
import math
import time

import numpy as np

from . import common, corpus
from .stats import percentile
from ..reference import hdb as ref_hdb
from ..reference import keys as ref_keys

TENANT = "tenant0"
# a step this long is logged as a stall: a 64-row step takes ~25-150 ms
STALL_S = 0.25


def make_pool(cfg: dict, traffic: dict, stored: dict, seed: int) -> dict:
    """The probes: duplicates of stored persons in the corpus's own share
    of duplicates (``1 - originals_share``), the rest new persons."""
    gen = cfg["generator"]
    n = len(next(iter(stored.values()))[0])
    size = traffic["pool"]
    n_old = round(size * (1.0 - gen["originals_share"]))
    rng = np.random.default_rng([seed, 2])
    old = corpus.modify(rng, gen, corpus.take_rows(stored,
                                                   rng.choice(n, n_old)))
    new = corpus.originals(rng, gen, size - n_old)
    return {k: (np.concatenate([old[k][0], new[k][0]]),
                np.concatenate([old[k][1], new[k][1]])) for k in stored}


def program_keys(cfg: dict, columns: dict):
    from repro.core import blocks

    keys, valid = blocks.build_keys(common.program_columns(columns),
                                    common.program_blocking(cfg))
    return np.asarray(keys), np.asarray(valid)


def make_service(cfg: dict):
    from repro.serving.service import DedupeService, ServiceConfig

    s = cfg["service"]
    return DedupeService(common.hdb_config(cfg), ServiceConfig(
        probe_slots=s["probe_slots"], ingest_slots=s["ingest_slots"],
        max_read_queue=s["max_read_queue"],
        max_write_queue=s["max_write_queue"], min_bucket=s["min_bucket"]))


def rungs(cfg: dict) -> list:
    s = cfg["service"]
    out, b = [], s["min_bucket"]
    while b < s["probe_slots"]:
        out.append(b)
        b *= 2
    return out + [s["probe_slots"]]


def warm_up(run: common.Run, svc, keys, valid, include_probe: bool) -> None:
    """Compile the walk for every bucket rung: first the whole pool in
    full batches, then each smaller rung filled with the probes that
    walked the most levels, until a pass compiles nothing."""
    slots = run.cfg["service"]["probe_slots"]
    first = len(svc.probe_responses)
    for off in range(0, len(keys), slots):
        for i in range(off, min(off + slots, len(keys))):
            svc.submit_probe(TENANT, keys[i:i + 1], valid[i:i + 1],
                             include_probe=include_probe)
        svc.step()
    depth = np.array([r.results[0].levels_walked
                      for r in svc.probe_responses[first:]])
    deepest = np.argsort(-depth, kind="stable")
    for attempt in range(3):
        snap = run.compiles.snapshot()
        for b in rungs(run.cfg):
            for i in deepest[:b]:
                svc.submit_probe(TENANT, keys[i:i + 1], valid[i:i + 1],
                                 include_probe=include_probe)
            svc.step()
        new = run.compiles.since(snap)["compiles"]
        run.log(f"warm-up pass {attempt}: {new} compiles, deepest walk "
                f"{int(depth.max(initial=0))} levels")
        if new == 0:
            break


def arrivals(rate: float, seconds: float, seed: int, pool: int):
    """Seeded Poisson arrival offsets in ``[0, seconds)`` and the pool row
    each arrival probes."""
    rng = np.random.default_rng([seed, 3])
    gaps = rng.exponential(1.0 / rate, int(rate * seconds * 1.5) + 64)
    t = np.cumsum(gaps)
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(
            1.0 / rate, len(t)))])
    t = t[t < seconds]
    return t, rng.integers(0, pool, len(t))


def drive(svc, keys, valid, offsets, rows, include_probe: bool,
          drain_s: float) -> dict:
    """Submit each probe at its due time, step while work is queued.

    A probe that ``BackpressureError`` turns away, and every probe due
    after it, waits for the next step and is offered again, in due order,
    until the drain ends. Returns per probe ``latency`` (inf when failed),
    ``wait`` (due time to the start of the answering step), ``late`` (due
    time to admission), the answer, the number of rejections, the steps
    that served probes as (start, end, rows), the longest step, and each
    step over ``STALL_S`` as a stall: its wall seconds and the main
    thread's CPU seconds in it (near the wall when the interpreter was
    busy, near 0 when the thread waited).
    """
    from repro.serving.service import STATUS_OK, BackpressureError

    n = len(offsets)
    latency = np.full(n, math.inf)
    wait = np.full(n, math.nan)
    late = np.full(n, math.nan)
    answers: dict = {}
    index_of: dict = {}
    steps = []
    longest = 0.0
    stalls = []
    rejections = 0
    seen = len(svc.probe_responses)
    clock = time.perf_counter
    start = clock()
    due = start + offsets
    stop = start + (offsets[-1] if n else 0.0) + drain_s
    i = 0
    while True:
        now = clock()
        if i < n and due[i] <= now < stop:
            with common.span("bench.submit"):
                while i < n and due[i] <= now:
                    try:
                        uid = svc.submit_probe(
                            TENANT, keys[rows[i]:rows[i] + 1],
                            valid[rows[i]:rows[i] + 1],
                            include_probe=include_probe)
                    except BackpressureError:
                        rejections += 1
                        break
                    index_of[uid] = i
                    late[i] = clock() - due[i]
                    i += 1
        if svc.busy and now < stop:
            with common.span("bench.step"):
                s0, c0 = clock(), time.thread_time()
                svc.step()
                s1 = clock()
            longest = max(longest, s1 - s0)
            if s1 - s0 > STALL_S:
                stalls.append({"seconds": s1 - s0,
                               "cpu_s": time.thread_time() - c0})
            served = 0
            for resp in svc.probe_responses[seen:]:
                k = index_of.pop(resp.uid)
                if resp.status == STATUS_OK:
                    latency[k] = s1 - due[k]
                    wait[k] = s0 - due[k]
                    answers[k] = resp.results[0]
                    served += 1
            seen = len(svc.probe_responses)
            if served:
                steps.append((s0, s1, served))
        elif i < n and now < stop:
            with common.span("bench.idle"):
                while clock() < due[i] - 0.002:
                    time.sleep(0.001)
                while clock() < due[i]:
                    pass
        else:
            break
    return {"start": start, "latency": latency, "wait": wait, "late": late,
            "answers": answers, "rejections": rejections, "steps": steps,
            "longest_step": longest, "stalls": stalls, "lost": len(index_of)}


def check(cfg: dict, stored: dict, pool: dict, rows: np.ndarray,
          answers: dict, max_levels: int | None = None) -> dict:
    """Probes whose answer differs from the reference walk."""
    keys, valid = ref_keys.build_keys(stored, cfg["blocking"])
    blocking = ref_hdb.hdb(cfg["hdb"], keys, valid)
    asked = np.unique(np.array([rows[k] for k in answers], np.int64))
    qk, qv = ref_keys.build_keys(corpus.take_rows(pool, asked),
                                 cfg["blocking"])
    want = dict(zip(asked.tolist(), ref_hdb.walk(cfg["hdb"], blocking, qk, qv,
                                                 max_levels=max_levels)))
    bad = 0
    for k, got in answers.items():
        cand, sizes = want[int(rows[k])]
        if not (np.array_equal(got.candidates, cand)
                and np.array_equal(got.block_sizes, sizes)):
            bad += 1
    return {"probes_diff": (bad, 0)}


def prepare(run: common.Run) -> dict:
    """Set-up: the stored records and the probe pool from the seed, their
    keys, the store loaded with one ingest, and every rung warmed."""
    cfg, traffic = run.cfg, run.traffic
    with common.span("bench.generate"):
        stored, _ = corpus.records(cfg, run.seed)
        pool = make_pool(cfg, traffic, stored, run.seed)
    store_k, store_v = program_keys(cfg, stored)
    pool_k, pool_v = program_keys(cfg, pool)
    svc = make_service(cfg)
    t = time.perf_counter()
    svc.submit_ingest(TENANT, store_k, store_v)
    svc.run()
    run.log(f"ingested {cfg['records']} records in "
            f"{time.perf_counter() - t:.3f}s")
    warm_up(run, svc, pool_k, pool_v, traffic["include_probe"])
    return {"svc": svc, "stored": stored, "pool": pool, "keys": pool_k,
            "valid": pool_v}


def window(run: common.Run, ctx: dict, rate: float, seconds: float,
           seed: int) -> dict:
    """Probes at ``rate`` for ``seconds``, drained."""
    offsets, rows = arrivals(rate, seconds, seed, run.traffic["pool"])
    got = drive(ctx["svc"], ctx["keys"], ctx["valid"], offsets, rows,
                run.traffic["include_probe"], run.traffic["drain_s"])
    got["rows"] = rows
    return got


def summary(got: dict) -> dict:
    """What a window's log line reports."""
    ms = got["latency"].tolist()
    late = got["late"][~np.isnan(got["late"])]
    steps = got["steps"]
    return {"probes": len(ms), "failed": int(np.isinf(got["latency"]).sum()),
            "rejections": got["rejections"],
            "p50_ms": percentile(ms, 50) * 1e3,
            "p99_ms": percentile(ms, 99) * 1e3,
            "steps": len(steps),
            "rows_per_step": (sum(r for *_, r in steps) / len(steps)
                              if steps else 0.0),
            "longest_step_ms": got["longest_step"] * 1e3,
            "late_max_ms": (late.max() if len(late) else 0.0) * 1e3,
            "stalls": sorted(got["stalls"], key=lambda x: -x["seconds"])[:3]}


def run(run: common.Run) -> dict:
    cfg, traffic = run.cfg, run.traffic
    ctx = prepare(run)
    seconds = traffic["trace_seconds"] if run.trace else run.seconds
    snap = run.compiles.snapshot()
    out = {"metrics": {}, "layer": {}, "setup_compiles": snap}
    if not run.trace:
        got = window(run, ctx, traffic["rate_per_s"], seconds, run.seed)
        out["setup_end"] = got["start"]
        ms = got["latency"].tolist()
        out["metrics"]["probe_p50_ms"] = percentile(ms, 50) * 1e3
        out["metrics"]["probe_p99_ms"] = percentile(ms, 99) * 1e3
    else:
        with common.traced(run, out):
            got = window(run, ctx, traffic["rate_per_s"], seconds, run.seed)
        out["layer"] = {"trace": out["trace"], "steps": got["steps"],
                        "wait": got["wait"]}
    out["window_compiles"] = run.compiles.since(snap)
    out["peak_bytes"] = common.peak_bytes()
    out["attempted"] = len(got["latency"])
    out["failed"] = int(np.isinf(got["latency"]).sum())
    run.log("window " + json.dumps(summary(got)))
    rows, answers, lost = got["rows"], got["answers"], got["lost"]
    stored, pool = ctx["stored"], ctx["pool"]
    del ctx
    # a probe that never comes back breaks "no probe is dropped"; one
    # never admitted or shed came back explicitly and is only failed
    out["finish"] = lambda: dict(check(cfg, stored, pool, rows, answers),
                                 probes_lost=(lost, 0))
    return out
