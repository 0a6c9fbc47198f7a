"""Closed-loop batch jobs: ``data.pipeline.dedup_corpus`` back to back.

Set-up generates one corpus from the seed and runs one warm-up job on
it, which compiles every program the window runs (every job is the same
corpus). The window runs jobs back to back, each on fresh device copies
of the columns; the job in flight when the window's seconds are up runs
to its end and counts. The check compares each job's blocks, candidate
pairs, matched-pair count, cluster labels and survivors with the plain
reference over the same corpus.
"""
from __future__ import annotations

import time

import numpy as np

from . import common, corpus
from .stats import rate
from ..reference import hdb as ref_hdb
from ..reference import keys as ref_keys
from ..reference import pairs as ref_pairs


def _matcher_config(cfg: dict):
    from repro.data.matcher import MatcherConfig

    m = cfg["matcher"]
    return MatcherConfig(threshold=m["threshold"],
                         weights=tuple((n, w) for n, w in m["weights"]))


def make_job(cfg: dict, columns: dict, entity_id: np.ndarray):
    """The timed call: one dedup job over fresh device copies of
    ``columns``. Returns the program's report with host outputs only."""
    from repro.data import pipeline, synthetic

    blocking = common.program_blocking(cfg)
    hcfg, mcfg = common.hdb_config(cfg), _matcher_config(cfg)

    def job():
        with common.span("bench.job"):
            data = synthetic.Corpus(columns=common.program_columns(columns),
                                    blocking=blocking, entity_id=entity_id,
                                    num_records=len(entity_id))
            rep = pipeline.dedup_corpus(
                data, hcfg, mcfg, pair_budget=cfg["pairs"]["budget"],
                cc_max_rounds=cfg["cc_max_rounds"])
            pset = rep.pairs
            if pset.device_a is not None:
                pset.device_a.block_until_ready()
                pset.device_b.block_until_ready()
            pset.device_a = pset.device_b = None
        return rep

    return job


def ranges(start: np.ndarray, size: np.ndarray) -> np.ndarray:
    """Concatenated ``arange(s, s + n)`` over ``zip(start, size)``."""
    size = np.asarray(size, np.int64)
    first = np.cumsum(size) - size
    return np.repeat(np.asarray(start, np.int64) - first, size) + \
        np.arange(int(size.sum()), dtype=np.int64)


def answer_of(rep) -> dict:
    """A program report in the form the comparison reads."""
    blk = rep.blocks
    key = (blk.key_hi.astype(np.uint64) << np.uint64(32)) | \
        blk.key_lo.astype(np.uint64)
    idx = ranges(blk.start, blk.size)
    members = np.stack([np.repeat(key, blk.size),
                        blk.members[idx].astype(np.uint64)], axis=1)
    p = rep.pairs
    return {"blocks": members,
            "pairs": np.stack([p.a, p.b, p.src_size], axis=1),
            "pairs_exact": bool(p.exact), "pairs_total": int(p.total_slots),
            "matched": int(rep.num_matched_pairs),
            "label": np.asarray(rep.component_of, np.int64),
            "survivors": np.asarray(rep.survivors, np.int64)}


def reference_answer(cfg: dict, columns: dict, precision: str | None = None
                     ) -> dict:
    """The plain reference's answer; ``precision`` overrides the
    matcher's configured one (the control scores in bfloat16)."""
    keys, valid = ref_keys.build_keys(columns, cfg["blocking"])
    blocking = ref_hdb.hdb(cfg["hdb"], keys, valid)
    start, size, members = ref_pairs.blocks(blocking)
    idx = ranges(start, size)
    a, b, src, exact, total = ref_pairs.candidate_pairs(
        blocking, cfg["pairs"]["budget"], cfg["pairs"]["sample_seed"])
    m = cfg["matcher"]
    hit = ref_pairs.match(columns, m["weights"], m["threshold"], a, b,
                          dtype=precision or m["precision"])
    n = len(next(iter(columns.values()))[0])
    label, survivors = ref_pairs.clusters(n, a[hit], b[hit])
    return {"blocks": np.stack([blocking.key[idx],
                                members[idx].astype(np.uint64)], axis=1),
            "pairs": np.stack([a, b, src], axis=1),
            "pairs_exact": bool(exact), "pairs_total": int(total),
            "matched": int(hit.sum()), "label": label,
            "survivors": survivors}


def row_diff(x: np.ndarray, y: np.ndarray) -> int:
    """Rows in one of two row sets and not in the other."""
    if x.shape == y.shape and np.array_equal(
            x[np.lexsort(x.T[::-1])], y[np.lexsort(y.T[::-1])]):
        return 0
    both = np.concatenate([np.unique(x, axis=0), np.unique(y, axis=0)])
    _, counts = np.unique(both, axis=0, return_counts=True)
    return int((counts == 1).sum()) + (len(x) - len(np.unique(x, axis=0))) \
        + (len(y) - len(np.unique(y, axis=0)))


def compare(got: dict, want: dict) -> dict:
    """Numbers of disagreements with the reference, by output."""
    lab_g, lab_w = got["label"], want["label"]
    return {
        "blocks_diff": row_diff(got["blocks"], want["blocks"]),
        "pairs_diff": row_diff(got["pairs"], want["pairs"])
        + int(got["pairs_exact"] != want["pairs_exact"])
        + int(got["pairs_total"] != want["pairs_total"]),
        "matched_diff": abs(got["matched"] - want["matched"]),
        "labels_diff": int((lab_g != lab_w).sum()) if len(lab_g) == len(lab_w)
        else max(len(lab_g), len(lab_w)),
        "survivors_diff": len(np.setxor1d(got["survivors"], want["survivors"])),
    }


LIMITS = {"blocks_diff": 0, "pairs_diff": 0, "matched_diff": 0,
          "labels_diff": 0, "survivors_diff": 0}


def check(cfg: dict, columns: dict, answers: list) -> dict:
    """Worst disagreement over ``answers`` against the reference, with
    its limit: ``{name: (value, limit)}``."""
    want = reference_answer(cfg, columns)
    worst = dict.fromkeys(LIMITS, 0)
    for got in answers:
        for k, v in compare(got, want).items():
            worst[k] = max(worst[k], v)
    return {k: (worst[k], LIMITS[k]) for k in LIMITS}


def run(run: common.Run) -> dict:
    cfg = run.cfg
    n = cfg["records"]
    with common.span("bench.generate"):
        columns, entity_id = corpus.records(cfg, run.seed)
    job = make_job(cfg, columns, entity_id)
    t = time.perf_counter()
    job()
    run.log(f"warm-up job {time.perf_counter() - t:.3f}s")
    snap = run.compiles.snapshot()
    out = {"attempted": 0, "failed": 0, "metrics": {}, "layer": {},
           "setup_compiles": snap}
    reports = []
    if not run.trace:
        start = time.perf_counter()
        out["setup_end"] = start
        end = start
        while end - start < run.seconds:
            reports.append(job())
            end = time.perf_counter()
            run.log(f"job {len(reports)} ends at {end - start:.3f}s")
        out["metrics"]["records_per_s"] = rate(len(reports) * n, end - start)
    else:
        with common.traced(run, out):
            for _ in range(run.traffic["trace_jobs"]):
                reports.append(job())
        out["layer"] = {"trace": out["trace"], "jobs": reports}
    out["window_compiles"] = run.compiles.since(snap)
    out["peak_bytes"] = common.peak_bytes()
    out["attempted"] = len(reports)
    answers = [answer_of(r) for r in reports]
    out["finish"] = lambda: check(cfg, columns, answers)
    return out
