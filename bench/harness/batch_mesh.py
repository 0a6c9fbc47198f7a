"""Closed-loop batch jobs over a mesh of chips: ``data.pipeline.dedup_corpus``
back to back, each on fresh copies of the columns placed over the
configuration's chips, rows in order.

The placement is the deployment: ``dedup_corpus`` runs its sharded job
because the columns arrive split by record over several devices. Set-up
refuses, before any job, a program whose ``DedupReport`` has no
``shards`` field: such a program cannot run the sharded job, and would
only partition one device's work over the mesh. Then it generates one
corpus from the seed and runs one warm-up job on it, which compiles
every program the window runs. The window and the check are
``batch``'s: the same closed loop, the same comparison with the plain
reference (``batch.reference_answer``, ``compare``, ``check``), plus
three counts that must stay 0: routed pair dedupes that fell back to
the single-device engine, HDB entries dropped for buffer capacity, and
jobs that did not run on every chip.

The per-layer readers of the cell read ``layer``: the trace, the traced
jobs' reports (``DedupReport.exchanges`` holds each all_to_all's counts)
and the device kind, for the interconnect peak below.
"""
from __future__ import annotations

import dataclasses
import re
import time

import numpy as np

from . import batch, common, corpus
from .stats import rate
from .trace import _clip

# one TPU v5e chip's inter-chip interconnect: 1,600 Gbit/s (Google Cloud
# documentation, "TPU v5e"), in bytes per second
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}

# bytes of one live entry that an all_to_all moves, by exchange stage:
# an HDB entry is a u64 block key and an int32 record id, a pair round's
# entry one 62-bit sort word
ENTRY_BYTES = {"hdb": 12, "pairs": 8}

# collective operations by their name in a TPU trace's "XLA Ops" line:
# the HLO instruction's name, which follows the JAX primitive
# (``%all_to_all.59``, ``%psum.70``, ``%pmax.7``) or the XLA opcode an
# all_gather became (``%all-reduce.27``), sometimes with its text after
# " = "
COLLECTIVE = re.compile(r"(all[-_]to[-_]all|all[-_]reduce|all[-_]gather"
                        r"|reduce[-_]scatter|collective[-_]permute"
                        r"|psum|pmax|pmin)")
ALL_TO_ALL = re.compile(r"all[-_]to[-_]all")


def op_kind(name: str) -> str:
    """A trace operation's instruction name: ``"%psum.70 = ..."`` ->
    ``"psum.70"``."""
    return name.split(" = ")[0].lstrip("%")


EXTRA_LIMITS = {"pair_fallbacks": 0, "route_overflows": 0,
                "unsharded_jobs": 0}


def op_seconds(trace: dict, pattern: re.Pattern) -> float:
    """Device seconds of the operations whose instruction name starts
    with ``pattern`` inside the window, summed per device and averaged
    over devices."""
    if not trace["devices"]:
        return 0.0
    total = 0.0
    for dev in trace["devices"]:
        ops = [e for e in dev["ops"] if pattern.match(op_kind(e[0]))]
        total += sum(e - s for _, s, e in _clip(ops, trace["window"]))
    return total / len(trace["devices"]) / 1e9


def off_chip_bytes(exchanges) -> int:
    """Bytes of the live entries the exchanges moved from one chip to
    another: each ``sent`` count up to its bucket's capacity, off the
    diagonal, times the stage's entry size."""
    total = 0
    for ex in exchanges:
        sent = np.minimum(np.asarray(ex.sent, np.int64), ex.cap)
        total += (int(sent.sum()) - int(np.trace(sent))) \
            * ENTRY_BYTES[ex.stage]
    return total


def refuse_unsharded_program() -> None:
    """Exit at once where the program's ``DedupReport`` has no ``shards``
    field: it has no sharded job to measure."""
    from repro.data import pipeline

    fields = {f.name for f in dataclasses.fields(pipeline.DedupReport)}
    if "shards" not in fields:
        raise SystemExit("batch_mesh: this program's DedupReport has no "
                         "'shards' field, so dedup_corpus has no sharded "
                         "job; the cell cannot run")


def make_mesh(cfg: dict):
    import jax
    from jax.sharding import Mesh

    layout = cfg["layout"]
    devices = jax.devices()[:layout["chips"]]
    if len(devices) < layout["chips"] or cfg["records"] % layout["chips"]:
        raise SystemExit(f"batch_mesh: {cfg['records']} records over "
                         f"{layout['chips']} chips, {len(devices)} found")
    return Mesh(np.array(devices), (layout["axis"],))


def make_job(cfg: dict, columns: dict, entity_id: np.ndarray, mesh):
    """The timed call: one dedup job over fresh copies of ``columns``
    placed over ``mesh``, rows in order. Returns the program's report."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.blocks import TokenColumn
    from repro.data import pipeline, synthetic

    blocking = common.program_blocking(cfg)
    hcfg, mcfg = common.hdb_config(cfg), batch._matcher_config(cfg)
    rows = NamedSharding(mesh, P(cfg["layout"]["axis"], None))

    def job():
        with common.span("bench.job"):
            placed = {name: TokenColumn(jax.device_put(tok, rows),
                                        jax.device_put(mask, rows))
                      for name, (tok, mask) in columns.items()}
            data = synthetic.Corpus(columns=placed, blocking=blocking,
                                    entity_id=entity_id,
                                    num_records=len(entity_id))
            return pipeline.dedup_corpus(
                data, hcfg, mcfg, pair_budget=cfg["pairs"]["budget"],
                cc_max_rounds=cfg["cc_max_rounds"])

    return job


def distinct(answers: list) -> list:
    """``answers`` without repeats: equal answers compare equally with
    the reference, so each distinct one is compared once."""
    out: list = []
    for got in answers:
        if not any(all(np.array_equal(got[k], seen[k]) for k in got)
                   for seen in out):
            out.append(got)
    return out


def check(cfg: dict, columns: dict, answers: list, reports: list) -> dict:
    """``batch.check`` over the distinct answers, plus the sharded job's
    own counts: ``{name: (value, limit)}``."""
    out = batch.check(cfg, columns, distinct(answers))
    chips = cfg["layout"]["chips"]
    extra = {
        "pair_fallbacks": sum(r.pair_fallbacks for r in reports),
        "route_overflows": sum(r.route_overflows for r in reports),
        "unsharded_jobs": sum(r.shards != chips for r in reports),
    }
    out.update({k: (v, EXTRA_LIMITS[k]) for k, v in extra.items()})
    return out


def run(run: common.Run) -> dict:
    refuse_unsharded_program()
    import jax

    cfg = run.cfg
    n = cfg["records"]
    mesh = make_mesh(cfg)
    with common.span("bench.generate"):
        columns, entity_id = corpus.records(cfg, run.seed)
    job = make_job(cfg, columns, entity_id, mesh)
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
        out["layer"] = {"trace": out["trace"], "jobs": reports,
                        "device_kind": jax.devices()[0].device_kind}
    out["window_compiles"] = run.compiles.since(snap)
    out["peak_bytes"] = common.peak_bytes()
    out["attempted"] = len(reports)
    answers = [batch.answer_of(r) for r in reports]
    out["finish"] = lambda: check(cfg, columns, answers, reports)
    return out
