"""Finds a cell's configuration, traffic mix and per-layer metrics by name.

Everything a cell needs sits in files of its own under the checkout
root: ``BENCHMARK.json`` names the cell, its configuration (whose entry
gives the file) and its traffic mix (``bench/traffic/<traffic>.json``);
each per-layer metric is a reader ``bench/metrics/<metric>.py`` with a
``read(ctx)`` function. A cell is added by adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import os


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(root: str, bench: dict, name: str) -> dict:
    entry = _named(bench["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def end_to_end(bench: dict, cell_name: str) -> list:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer(root: str, bench: dict, cell_name: str) -> list:
    """``(metric entry, read function)`` for this cell's per-layer metrics:
    those whose ``workloads`` list the cell."""
    out = []
    for m in bench["per_layer"]:
        if cell_name not in m["workloads"]:
            continue
        path = os.path.join(root, "bench", "metrics", f"{m['name']}.py")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        out.append((m, mod.read))
    return out
