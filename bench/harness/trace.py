"""Reduction of a profiler trace to device busy time, per-program device
time and idle gaps named by the harness span open during them.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a plain
dict, which tests can also build by hand:

    {"window": [start_ns, end_ns],
     "devices": [{"ops": [[name, start_ns, dur_ns], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "spans": [[name, start_ns, dur_ns], ...]}

``devices`` holds one entry per accelerator plane: its ``XLA Ops`` line
(each operation the device ran) and its ``XLA Modules`` line (each
compiled program, named ``jit_<function>(<id>)``). ``spans`` are the
harness's own ``jax.profiler.TraceAnnotation`` spans (names starting
``bench.``) from the host plane, on the same clock. ``window`` is the
span ``bench.trace``, which encloses the traced work.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.trace"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            if dev["ops"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith("bench."))
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if not window:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w = max(window, key=lambda s: s[2])
    return {"window": [w[1], w[1] + w[2]], "devices": devices, "spans": spans}


def _clip(events, window):
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def _union(intervals):
    merged = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def busy_s(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace["devices"]:
        return 0.0
    total = 0.0
    for dev in trace["devices"]:
        total += sum(e - s for s, e in _union(_clip(dev["ops"],
                                                    trace["window"])))
    return total / len(trace["devices"]) / 1e9


def idle_share(trace: dict):
    """1 - busy / window, or None where no device operation was traced."""
    busy = busy_s(trace)
    if busy <= 0:
        return None
    return 1.0 - busy / window_s(trace)


_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def module_name(raw: str) -> str:
    """``jit__count_step(123)`` -> ``_count_step``."""
    return _MODULE.match(raw).group(1)


def module_seconds(trace: dict) -> dict:
    """Device seconds per compiled program inside the window, summed over
    devices and divided by their number."""
    out: dict = {}
    for dev in trace["devices"]:
        for name, s, e in _clip(dev["modules"], trace["window"]):
            key = module_name(name)
            out[key] = out.get(key, 0.0) + (e - s) / 1e9
    n = max(len(trace["devices"]), 1)
    return {k: v / n for k, v in out.items()}


def idle_gaps(trace: dict, top: int = 10):
    """The ``top`` longest idle gaps of the first device, each named by
    the innermost harness span that covers its middle."""
    if not trace["devices"]:
        return []
    lo, hi = trace["window"]
    busy = _union(_clip(trace["devices"][0]["ops"], trace["window"]))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [(n, s, s + d) for n, s, d in trace["spans"] if n != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover \
            else "outside any span"
        out.append([name, (e - s) / 1e9])
    return out


def breakdown(trace: dict, top: int = 10) -> dict:
    ops = sorted(module_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": idle_gaps(trace, top)}
