"""What both traffic loops share: the run's settings, compile counting, spans,
tracing, device facts, and the bridge from generated columns to the
program's entry-point types."""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
import time

from . import trace as trace_mod

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Compiles:
    """Counts compilations (a persistent-cache hit included: it still
    loads a program that was not in memory) and cache hits and misses."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1
        elif event == CACHE_MISS_EVENT:
            self.misses += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses, "n_names": len(self.names)}

    def since(self, snap: dict) -> dict:
        now = self.snapshot()
        out = {k: now[k] - snap[k] for k in ("compiles", "cache_hits",
                                             "cache_misses")}
        out["programs"] = sorted(set(self.names[snap["n_names"]:]))
        return out


@dataclasses.dataclass
class Run:
    root: str
    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t0: float                  # process start, perf_counter
    compiles: Compiles
    cache_dir: str = ""

    def log(self, msg: str) -> None:
        print(f"[{time.perf_counter() - self.t0:8.2f}s] {msg}",
              file=sys.stderr, flush=True)


def span(name: str):
    """A harness span, written into the profiler's trace while tracing."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(run: Run, out: dict):
    """Profile the enclosed work; ``out["trace"]`` gets the reduced trace."""
    import jax

    path = os.path.join(run.root, ".bench_trace", run.cell["name"])
    shutil.rmtree(path, ignore_errors=True)
    # no Python function tracing: it would slow the host-bound paths the
    # traced window measures; the harness spans are recorded without it
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=options)
    try:
        with span(trace_mod.WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()
    out["trace"] = trace_mod.load(path)
    shutil.rmtree(path, ignore_errors=True)


def peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def program_blocking(cfg: dict) -> dict:
    from repro.core.blocks import ColumnBlocking

    out = {}
    for name, spec in cfg["blocking"].items():
        if spec["kind"] == "lsh":
            out[name] = ColumnBlocking.lsh(spec["bands"], spec["rows_per_band"])
        else:
            out[name] = getattr(ColumnBlocking, spec["kind"])()
    return out


def program_columns(columns: dict) -> dict:
    """Fresh device arrays of the generated columns (explicit uploads)."""
    import jax.numpy as jnp
    from repro.core.blocks import TokenColumn

    return {name: TokenColumn(jnp.asarray(tok), jnp.asarray(mask))
            for name, (tok, mask) in columns.items()}


def hdb_config(cfg: dict):
    from repro.core.hdb import HDBConfig

    return HDBConfig(**cfg["hdb"])
