"""Program spans: named host intervals written into the profiler's trace.

A span is a ``jax.profiler.TraceAnnotation``. While ``jax.profiler``
traces, it lands on the trace's host plane, on the clock of the device
operations, with its keyword arguments as event stats; while it does not,
a span costs about a microsecond. Names follow ``repro.<layer>.<stage>``.

A span never waits for the device: around an asynchronous dispatch it
times the host's share, and the trace's device lines time the device's.
A count rides on a span as an argument only once it is on the host; a
count known only after the work it counts goes on a zero-length marker,
``<span>.counts``, emitted right after that work (``mark``).

``docs/PIPELINE.md`` ("Tracing") lists the spans and how to read them.
"""
from __future__ import annotations

import resource
import time

import jax


class span:
    """``with span(name, **args) as s:`` writes a span around the block;
    ``s.seconds`` is then its wall time, read inside the span."""

    __slots__ = ("_trace", "_t0", "seconds")

    def __init__(self, name: str, **args):
        self._trace = jax.profiler.TraceAnnotation(name, **args)
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)


def mark(name: str, **args) -> None:
    """A zero-length span that carries ``args``."""
    with jax.profiler.TraceAnnotation(name, **args):
        pass


def thread_usage() -> tuple:
    """The calling thread's CPU nanoseconds, involuntary and voluntary
    context switches, and major and minor page faults, so far (Linux)."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return (time.thread_time_ns(), ru.ru_nivcsw, ru.ru_nvcsw, ru.ru_majflt,
            ru.ru_minflt)
