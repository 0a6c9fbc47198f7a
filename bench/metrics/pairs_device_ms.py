"""Pair engine (``core.pairs``, ``kernels.pairs``, ``kernels.sort``):
device milliseconds per job of pair-slot decoding and the dedupe sort,
summed from the profiler trace's compiled programs."""
from bench.harness.trace import module_seconds

PROGRAMS = ("decode_block_local", "decode_chunk", "dedupe_device")


def read(ctx):
    jobs, tr = ctx.get("jobs"), ctx.get("trace")
    if not jobs or tr is None:
        return None
    seconds = module_seconds(tr)
    total = sum(seconds.get(p, 0.0) for p in PROGRAMS)
    return total / len(jobs) * 1e3 if total > 0 else None
