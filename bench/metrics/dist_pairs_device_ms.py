"""Routed pair dedupe (``core.distributed``, ``kernels.pairs``): device
milliseconds per job of the pair rounds (the compiled ``local_round``:
slot decode, sort-word pack, routing and its all_to_all) and the
shard-local dedupe sort (``local_dedupe``), from the profiler trace,
averaged over the chips."""
from bench.harness.trace import module_seconds

PROGRAMS = ("local_round", "local_dedupe")


def read(ctx):
    jobs, tr = ctx.get("jobs"), ctx.get("trace")
    if not jobs or tr is None:
        return None
    seconds = module_seconds(tr)
    total = sum(seconds.get(p, 0.0) for p in PROGRAMS)
    return total / len(jobs) * 1e3 if total > 0 else None
