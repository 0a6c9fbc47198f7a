"""Sharded HDB step (``core.distributed``): device milliseconds per job of
the shard-mapped HDB iteration (the compiled ``local_step``: sketch,
psum, both routed exchanges, exact counts, Bloom filter, survivor map
and intersection), from the profiler trace, averaged over the chips."""
from bench.harness.trace import module_seconds

PROGRAMS = ("local_step",)


def read(ctx):
    jobs, tr = ctx.get("jobs"), ctx.get("trace")
    if not jobs or tr is None:
        return None
    seconds = module_seconds(tr)
    total = sum(seconds.get(p, 0.0) for p in PROGRAMS)
    return total / len(jobs) * 1e3 if total > 0 else None
