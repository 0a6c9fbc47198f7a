"""HDB iteration (``core.hdb``): device milliseconds per job of the two
jitted HDB steps, summed from the profiler trace's compiled programs."""
from bench.harness.trace import module_seconds

PROGRAMS = ("_count_step", "_intersect_step")


def read(ctx):
    jobs, tr = ctx.get("jobs"), ctx.get("trace")
    if not jobs or tr is None:
        return None
    seconds = module_seconds(tr)
    total = sum(seconds.get(p, 0.0) for p in PROGRAMS)
    return total / len(jobs) * 1e3 if total > 0 else None
