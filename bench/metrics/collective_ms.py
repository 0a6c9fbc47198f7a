"""Exchanges between chips: device milliseconds per job of the
collective operations (all-to-all, all-reduce and all-gather, by the
operation's name in the trace), summed on each chip and averaged over
the chips."""
from bench.harness.batch_mesh import COLLECTIVE, op_seconds


def read(ctx):
    jobs, tr = ctx.get("jobs"), ctx.get("trace")
    if not jobs or tr is None:
        return None
    seconds = op_seconds(tr, COLLECTIVE)
    return seconds / len(jobs) * 1e3 if seconds > 0 else None
