"""Device idle share over the traced sharded batch job: 1 - (union of
device operation intervals, averaged over the chips) / traced window,
in percent. Reads nothing where no traced job ran sharded."""
from bench.harness.trace import idle_share


def read(ctx):
    jobs = ctx.get("jobs")
    if not jobs or ctx.get("trace") is None \
            or not all(getattr(r, "shards", 1) > 1 for r in jobs):
        return None
    share = idle_share(ctx["trace"])
    return None if share is None else share * 100.0
