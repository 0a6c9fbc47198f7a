"""Blocking stage (``data.pipeline``): the program's own
``DedupReport.blocking_seconds`` per job (key build, HDB, block build and
pair dedupe), in milliseconds. Less ``hdb_device_ms`` and
``pairs_device_ms``, this is the host's share of blocking."""


def read(ctx):
    jobs = ctx.get("jobs")
    if not jobs:
        return None
    return sum(r.blocking_seconds for r in jobs) / len(jobs) * 1e3
