"""Match and clusters (``data.matcher``, ``kernels.match``,
``data.components``): the program's ``matching_seconds +
partition_seconds`` per job, in milliseconds."""


def read(ctx):
    jobs = ctx.get("jobs")
    if not jobs:
        return None
    return sum(r.matching_seconds + r.partition_seconds
               for r in jobs) / len(jobs) * 1e3
