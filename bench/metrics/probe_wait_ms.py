"""Service scheduler (``serving.service``): mean time from a probe's due
time to the start of the step that served it, in milliseconds."""
import math


def read(ctx):
    wait = [w for w in ctx.get("wait", []) if not math.isnan(w)]
    if not wait:
        return None
    return sum(wait) / len(wait) * 1e3
