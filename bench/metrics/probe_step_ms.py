"""Probe walk (``streaming.delta.query_keys`` via ``serving.service``):
mean wall time of a ``step()`` that served probes, from the harness's
clock around each step, in milliseconds."""


def read(ctx):
    steps = ctx.get("steps")
    if not steps:
        return None
    return sum(s1 - s0 for s0, s1, _ in steps) / len(steps) * 1e3
