"""Device idle share over the traced probe window: 1 - (union of device
operation intervals) / traced window, in percent."""
from bench.harness.trace import idle_share


def read(ctx):
    if not ctx.get("steps") or ctx.get("trace") is None:
        return None
    share = idle_share(ctx["trace"])
    return None if share is None else share * 100.0
