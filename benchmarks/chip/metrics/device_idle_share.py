"""Share (%) of the time requests were being served (the union of the
harness's ``submit`` spans) in which no operation ran on the device.
Gaps between requests do not count."""
from chipbench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    serving = trace.union(ctx.trace.host_spans("submit"))
    total = trace.length(serving)
    if total <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_in(ctx.trace, serving) / total)
