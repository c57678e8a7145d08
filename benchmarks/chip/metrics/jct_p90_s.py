"""90th-percentile JCT (s) over every request due in the window."""
from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.win.jcts(), 90)
