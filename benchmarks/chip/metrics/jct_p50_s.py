"""Median JCT (s): due to done, over every request due in the window;
one that never completed counts as infinitely late."""
from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.win.jcts(), 50)
