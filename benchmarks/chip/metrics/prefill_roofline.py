"""The prefill program's share (%) of the chip's peak on the resource
that binds it: the mix's mean over its prefill calls of max(FLOPs / peak
FLOP/s, bytes / peak HBM B/s), over the mean device time per execution.
FLOPs and bytes come from the configuration's shapes
(``chipbench/prefill_work.py``).  Nothing where the program never ran."""
from chipbench import prefill_work, trace


def read(ctx):
    if ctx.trace is None:
        return None
    d = trace.step_durations_ns(ctx.trace, prefill_work.PROGRAM)
    if not d:
        return None
    conf = ctx.spec.conf
    least = prefill_work.least_s(conf["model"], conf["family"], ctx.spec.mix,
                                 ctx.peaks)
    return 100.0 * least / (sum(d) / len(d) / 1e9)
