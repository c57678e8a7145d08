"""The decode step's share (%) of the chip's peak on the resource that
binds it: max(FLOPs / peak FLOP/s, bytes / peak HBM B/s) over the mean
device time per step.  FLOPs and bytes come from the configuration's
shapes (``chipbench/work.py``) at the mix's mean context."""
from chipbench import trace, work


def read(ctx):
    if ctx.trace is None or ctx.step_module is None:
        return None
    d = trace.step_durations_ns(ctx.trace, ctx.step_module)
    if not d:
        return None
    step_s = sum(d) / len(d) / 1e9
    conf = ctx.spec.conf
    flops, nbytes = work.decode_step(conf["model"], conf["family"],
                                     work.mean_context(ctx.spec.mix))
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step_s
