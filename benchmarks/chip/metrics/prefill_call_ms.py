"""Mean device milliseconds per execution of the served path's
whole-prompt prefill program (one per task of a dense model).  Nothing
where the program never ran."""
from chipbench import prefill_work, trace


def read(ctx):
    if ctx.trace is None:
        return None
    d = trace.step_durations_ns(ctx.trace, prefill_work.PROGRAM)
    return sum(d) / len(d) / 1e6 if d else None
