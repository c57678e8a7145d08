"""Mean device milliseconds per execution of the engine's step program,
found in the trace by the module name read from the engine's own step."""
from chipbench import trace


def read(ctx):
    if ctx.trace is None or ctx.step_module is None:
        return None
    d = trace.step_durations_ns(ctx.trace, ctx.step_module)
    return sum(d) / len(d) / 1e6 if d else None
