"""Mean host milliseconds per placement decision: the harness's span
around ``ServingCluster.scheduler.plan``, one per request."""


def read(ctx):
    plans = ctx.win.plans
    return 1e3 * sum(plans) / len(plans) if plans else None
