"""Process start to the window's opening: imports, device start-up,
weights, cluster, warm-up and any compilation."""


def read(ctx):
    return ctx.setup_s
