"""Generated tokens produced inside the window, over the window's seconds:
every token of a task that ended inside it, and of a task the close cuts
those its step calls before the close produced (``Window.tokens_in_window``)."""


def read(ctx):
    return ctx.win.tokens_in_window() / ctx.win.seconds
