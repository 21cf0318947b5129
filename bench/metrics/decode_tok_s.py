"""Output tokens emitted inside the window, over the window's length."""


def read(ctx):
    n = sum(1 for r in ctx.window.records for t in r.token_s
            if t < ctx.seconds)
    return n / ctx.seconds
