"""Output tokens the engine emitted inside the window, over the window."""


def read(ctx):
    n = sum(1 for tr in ctx.drv.tracked for t in tr.times
            if ctx.t_open <= t <= ctx.t_close)
    return n / (ctx.t_close - ctx.t_open) if n else None
