"""85th percentile of admission (the engine's Request.t_admit) minus the
scheduled arrival, over requests admitted inside the window, in ms."""
import layer_stats


def read(ctx):
    v = [1e3 * (tr.req.t_admit - tr.due) for tr in ctx.drv.tracked
         if ctx.t_open <= tr.req.t_admit <= ctx.t_close]
    return layer_stats.percentile(v, 85)
