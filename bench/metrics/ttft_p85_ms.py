"""85th percentile, over every request whose first token came inside the
window, of first token minus the request's scheduled arrival, in ms.
85th and not 90th: the open-loop cell's window holds some tens of first
tokens, and a percentile wants several samples beyond it."""
import layer_stats


def read(ctx):
    v = [1e3 * (tr.times[0] - tr.due) for tr in ctx.drv.tracked
         if tr.times and ctx.t_open <= tr.times[0] <= ctx.t_close]
    return layer_stats.percentile(v, 85)
