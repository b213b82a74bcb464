"""99th percentile of every gap between consecutive tokens of a request,
over all requests, where the later token came inside the window, in ms.
Two tokens one step delivers together (the first token of a prompt that
finished prefill, and the decode of the same step) are a gap of 0.

99th and not 95th: in the chat cell about 6% of the gaps fall in steps
that carry two 512-token prefill chunks (about 240 ms) and most of the
rest in steps with one chunk or none (165 ms or less), so the 95th
percentile sits on the edge between the two and jumps between them from
one run of a seed to the next; the 99th lies inside the two-chunk mode."""
import layer_stats


def read(ctx):
    v = [1e3 * (b - a) for tr in ctx.drv.tracked
         for a, b in zip(tr.times, tr.times[1:])
         if ctx.t_open <= b <= ctx.t_close]
    return layer_stats.percentile(v, 99)
