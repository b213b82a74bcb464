"""Device milliseconds of one execution of the decode program, averaged
over the executions the trace of the window holds (first chip in use)."""
import layer_stats


def read(ctx):
    got = layer_stats.module_ms(ctx, layer_stats.DECODE_PROGRAM)
    return None if got is None else got[0] / got[1]
