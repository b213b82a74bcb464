"""Device milliseconds of the prefill programs per 1000 prompt tokens: the
trace's prefill executions in the window over the prompt tokens of the
requests whose prefill completed in it (first chip in use)."""
import layer_stats


def read(ctx):
    got = layer_stats.module_ms(ctx, layer_stats.PREFILL_PROGRAM)
    toks = sum(s.prompt_tokens for s in layer_stats.steps(ctx))
    if got is None or not toks:
        return None
    return got[0] / (toks / 1000.0)
