"""Host milliseconds per decode iteration of the engine loop: the engine's
own admit + dispatch + sample_copy phase spans (repro.obs, host clock)
summed over the window, over the number of decode dispatches."""
import layer_stats


def read(ctx):
    parts = [layer_stats.phase_ms(ctx, p)
             for p in ("admit", "dispatch", "sample_copy")]
    if any(p is None for p in parts):
        return None
    return sum(p[0] for p in parts) / parts[1][1]
