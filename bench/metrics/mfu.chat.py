"""Model FLOPs utilization of the whole step (layer_stats.mfu)."""
import layer_stats


def read(ctx):
    return layer_stats.mfu(ctx)
