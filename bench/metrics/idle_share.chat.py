"""Share of the window in which no operation ran on the device: 1 - the
union of op intervals over the window, averaged over the chips in use."""
import layer_stats


def read(ctx):
    return layer_stats.idle_share(ctx)
