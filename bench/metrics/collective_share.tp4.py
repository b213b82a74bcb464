"""Share of the window in which a collective runs on a chip and no other
op does, mean over the chips in use, in %: the device time the
tensor-parallel step spends waiting on its exchanges (two all-reduces per
layer, the logits all-gather). Collectives are found by HLO opcode in the
op text (bench/phase_reduce.py)."""
import phase_reduce


def read(ctx):
    return phase_reduce.collective_share(ctx.trace, ctx.planes)
