"""Share of its roofline the fused SwiGLU gate kernel
(kernels/cordic_act.py, ``_silu_mul_kernel``) reached: reading g and u
and writing h at bf16 for every prompt and decoded token of the window,
in every layer, over the kernel's summed device time (first chip).

The trace names a Pallas kernel only by its HLO instruction, so the
kernel is found by its signature: the ``tpu_custom_call`` that takes two
bf16 (rows, 1024) tiles of the same shape and returns a third
(kernels/ops.py lays g and u out 1024 wide)."""
import costs
import layer_stats

KERNEL = (r"^%\S+ = bf16\[(\d+),1024\]\S* custom-call\("
          r"bf16\[\1,1024\]\S* %\S+, bf16\[\1,1024\]\S* %\S+\), "
          r'custom_call_target="tpu_custom_call"')


def read(ctx):
    k = layer_stats.kernel_s(ctx, KERNEL)
    if k is None:
        return None
    toks = sum(s.decoded + s.prompt_tokens for s in layer_stats.steps(ctx))
    f, b = costs.silu_mul_call(ctx.m, toks, ctx.tp)
    t, bound = costs.roofline_s(f, b, ctx.peaks)
    roof = t * ctx.m["num_layers"]
    ctx.log(f"silu_mul roofline: {roof:.6f} s bound by {bound}, kernel "
            f"{k:.6f} s over {toks} tokens")
    return 100.0 * roof / k
