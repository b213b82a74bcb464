"""Share of its roofline the paged-attention decode kernel
(kernels/paged_attention.py, ``gqa_decode``) reached: the least time the
chip needs for the live K/V of every decoded slot at bf16, plus query and
output, summed over every layer of every decode step in the window,
over the kernel's summed device time (first chip in use).

The trace names a Pallas kernel only by its HLO instruction (a
``tpu_custom_call``), so the kernel is found by its signature: the custom
call whose result is the f32 (slots, kv heads per chip, group, head_dim)
attention output."""
import costs
import layer_stats


def kernel_pattern(ctx) -> str:
    m = ctx.m
    kh = m["num_kv_heads"] // ctx.tp
    g = m["num_heads"] // m["num_kv_heads"]
    slots = ctx.config["engine"]["slots"]
    return (rf"^%\S+ = f32\[{slots},{kh},{g},{m['head_dim']}\]\S* "
            r'custom-call\(.*custom_call_target="tpu_custom_call"')


def read(ctx):
    k = layer_stats.kernel_s(ctx, kernel_pattern(ctx))
    if k is None:
        return None
    roof, bounds = 0.0, set()
    for s in layer_stats.steps(ctx):
        if s.ctx:
            f, b = costs.paged_attn_step(ctx.m, s.ctx, ctx.tp)
            t, bound = costs.roofline_s(f, b, ctx.peaks)
            roof += t * ctx.m["num_layers"]
            bounds.add(bound)
    ctx.log(f"paged_attn roofline: {roof:.6f} s bound by {sorted(bounds)}, "
            f"kernel {k:.6f} s")
    return 100.0 * roof / k
