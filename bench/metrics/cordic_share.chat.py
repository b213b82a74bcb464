"""Share of the chip's busy time spent in the CORDIC SwiGLU kernel
(kernels/cordic_act.py, the Pallas kernel named ``cordic_silu_mul``): the
paper's CORDIC sigmoid datapath beside the rest of a served step, as its
summed device time over the union of op intervals in the window (first
chip), in %.

A program whose kernels carry no names (one older than them) is read by
the kernel's signature, as silu_mul_roofline.chat reads it."""
import phase_reduce


def read(ctx):
    return phase_reduce.kernel_share(ctx, "cordic_silu_mul",
                                     phase_reduce.SILU_MUL_SIGNATURE)
