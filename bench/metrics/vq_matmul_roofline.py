"""The VQ dequant-matmul kernel's share of its roofline in decode steps.

Required work per call (bench/work/vq_dequant_matmul.py): 2 M r c FLOPs;
the packed codes, int8 codebooks and their scales, x and y in bfloat16.
The architecture's ``decode_vq_matmuls`` lists every call of one decode
step at M = the engine's batch width (None where routing decides the
calls: nothing is read then), so the least time is that of every call of
every traced decode step at the chip's peaks (memory-bound at these
shapes), over the kernel's time inside the decode programs on the device
trace."""
from bench.work import roofline
from bench.work import vq_dequant_matmul as vq

PROGRAM, KERNEL = "decode", "vq_dequant_matmul"


def read(ctx):
    steps = ctx.trace.program_count(PROGRAM)
    spent = ctx.trace.kernel_seconds(KERNEL, PROGRAM)
    calls = ctx.arch.decode_vq_matmuls(ctx.spec,
                                       ctx.mix["engine"]["max_batch"])
    if steps == 0 or spent <= 0 or not calls:
        return None
    least = sum(roofline(vq.work(M, r, c, ctx.spec.vq), ctx.device_kind)[0]
                for M, r, c in calls)
    return 100.0 * least * steps / spent
