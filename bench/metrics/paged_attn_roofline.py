"""The paged decode-attention kernel's share of its roofline.

Required work (bench/work/paged_attention.py): for every live slot of a
traced decode step, its K and V rows 0..pos in the float32 pool, q and the
output; in each layer that attends through the pool (the architecture's
``paged_attention_layers``). The least time of every traced step at the
chip's peaks, over the kernel's time inside the decode programs."""
from bench.work import paged_attention as pa
from bench.work import roofline

PROGRAM, KERNEL = "decode", "paged_attention_tpu"


def read(ctx):
    n_layers, n_heads, n_kv, hd = ctx.arch.paged_attention_layers(ctx.spec)
    ticks = [t for t in ctx.traced_ticks if t.decode_context]
    spent = ctx.trace.kernel_seconds(KERNEL, PROGRAM)
    if not n_layers or not ticks or spent <= 0 or \
            len(ticks) != ctx.trace.program_count(PROGRAM):
        return None
    least = sum(roofline(pa.work(t.decode_context, n_heads, n_kv, hd),
                         ctx.device_kind)[0]
                for t in ticks)
    return 100.0 * least * n_layers / spent
