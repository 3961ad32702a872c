"""Whole decode step's share of the chip's bf16 peak: the model FLOPs of
every token decoded in the traced steps (the architecture's
``decode_flops``: layers, attention over its context, the head), over the
decode programs' device time times the peak. Moves tokens_per_s, beside
the kernels' rooflines."""
from bench.work import peaks

PROGRAM = "decode"


def read(ctx):
    ticks = [t for t in ctx.traced_ticks if t.decode_context]
    spent = ctx.trace.program_seconds(PROGRAM)
    if not ticks or spent <= 0 or \
            len(ticks) != ctx.trace.program_count(PROGRAM):
        return None
    n = sum(len(t.decode_context) for t in ticks)
    ctx_rows = sum(sum(t.decode_context) for t in ticks)
    f = ctx.arch.decode_flops(ctx.spec, n, ctx_rows, n_logits=n)
    return 100.0 * f / (spent * peaks(ctx.device_kind)["bf16_flops_per_s"])
