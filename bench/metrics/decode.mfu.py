"""Paged decode, the whole step: the FLOPs the window's decode tokens
require (projections, attention over each token's live context, output
head) over the paged step program's device time (`jit_step`) times the
chip's bf16 peak, in %."""


def read(ctx):
    if not ctx.trace or not ctx.peaks or not ctx.window.decode_ctx:
        return None
    t = ctx.trace.program_s("jit_step")
    if t <= 0:
        return None
    flops = ctx.shapes.decode_flops(ctx.window.decode_ctx)
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
