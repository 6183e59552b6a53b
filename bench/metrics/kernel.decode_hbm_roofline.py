"""Kernels, the paged step program: the HBM bytes the window's decode
steps require (the weights once per step, each decode token's live KV
rows and its one new row) at the chip's peak bandwidth, over the
program's device time (`jit_step`), in %."""


def read(ctx):
    if not ctx.trace or not ctx.peaks or not ctx.window.decode_ctx:
        return None
    t = ctx.trace.program_s("jit_step")
    steps = [active for _, _, (_, active) in ctx.spans_in_window("decode")
             if active]
    if t <= 0 or not steps:
        return None
    need = sum(ctx.shapes.decode_weight_bytes(a) for a in steps) \
        + ctx.shapes.decode_kv_bytes(ctx.window.decode_ctx)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t
