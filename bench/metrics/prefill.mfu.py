"""Prefill pod: the FLOPs the window's prompts require at their true
lengths (not the bucket), over the prefill program's device time times
the chip's bf16 peak, in %."""


def read(ctx):
    if not ctx.trace or not ctx.peaks:
        return None
    t = ctx.trace.program_s("jit_prefill")
    plen = ctx.plen()
    flops = sum(ctx.shapes.prefill_flops(plen[k])
                for _, _, k in ctx.spans_in_window("prefill_pod")
                if k in plen)
    if t <= 0 or not flops:
        return None
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
