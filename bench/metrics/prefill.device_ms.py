"""Prefill pod: device time of the prefill program (`jit_prefill` in the
trace) per request prefilled in the window, in ms."""


def read(ctx):
    n = len(ctx.spans_in_window("prefill"))
    t = ctx.trace.program_s("jit_prefill") if ctx.trace else 0.0
    return t / n * 1e3 if n and t > 0 else None
