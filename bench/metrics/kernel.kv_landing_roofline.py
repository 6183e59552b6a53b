"""Kernels, `kernels/wr_scatter` (`gather_records` on the source,
`wr_scatter` landing on the decode pool): the window's migrated KV bytes
read once and written once at the chip's peak bandwidth, over the
device time of those programs (`jit__gather`, `jit__scatter`), in %."""


def read(ctx):
    if not ctx.trace or not ctx.peaks:
        return None
    t = ctx.trace.program_s("jit__gather", "jit__scatter")
    plen = ctx.plen()
    nbytes = sum(ctx.shapes.migrated_bytes(plen[k])
                 for _, _, k in ctx.spans_in_window("prefill_pod")
                 if k in plen)
    if t <= 0 or not nbytes:
        return None
    return 100.0 * 2 * nbytes / ctx.peaks["hbm_bytes_per_s"] / t
