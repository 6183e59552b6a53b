"""Migration: KV bytes the window's requests migrate (their pages, K and
V, from shapes) over the time spent in `migrate_pages`, in GB/s."""


def read(ctx):
    plen = ctx.plen()
    nbytes = sum(ctx.shapes.migrated_bytes(plen[k])
                 for _, _, k in ctx.spans_in_window("prefill_pod")
                 if k in plen)
    t = sum(t1 - t0 for t0, t1, _ in ctx.spans_in_window("migrate"))
    return nbytes / t / 1e9 if t > 0 and nbytes else None
