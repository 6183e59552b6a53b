"""Migration: mean host time of `KVTransferEngine.migrate_pages` per
request (harness span), in ms."""
import numpy as np


def read(ctx):
    d = [t1 - t0 for t0, t1, _ in ctx.spans_in_window("migrate")]
    return float(np.mean(d)) * 1e3 if d else None
