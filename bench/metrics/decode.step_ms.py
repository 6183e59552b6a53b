"""Paged decode: mean host time of a `ServeEngine.step` that decoded at
least one slot (harness span), in ms."""
import numpy as np


def read(ctx):
    d = [t1 - t0 for t0, t1, (_, active) in ctx.spans_in_window("decode")
         if active]
    return float(np.mean(d)) * 1e3 if d else None
