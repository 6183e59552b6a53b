"""Router layer: median wait of a request from its scheduled send time to
the start of `PrefillPod.process` (harness span), in ms."""
import numpy as np


def read(ctx):
    sched = {r.rid: r.sched for r in ctx.window.recs}
    waits = [t0 - sched[k] for t0, _, k in ctx.spans_in_window("prefill_pod")
             if k in sched]
    return float(np.median(waits)) * 1e3 if waits else None
