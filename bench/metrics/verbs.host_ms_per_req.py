"""Verbs datapath: host time covered by the datapath's own spans
(`repro.obs.trace`: post_send, dispatch_run, cqe_publish, poll_cq, as
the union of their intervals) per request processed, in ms."""


def read(ctx):
    spans = ctx.window.verbs_spans
    n = len(ctx.spans_in_window("prefill_pod"))
    if not spans or not n:
        return None
    total, t = 0, None
    for a, b in sorted(spans):
        if t is None or a > t:
            total += b - a
            t = b
        elif b > t:
            total += b - t
            t = b
    return total / 1e6 / n
