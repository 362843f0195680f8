"""Requests per dispatched query block: the serve.dispatch spans'
requests over their count, in the traced window."""
from bench.spans import host_spans, stat_sum


def read(ctx):
    spans = [s for s in host_spans(ctx.trace, "serve.dispatch") if "requests" in s[2]]
    if not spans:
        return None
    return stat_sum(spans, "requests") / len(spans)
