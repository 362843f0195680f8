"""Mean time from a block's dispatch to its requests' results (the
forward and gather, the wait behind the block in flight, the host copy):
requests x service_us of the serve.complete spans over their requests,
for the blocks completed in the traced window."""
from bench.spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx.trace, "serve.complete", "requests", "service_us")
