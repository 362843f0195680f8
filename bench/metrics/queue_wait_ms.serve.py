"""Mean time a request waits in ServeFrontend's queue before a drain packs
it into a block (t_packed - t_submit, on the serving clock): the
serve.dispatch spans' queue_wait_us_sum over their requests, for the
blocks dispatched in the traced window."""
from bench.spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx.trace, "serve.dispatch", "queue_wait_us_sum")
