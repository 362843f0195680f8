"""Mean time a request's packed block waits for the stepper (t_dispatch -
t_packed: the depth-2 block pipe and the collector's blocking put): the
serve.dispatch spans' pipe_wait_us_sum over their requests, for the blocks
dispatched in the traced window."""
from bench.spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx.trace, "serve.dispatch", "pipe_wait_us_sum")
