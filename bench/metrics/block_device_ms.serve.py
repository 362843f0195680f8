"""Device time per dispatched query block (InferenceSession.query: the
forward plus the query gather): busy device time of the traced window
over the blocks dispatched in it."""


def read(ctx):
    blocks = ctx.run.get("blocks")
    busy = ctx.trace.busy_s
    if not blocks or busy <= 0:
        return None
    return busy * 1e3 / blocks
