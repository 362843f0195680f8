"""Share of query-block slots that carried a requested id (ServeStats
valid_slots over valid_slots + padded_slots, counted over the window)."""


def read(ctx):
    valid, padded = ctx.run.get("valid_slots"), ctx.run.get("padded_slots")
    if not valid:
        return None
    return 100.0 * valid / (valid + padded)
