"""Helpers the per-layer metric readers share (``bench/metrics/``).

A reader is ``read(ctx) -> float | None``; ``ctx`` holds the reduced
``trace``, the driver's ``run`` record, the per-forward ``cost``, the
device's ``peaks`` and the ``costs`` module. A reader that finds nothing
to read returns None, and the harness leaves its metric out.
"""
from __future__ import annotations


def idle_share(ctx):
    tr = ctx.trace
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def kernel_roofline(ctx, pattern, ops_key, bytes_key):
    seconds = ctx.trace.op_seconds(pattern)
    n = ctx.run.get("forwards")
    if seconds <= 0 or not n or ctx.peaks is None:
        return None
    share, _ = ctx.costs.roofline_share(
        ctx.cost[ops_key] * n, ctx.cost[bytes_key] * n, seconds, ctx.peaks
    )
    return share
