"""The whole forward's share of the chip's peak: the model FLOPs of one
forward (bench/costs.py) times forwards per second of the window, over
the peak of the device kind (bench/peaks.json)."""


def read(ctx):
    run = ctx.run
    if not run.get("forwards") or ctx.peaks is None:
        return None
    rate = run["forwards"] / run["elapsed_s"]
    return 100.0 * ctx.cost["flops"] * rate / ctx.peaks["flops_per_s"]
