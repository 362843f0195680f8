"""K1 grid steps a forward launches (fused_prune_aggregate's prune +
softmax kernel): the program's own count, kept while the forward was
traced (repro.tracing.program_counts, counter k1_steps), summed over the
programs compiled in the run; in a .full cell only the forward launches
K1. A program that keeps no such count gives nothing."""


def read(ctx):
    try:
        from repro import tracing
    except ImportError:
        return None
    counts = getattr(tracing, "program_counts", None)
    if counts is None:
        return None
    steps = [c["k1_steps"] for c in counts().values() if "k1_steps" in c]
    return float(sum(steps)) if steps else None
