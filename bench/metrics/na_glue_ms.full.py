"""Device time per forward of the NA glue: the ops under a semantic
graph's na.<graph> scope and under neither k1 nor k2 (the theta gathers
and tile transposes, K2's source-id gather, the inverse permutation).
Scopes come from the program's op_name table (repro.tracing)."""
from bench.spans import seconds_by_stage


def read(ctx):
    n = ctx.run.get("forwards")
    glue = [s for k, s in seconds_by_stage(ctx.trace).items() if k.endswith("/glue")]
    if not n or not glue:
        return None
    return sum(glue) * 1e3 / n
