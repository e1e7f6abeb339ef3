"""Device time of the compiled ``prefill_step`` per prompt token."""

PROGRAM = "jit_prefill_step"


def read(ctx):
    calls = ctx.trace.programs.get(PROGRAM, [])
    if not calls or len(calls) != len(ctx.batches):
        return None
    return sum(calls) / sum(B * S0 for B, S0, _ in ctx.batches) * 1e6
