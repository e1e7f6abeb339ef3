"""Device time of one decode step: the mean duration of the compiled
``serve_step`` program's calls in the traced window."""

PROGRAM = "jit_serve_step"


def read(ctx):
    calls = ctx.trace.programs.get(PROGRAM)
    return sum(calls) / len(calls) * 1e3 if calls else None
