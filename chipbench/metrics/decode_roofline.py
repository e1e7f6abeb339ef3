"""Share of the decode steps' roofline: the least time the steps' needed
FLOPs and bytes allow on the chip's peaks, over the device time of the
``serve_step`` calls. Each step's context is known from its batch: the
step that writes position p has p + 1 live positions."""

PROGRAM = "jit_serve_step"


def least_seconds(ctx, flops, byts):
    return max(flops / ctx.peaks["bf16_flops_per_s"],
               byts / ctx.peaks["hbm_bytes_per_s"])


def read(ctx):
    calls = ctx.trace.programs.get(PROGRAM, [])
    steps = [(B, S0 + i) for B, S0, n in ctx.batches for i in range(n - 1)]
    if not calls or len(calls) != len(steps):
        return None
    least = sum(least_seconds(ctx, *ctx.work.decode_step(ctx.conf,
                                                         [pos + 1] * B))
                for B, pos in steps)
    return 100.0 * least / sum(calls)
