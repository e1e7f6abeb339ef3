"""Model FLOPs of the window's work (every prompt's prefill and every
decode step) per second of the traced window, over the chip's bf16 peak:
FLOPs per generated token times generated tokens per second."""


def read(ctx):
    flops = 0
    for B, S0, n in ctx.batches:
        flops += ctx.work.prefill(ctx.conf, B, S0)[0]
        flops += sum(ctx.work.decode_step(ctx.conf, [S0 + i + 1] * B)[0]
                     for i in range(n - 1))
    return 100.0 * flops / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]
