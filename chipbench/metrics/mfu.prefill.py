"""Model FLOPs of the window's prompts (the head once per request) per
second of the traced window, over the chip's bf16 peak."""


def read(ctx):
    flops = sum(ctx.work.prefill(ctx.conf, B, S0)[0]
                for B, S0, _ in ctx.batches)
    return 100.0 * flops / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]
