"""Share of the SSD kernel's roofline: the least time the intra-chunk work
of every call in the traced window allows on the chip's peaks, over the
kernel's device time. The kernel runs once per layer of every prefill."""

# the Pallas call takes its name from the jitted ``ssd`` that wraps it
KERNEL = "ssd"


def read(ctx):
    times = ctx.trace.kernels.get(KERNEL)
    calls = ctx.conf["n_layers"] * len(ctx.batches)
    if not times or len(times) != calls:
        return None
    least = 0.0
    for B, S0, _ in ctx.batches:
        flops, byts = ctx.work.ssd_intra_chunk(ctx.conf, B, S0)
        least += ctx.conf["n_layers"] * max(
            flops / ctx.peaks["bf16_flops_per_s"],
            byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(times)
