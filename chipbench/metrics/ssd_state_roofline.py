"""Share of the decode SSD kernel's roofline: the least time that moving
each layer's float32 state in and out once, with the token's inputs and
its y, allows at the chip's memory bandwidth, over the kernel's device
time. The kernel runs once per layer of every decode step; a trace with
any other count of its calls reads nothing."""

# the Pallas call takes its name from the jitted ``ssd_state`` that wraps it
KERNEL = "ssd_state"
F32 = 4


def ssd_state_bytes(c: dict, batch: int) -> int:
    """What the kernel moves for one layer and one token of each of
    ``batch`` sequences, all in float32: the state read and written once,
    x·dt, exp(dt·A), each group's B and C in, y out."""
    s = c["ssm"]
    hp, ns, G = s["headdim"], s["d_state"], s.get("ngroups", 1)
    nh = s["expand"] * c["d_model"] // hp
    return batch * F32 * (2 * nh * hp * ns + 2 * nh * hp + nh + 2 * G * ns)


def read(ctx):
    times = ctx.trace.kernels.get(KERNEL)
    steps = [B for B, _, n in ctx.batches for _ in range(n - 1)]
    if not times or len(times) != ctx.conf["n_layers"] * len(steps):
        return None
    least = sum(ctx.conf["n_layers"] * ssd_state_bytes(ctx.conf, B)
                for B in steps) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / sum(times)
