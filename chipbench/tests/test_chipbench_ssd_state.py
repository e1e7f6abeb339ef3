"""``ssd_state_roofline``: the decode SSD kernel's bytes at both cells'
sizes against hand counts, and its reader on a synthetic reduced trace."""

import pytest

from chipbench import tracing
from chipbench.run import Context
from chipbench.spec import HERE, load_json, load_module
from chipbench.work import ssm

METRIC = load_module("metrics", "ssd_state_roofline")
MAMBA = load_json(HERE / "configs" / "mamba2-130m.json")
ZAMBA = load_json(HERE / "configs" / "zamba2-7b.json")
PEAKS = load_json(HERE / "peaks.json")["TPU v5 lite"]


def test_bytes_at_both_cells():
    """One layer of one step: the float32 state read and written once,
    x·dt and y (nh·hp each), exp(dt·A) (nh) and each group's B and C (ns
    each), in float32."""
    # mamba2-130m.chat-gen: B=128, 24 heads of 64, d_state 128, one group
    assert METRIC.ssd_state_bytes(MAMBA, 128) == 128 * 4 * (
        2 * 24 * 64 * 128 + 2 * 24 * 64 + 24 + 2 * 128)     # 203,042,816
    # 4.87 GB over 24 layers: the state is nearly all of it
    assert 4.8e9 < 24 * METRIC.ssd_state_bytes(MAMBA, 128) < 4.9e9
    # zamba2-7b.doc-qa: B=8, 112 heads of 64, d_state 64, two groups
    assert METRIC.ssd_state_bytes(ZAMBA, 8) == 8 * 4 * (
        2 * 112 * 64 * 64 + 2 * 112 * 64 + 112 + 2 * 2 * 64)  # 29,830,656


@pytest.mark.parametrize("calls_less", [None, 0, 1, -24])
def test_reader_counts_every_layer_of_every_step(calls_less):
    """With one ``ssd_state`` call per layer of every decode step (24 x 14
    for two batches of 8 new tokens at B=32), the least time is the bytes
    counted above over 819 GB/s; any other count of calls, or none (a
    program without the kernel), reads nothing."""
    batches = [(32, 256, 8)] * 2
    calls = 24 * 14
    kernels = {} if calls_less is None else \
        {METRIC.KERNEL: [2e-4] * (calls - calls_less)}
    ctx = Context(conf=MAMBA, work=ssm, peaks=PEAKS, batches=batches,
                  trace=tracing.Reduced(window_s=1.0, busy_s=1.0, chips=1,
                                        kernels=kernels))
    got = METRIC.read(ctx)
    if calls_less != 0:
        assert got is None
        return
    assert PEAKS["hbm_bytes_per_s"] == 819e9
    least = calls * METRIC.ssd_state_bytes(MAMBA, 32) / 819e9
    assert got == pytest.approx(100 * least / (calls * 2e-4), rel=1e-12)
