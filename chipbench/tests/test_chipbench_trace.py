"""The trace reduction and the per-layer readers on a trace recorded on
one TPU v5e: the harness's closed loop over mamba2-130m at batch 32,
256-token prompts and 8 new tokens, two batches (data/, gzipped)."""

import math

import pytest

from chipbench import tracing
from chipbench.run import Context
from chipbench.spec import HERE, load_json, load_module
from chipbench.work import ssm

TRACE = HERE / "tests" / "data" / "mamba2-130m.gen-b32.xplane.pb.gz"
BATCHES = [(32, 256, 8)] * 2
CONF = load_json(HERE / "configs" / "mamba2-130m.json")
PEAKS = load_json(HERE / "peaks.json")["TPU v5 lite"]


@pytest.fixture(scope="module")
def reduced():
    return tracing.reduce(tracing.load(TRACE))


def test_programs_and_kernel_calls(reduced):
    # 7 decode steps and one prefill a batch; the SSD kernel once per
    # layer of each prefill
    assert len(reduced.programs["jit_serve_step"]) == 14
    assert len(reduced.programs["jit_prefill_step"]) == 2
    assert len(reduced.kernels["ssd"]) == 2 * 24
    assert reduced.chips == 1


def test_busy_and_gaps_fill_the_window(reduced):
    assert 0 < reduced.busy_s <= reduced.window_s
    idle = sum(s for s, _ in reduced.gaps)
    assert math.isclose(idle + reduced.busy_s, reduced.window_s,
                        rel_tol=1e-9)
    assert {label for _, label in reduced.gaps} <= {"prepare", "generate",
                                                    "fetch"}
    assert reduced.gaps == sorted(reduced.gaps, reverse=True)


def test_self_times_do_not_exceed_busy_time(reduced):
    # nested operations (a while loop and its body) count once
    assert sum(reduced.ops.values()) <= reduced.busy_s * 1.0001
    kernel = [k for k in reduced.ops if k.startswith("ssd.")]
    assert kernel and "custom-call" in kernel[0]


def test_op_labels():
    text = ('%convert.69 = bf16[24,2048,5632]{2,1,0:T(8,128)(2,1)} '
            'convert(f32[24,2048,5632]{2,1,0:T(8,128)} %params)')
    assert tracing.op_label(text) == "convert.69 convert bf16[24,2048,5632]"
    text = ('%ssd.3 = (f32[32,24,256,64]{3,2,1,0:T(8,128)}, f32[8]{0}) '
            'custom-call(bf16[32,24,64,256]{3,2,1,0} %b), '
            'custom_call_target="tpu_custom_call"')
    assert tracing.op_label(text) == \
        "ssd.3 custom-call (f32[32,24,256,64], f32[8])"
    assert tracing.kernel_name(text) == "ssd"
    assert tracing.kernel_name("%fusion.3 = f32[8]{0} fusion()") is None
    assert tracing.program_name("jit_serve_step(1029384756)") == \
        "jit_serve_step"


@pytest.fixture(scope="module")
def ctx(reduced):
    return Context(conf=CONF, work=ssm, trace=reduced,
                   peaks=PEAKS, batches=BATCHES)


def read(name, ctx):
    return load_module("metrics", name).read(ctx)


def test_step_readers(ctx, reduced):
    steps = reduced.programs["jit_serve_step"]
    assert read("decode_step_ms", ctx) == pytest.approx(
        sum(steps) / 14 * 1e3)
    roof = read("decode_roofline", ctx)
    least = ssm.decode_step(CONF, [300] * 32)[1] / PEAKS["hbm_bytes_per_s"]
    assert roof == pytest.approx(100 * 14 * least / sum(steps), rel=1e-6)
    prefill = reduced.programs["jit_prefill_step"]
    assert read("prefill_us_per_token", ctx) == pytest.approx(
        sum(prefill) / (2 * 32 * 256) * 1e6)


@pytest.mark.parametrize("name", ["decode_roofline", "mfu.gen",
                                  "idle_share.gen", "mfu.prefill",
                                  "idle_share.prefill", "ssd_scan_roofline"])
def test_shares_lie_in_0_100(name, ctx):
    assert 0 < read(name, ctx) < 100


def test_readers_stay_silent_when_the_trace_does_not_match(reduced):
    # one batch too many: the step and kernel counts no longer match
    ctx = Context(conf=CONF, work=ssm, trace=reduced,
                  peaks=PEAKS, batches=BATCHES * 2)
    for name in ("decode_roofline", "prefill_us_per_token",
                 "ssd_scan_roofline"):
        assert read(name, ctx) is None
