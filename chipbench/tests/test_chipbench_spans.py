"""The serving loop's spans, as the profiler records them on the CPU at
smoke sizes, and the program names the trace readers look for."""

import re
import time
from collections import Counter
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench import program, run, tracing
from chipbench.serve_spans import (CACHE, CONCAT, GENERATE, PREFILL,
                                   PREFILL_PROGRAM, SERVE, STEP, UPLOAD,
                                   Span, serve_batches)
from chipbench.spec import load_module
from chipbench.tests.smoke import CELLS, smoke_cell

SEED = 2**31 + 7
N_BATCHES = 2


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(program, "enable_compile_cache", lambda: "off")


def host_spans(log_dir):
    """The ``serve.*`` events of the trace's CPU host plane."""
    data = tracing.load(tracing.find_xplane(log_dir))
    return sorted((Span(e.start_ns, e.end_ns, e.name, dict(e.stats))
                   for plane in data.planes if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events
                   if e.name.startswith(SERVE)),
                  key=lambda x: x.start_ns)


@pytest.fixture(scope="module", params=CELLS)
def traced(request, tmp_path_factory):
    """Two batches of the cell's smoke traffic through the harness's
    closed loop, under the harness's profiler session."""
    cell = smoke_cell(request.param)
    sv = run.set_up(cell, SEED, time.perf_counter())
    log_dir = tmp_path_factory.mktemp("trace")
    with tracing.record(log_dir):
        batches, _ = run.drive(sv.loop, cell, SEED, 0.0,
                               N_BATCHES * cell.traffic["batch"])
    shapes = [(b.prompts.shape[0], b.prompts.shape[1],
               cell.traffic["new_tokens"]) for b in batches]
    return SimpleNamespace(cell=cell, sv=sv, shapes=shapes,
                           spans=host_spans(log_dir))


def test_program_names_its_spans_as_the_reduction_reads_them():
    from repro.serve import loop
    assert [loop.GENERATE, loop.UPLOAD, loop.PREFILL, loop.CACHE, loop.STEP,
            loop.CONCAT] == [GENERATE, UPLOAD, PREFILL, CACHE, STEP, CONCAT]


def test_one_generate_span_per_batch_with_its_shape(traced):
    gens = [x for x in traced.spans if x.name == GENERATE]
    assert len(gens) == len(traced.shapes) == N_BATCHES
    for g, (B, S0, n) in zip(gens, traced.shapes):
        assert g.stats == {"batch": g.stats["batch"], "requests": B,
                           "prompt_len": S0, "new_tokens": n}
    assert len({g.stats["batch"] for g in gens}) == N_BATCHES


def test_children_nest_in_their_generate_and_share_its_batch(traced):
    gens = [x for x in traced.spans if x.name == GENERATE]
    for g, (_, S0, n) in zip(gens, traced.shapes):
        kids = [x for x in traced.spans if x.name != GENERATE
                and g.start_ns <= x.start_ns and x.end_ns <= g.end_ns]
        assert all(x.stats["batch"] == g.stats["batch"] for x in kids)
        assert Counter(x.name for x in kids) == Counter({
            UPLOAD: 1, PREFILL: 1, CACHE: 1,
            STEP: n - 1, CONCAT: 1})
        order = [x.name for x in kids]
        assert order[:3] == [UPLOAD, PREFILL, CACHE]
        assert order[-1] == CONCAT
        steps = [x.stats["pos"] for x in kids if x.name == STEP]
        assert steps == list(range(S0, S0 + n - 1))
    assert all(any(g.start_ns <= x.start_ns and x.end_ns <= g.end_ns
                   for g in gens) for x in traced.spans)


def test_reduction_accepts_the_programs_spans(traced):
    trace = SimpleNamespace(spans=traced.spans)
    gens = serve_batches(trace, traced.shapes)
    assert gens == [x for x in traced.spans if x.name == GENERATE]
    # a batch the harness saw but the spans did not, or a lost step
    assert serve_batches(trace, traced.shapes * 2) is None
    if traced.shapes[0][2] > 1:
        fewer = list(traced.spans)
        fewer.remove(next(x for x in fewer if x.name == STEP))
        assert serve_batches(SimpleNamespace(spans=fewer),
                             traced.shapes) is None


def _module_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def test_compiled_programs_carry_the_names_the_readers_find(traced):
    loop = traced.sv.loop
    B, S0, _ = traced.shapes[0]
    tokens = jnp.zeros((B, S0), jnp.int32)
    prefill = loop.prefill.lower(loop.params, {"tokens": tokens})
    assert _module_name(prefill) == load_module(
        "metrics", "prefill_us_per_token").PROGRAM == PREFILL_PROGRAM
    _, cache = jax.eval_shape(loop.prefill, loop.params, {"tokens": tokens})
    cache = jax.eval_shape(lambda c: loop._full_cache(c, B), cache)
    step = loop.step.lower(loop.params, cache, tokens[:, :1], jnp.int32(S0))
    for metric in ("decode_step_ms", "decode_roofline"):
        assert _module_name(step) == load_module("metrics", metric).PROGRAM
