"""A run without the look for a chip, at smoke sizes on the CPU: sound, it
is correct; with the timed path broken underneath, ``correct`` comes out
false for each fault a serving cell can have."""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import program, run
from chipbench.spec import ROOT
from chipbench.tests.smoke import CELLS, smoke_cell

GEN = [n for n in CELLS if smoke_cell(n).traffic["new_tokens"] > 1]
SEED = 2**31 + 99


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(program, "enable_compile_cache", lambda: "off")


def run_smoke(name, trace=False):
    cell = smoke_cell(name)
    return run.run_cell(cell, SEED, 0.0, trace, jax.devices()[0],
                        time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = run_smoke(name)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "compared"
    e2e = {m["name"] for m in smoke_cell(name).end_to_end}
    assert set(r["metrics"]) == e2e
    assert all(m["value"] > 0 for m in r["metrics"].values())


def _serve_fault(monkeypatch, fault):
    from repro.launch import steps
    real_factory = steps.make_serve_step

    def factory(cfg):
        real = real_factory(cfg)

        def step(params, cache, tokens, pos):
            nxt, new_cache = real(params, cache, tokens, pos)
            if fault == "state_unchanged":
                return nxt, cache
            return (nxt + 1) % cfg.vocab_size, new_cache
        return step
    monkeypatch.setattr("repro.serve.loop.make_serve_step", factory)


@pytest.mark.parametrize("name", GEN)
@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_broken_decode_step_is_not_correct(name, fault, monkeypatch):
    _serve_fault(monkeypatch, fault)
    r = run_smoke(name)
    assert r["correct"] is False, r["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_of_prefill_is_not_correct(name, monkeypatch):
    from repro.launch import steps
    real_factory = steps.make_prefill_step

    def factory(cfg, mesh=None, rules=None):
        real = real_factory(cfg, mesh, rules)

        def prefill(params, batch):
            logits, cache = real(params, batch)
            return jnp.roll(logits, 1, axis=-1), cache
        return prefill
    monkeypatch.setattr("repro.serve.loop.make_prefill_step", factory)
    r = run_smoke(name)
    assert r["correct"] is False, r["compared"]


def test_no_chip_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "stablelm-1.6b.chat-gen", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
