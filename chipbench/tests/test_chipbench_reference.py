"""The plain references against the program at smoke sizes on the CPU.
This checks the reference, not the program: at float32 compute the two
must agree to rounding, through the forward pass and through ServeLoop's
prefill, cache hand-off and decode."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import judge, program
from chipbench.reference import dense, ssm
from chipbench.reference.common import fp8, seed_key
from chipbench.spec import HERE, load_json
from chipbench.tests.smoke import smoke_conf

FAMILIES = {"stablelm-1.6b": dense, "mamba2-130m": ssm}


def setup(name, **over):
    conf = dict(smoke_conf(load_json(HERE / "configs" / f"{name}.json")),
                compute_dtype="float32", **over)
    ref = FAMILIES[name]
    params = jax.jit(functools.partial(ref.init_params, conf))(
        seed_key(2**33 + 7))
    return conf, ref, params


@pytest.mark.parametrize("name", FAMILIES)
def test_weights_have_the_program_layout(name):
    conf, _, params = setup(name)
    program.check_layout(program.model_config(conf), params)


@pytest.mark.parametrize("name", FAMILIES)
def test_reference_matches_forward(name):
    from repro.models import lm
    conf, ref, params = setup(name)
    cfg = program.model_config(conf)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, conf["vocab_size"], (2, 40)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _, _ = lm.forward(cfg, params, {"tokens": tokens})
    want = ref.logits(conf, params, tokens, 0)
    assert want.shape == (2, 40, conf["vocab_size"])
    np.testing.assert_allclose(got[..., :conf["vocab_size"]], want,
                               atol=2e-4, rtol=2e-4)
    tail = ref.logits(conf, params, tokens, 30)
    np.testing.assert_allclose(tail, want[:, 30:], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("name,pallas", [("stablelm-1.6b", False),
                                         ("mamba2-130m", False),
                                         ("mamba2-130m", True)])
def test_served_tokens_are_the_reference_argmax(name, pallas, monkeypatch):
    """Greedy tokens from ServeLoop.generate at float32 compute lie on the
    reference's argmax: every gap is rounding."""
    from repro.kernels.ssd_scan import ops
    monkeypatch.setattr(ops, "ssd", functools.partial(ops.ssd,
                                                      interpret=True))
    conf, ref, params = setup(name, use_pallas=pallas)
    loop = program.serve_loop(program.model_config(conf), params, 64)
    prompts = np.random.default_rng(2).integers(
        0, conf["vocab_size"], (3, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        served = np.asarray(loop.generate(prompts, 6))
    gaps = judge.gaps(ref, conf, params, list(zip(prompts, served)), 4096)
    assert gaps.shape == (18,)
    assert gaps.max() < 1e-4


def test_fp8_rounding_keeps_three_mantissa_bits():
    x = jnp.asarray([[448.0, 1.0, 1.0625, 1.125, -3.3, 0.0]])
    y = fp8(x, -1)
    # scale is 1 (absmax 448): 1.0625 lies halfway and rounds to even
    np.testing.assert_array_equal(y, [[448.0, 1.0, 1.0, 1.125, -3.25, 0.0]])
    r = jax.random.normal(jax.random.PRNGKey(0), (64, 256)) * 20
    z = np.asarray(fp8(r.at[:, 0].set(448.0), -1))     # scale 1 per row
    m, _ = np.frexp(np.abs(z[z != 0]))
    assert np.all((m * 16) == np.round(m * 16))


def test_sample_takes_the_longest_and_follows_the_seed():
    reqs = [(np.zeros(n, np.int32), np.zeros(1, np.int32))
            for n in (8, 16, 8, 32, 16, 8)]
    a = judge.sample(reqs, 3, 5)
    assert len(a) == 3 and len(a[0][0]) == 32
    assert [len(p) for p, _ in a] == [len(p) for p, _ in
                                      judge.sample(reqs, 3, 5)]
