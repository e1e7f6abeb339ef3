"""The control against each cell's check, at real widths on the CPU: the
program at bfloat16 passes the cell's limits, and the float8 reference put
in its place fails one of them. stablelm-1.6b keeps 4 of its 24 layers
and 8192 rows of its vocabulary so that the test fits a worker's memory;
mamba2-130m runs whole, on the XLA path in place of the Pallas kernel."""

import functools
import importlib

import jax
import numpy as np
import pytest

from chipbench import judge, program
from chipbench.reference.common import seed_key
from chipbench.spec import resolve
from chipbench.tests.smoke import CELLS

CUT = {"stablelm-1.6b": dict(n_layers=4, vocab_size=8192)}


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_check(name):
    cell = resolve(name)
    conf = dict(cell.conf, use_pallas=False,
                **CUT.get(cell.conf["name"], {}))
    ref = importlib.import_module(f"chipbench.reference.{conf['family']}")
    params = jax.jit(functools.partial(ref.init_params, conf))(
        seed_key(2**32 + 11))
    n_new = min(cell.traffic["new_tokens"], 8)
    batch = 2 if n_new > 1 else 16
    prompts = np.random.default_rng(3).integers(
        0, conf["vocab_size"], (batch, 48)).astype(np.int32)
    loop = program.serve_loop(program.model_config(conf), params, 64)
    requests = list(zip(prompts, np.asarray(loop.generate(prompts, n_new))))

    def numbers(lower):
        gaps = judge.gaps(ref, conf, params, requests,
                          cell.check["block_tokens"], lower=lower)
        return judge.compared(cell.check, gaps, 0)

    sound, control = numbers(False), numbers(True)
    assert judge.passes(sound), sound
    assert not judge.passes(control), control
