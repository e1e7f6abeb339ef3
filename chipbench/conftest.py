"""The benchmark's tests run on the CPU, where a Pallas kernel runs only in
the interpreter, and a kernel compiles unless its caller asks for that.
A served model with ``use_pallas`` reaches decode's ``ssd_state`` kernel;
this patches it to the interpreter for every test here, as the tests
patch prefill's ``ssd`` themselves."""

import functools

import pytest


@pytest.fixture(autouse=True)
def interpret_decode_state_kernel(monkeypatch):
    from repro.kernels.ssd_scan import ops
    monkeypatch.setattr(ops, "ssd_state",
                        functools.partial(ops.ssd_state, interpret=True))
