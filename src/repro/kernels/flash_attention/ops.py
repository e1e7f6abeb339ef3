"""jit'd wrapper for the flash-attention forward kernel. It compiles for
the TPU; a caller that wants the Pallas interpreter (CPU validation) passes
``interpret=True``."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.kernel import flash_attention_fwd


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
