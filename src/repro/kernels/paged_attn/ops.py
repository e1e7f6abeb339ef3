"""jit'd wrapper for the paged-attention decode kernel (compiled unless the
caller passes ``interpret=True``)."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.paged_attn.kernel import paged_attention as _kernel


@partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    interpret: bool = False):
    return _kernel(q, k_pages, v_pages, page_table, lengths,
                   interpret=interpret)
