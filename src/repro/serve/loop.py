"""Batched serving loop: prefill once, decode with a jitted serve_step
(donated cache).

``generate`` marks its host path with profiler spans (below). They cost
under a microsecond each and record only while a ``jax.profiler`` trace
runs, in the trace's host plane beside the device's events. Every span
carries the batch's id; ``serve.generate`` also carries the batch's
shape."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import lm

GENERATE = "serve.generate"   # the call: requests, prompt_len, new_tokens
UPLOAD = "serve.upload"       # the prompts to the device
PREFILL = "serve.prefill"     # the prefill dispatch
CACHE = "serve.cache"         # the max_len cache made and filled: kv_bytes,
                              # state_bytes
STEP = "serve.step"           # one decode step's dispatch: pos
CONCAT = "serve.concat"       # the tokens joined


class ServeLoop:
    def __init__(self, cfg, params, *, max_len: int = 256,
                 mesh=None, rules=None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.prefill = jax.jit(make_prefill_step(cfg, mesh, rules))
        self.step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
        self.batches = 0              # generate calls; the spans' batch id

    def generate(self, prompt_tokens, n_new: int):
        """prompt_tokens: (B, S0[,K]) int32. Greedy decode n_new tokens."""
        cfg = self.cfg
        B, S0 = prompt_tokens.shape[0], prompt_tokens.shape[1]
        self.batches += 1
        b = self.batches
        with TraceAnnotation(GENERATE, batch=b, requests=B, prompt_len=S0,
                             new_tokens=n_new):
            with TraceAnnotation(UPLOAD, batch=b):
                batch = {"tokens": jnp.asarray(prompt_tokens, jnp.int32)}
            with TraceAnnotation(PREFILL, batch=b):
                logits, cache = self.prefill(self.params, batch)
            with TraceAnnotation(CACHE, batch=b,
                                 **lm.cache_bytes(cfg, self.max_len, B)):
                cache = self._full_cache(cache, B)

            nxt = jnp.argmax(logits[..., :cfg.vocab_size],
                             -1).astype(jnp.int32)
            if cfg.n_codebooks:
                nxt = nxt[:, None, :]
            else:
                nxt = nxt[:, None]
            out = [nxt]
            pos = S0
            for _ in range(n_new - 1):
                with TraceAnnotation(STEP, batch=b, pos=pos):
                    nxt, cache = self.step(self.params, cache, nxt,
                                           jnp.int32(pos))
                out.append(nxt)
                pos += 1
            with TraceAnnotation(CONCAT, batch=b):
                return jnp.concatenate(out, axis=1)

    def _full_cache(self, cache, B: int):
        """The prefill cache (sized S0) copied into one of ``max_len``, so
        that decode has room for the new tokens."""
        full = lm.init_cache(self.cfg, self.max_len, B)
        for k in cache:
            if cache[k].shape == full[k].shape:
                full[k] = cache[k]
            else:                     # grow the seq dim
                sl = tuple(slice(0, s) for s in cache[k].shape)
                full[k] = full[k].at[sl].set(cache[k])
        return full
