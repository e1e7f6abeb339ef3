"""Plain reference of the dense decoder as the program states it.

Per layer: x += Wo·attn(rope(Wq·n1), rope(Wk·n1), Wv·n1) with n1 =
RMSNorm(x), causal softmax scaled by 1/sqrt(head_dim), rotary over the
whole head (first half/second half pairing, inverse frequencies
theta**(-2i/head_dim)); then x += W2·(silu(W1·n2) * W3·n2) with n2 =
RMSNorm(x). A final RMSNorm and an untied head give the logits. Every
product is float32 at the highest precision; ``lower=True`` rounds both
inputs of every matmul to float8 (the control that must fail the check).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.common import (HIGHEST, fan_in_normal, matmul,
                                        normal, operands, padded_vocab,
                                        rms_norm, silu)


def init_params(conf: dict, key) -> dict:
    """Seeded weights in the layout the program serves (stacked layers)."""
    L, d, ff = conf["n_layers"], conf["d_model"], conf["d_ff"]
    H, KH, hd = conf["n_heads"], conf["n_kv_heads"], conf["head_dim"]
    V = padded_vocab(conf["vocab_size"])
    dt = jnp.dtype(conf["param_dtype"])
    ks = iter(jax.random.split(key, 9))
    w = lambda shape: fan_in_normal(next(ks), shape, dt)   # noqa: E731
    ones = lambda shape: jnp.ones(shape, dt)                # noqa: E731
    return {
        "embed": normal(next(ks), (V, d), 1 / math.sqrt(d), dt),
        "final_norm": ones((d,)),
        "head": normal(next(ks), (d, V), 1 / math.sqrt(d), dt),
        "layers": {
            "ln1": ones((L, d)), "ln2": ones((L, d)),
            "attn": {"wq": w((L, d, H * hd)), "wk": w((L, d, KH * hd)),
                     "wv": w((L, d, KH * hd)), "wo": w((L, H * hd, d))},
            "mlp": {"w1": w((L, d, ff)), "w3": w((L, d, ff)),
                    "w2": w((L, ff, d))},
        },
    }


def _rope(x, theta):
    """x: (B, S, H, hd) rotated by position along S."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(q, k, v, lower):
    """Causal softmax attention; q, k, v: (B, S, H, hd)."""
    S, hd = q.shape[1], q.shape[-1]
    if k.shape[2] != q.shape[2]:                      # grouped KV heads
        g = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    qa, ka = operands(q, -1, k, -1, lower)
    s = jnp.einsum("bqhd,bkhd->bhqk", qa, ka, precision=HIGHEST)
    s = s / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    pa, va = operands(p, -1, v, 1, lower)
    return jnp.einsum("bhqk,bkhd->bqhd", pa, va, precision=HIGHEST)


def logits(conf: dict, params: dict, tokens, start: int,
           lower: bool = False):
    """float32 logits over the vocabulary at positions start..S-1 of
    ``tokens`` (B, S), each predicting the token that follows it."""
    B, S = tokens.shape
    H, KH, hd = conf["n_heads"], conf["n_kv_heads"], conf["head_dim"]
    eps, theta = conf["norm_eps"], conf["rope_theta"]
    f32 = lambda t: jax.tree_util.tree_map(                  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    x = f32(params["embed"])[tokens]

    def layer(x, p):
        p = f32(p)
        a, m = p["attn"], p["mlp"]
        h = rms_norm(x, p["ln1"], eps)
        q = _rope(matmul(h, a["wq"], lower).reshape(B, S, H, hd), theta)
        k = _rope(matmul(h, a["wk"], lower).reshape(B, S, KH, hd), theta)
        v = matmul(h, a["wv"], lower).reshape(B, S, KH, hd)
        o = _attention(q, k, v, lower).reshape(B, S, H * hd)
        x = x + matmul(o, a["wo"], lower)
        h = rms_norm(x, p["ln2"], eps)
        f = silu(matmul(h, m["w1"], lower)) * matmul(h, m["w3"], lower)
        return x + matmul(f, m["w2"], lower), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x[:, start:], params["final_norm"].astype(jnp.float32), eps)
    head = params["head"][:, :conf["vocab_size"]].astype(jnp.float32)
    return matmul(x, head, lower)
