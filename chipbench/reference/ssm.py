"""Plain reference of the Mamba-2 decoder as the program states it.

Per layer, on the residual x (no norm in front of the mixer):
z = x·Wz, u = silu(conv(x·Wx)), b = silu(conv(x·Wb)), c = silu(conv(x·Wc)),
dt = softplus(x·Wdt + dt_bias), with conv a causal depthwise convolution
of d_conv taps. Each head h keeps a (headdim, d_state) state
    s_t = exp(dt_t·A_h)·s_{t-1} + dt_t·u_t ⊗ b_t,   A_h = -exp(A_log_h)
    y_t = s_t·c_t + D_h·u_t,
computed here one position after another (no chunks). Then
x += Wo·RMSNorm(y * silu(z)). A final RMSNorm and the tied embedding give
the logits. float32 throughout; ``lower=True`` rounds both inputs of
every matmul to float8 (the control that must fail the check).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.common import (HIGHEST, fan_in_normal, matmul,
                                        normal, padded_vocab, rms_norm,
                                        silu, softplus)


def dims(conf: dict):
    s, d = conf["ssm"], conf["d_model"]
    di = s["expand"] * d
    return di, di // s["headdim"], s["headdim"], s["d_state"], s["d_conv"]


def init_params(conf: dict, key) -> dict:
    """Seeded weights in the layout the program serves (stacked layers)."""
    L, d = conf["n_layers"], conf["d_model"]
    di, nh, hp, ns, taps = dims(conf)
    V = padded_vocab(conf["vocab_size"])
    dt_ = jnp.dtype(conf["param_dtype"])
    ks = iter(jax.random.split(key, 16))
    w = lambda shape: fan_in_normal(next(ks), shape, dt_)   # noqa: E731
    u = lambda lo, hi: jax.random.uniform(                   # noqa: E731
        next(ks), (L, nh), jnp.float32, lo, hi)
    dt0 = jnp.exp(u(math.log(1e-3), math.log(1e-1)))
    return {
        "embed": normal(next(ks), (V, d), 1 / math.sqrt(d), dt_),
        "final_norm": jnp.ones((d,), dt_),
        "layers": {
            "wz": w((L, d, di)), "wx": w((L, d, di)),
            "wb": w((L, d, ns)), "wc": w((L, d, ns)), "wdt": w((L, d, nh)),
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt_),
            "A_log": jnp.log(u(1.0, 16.0)).astype(dt_),
            "D": jnp.ones((L, nh), dt_),
            "conv_x": normal(next(ks), (L, taps, di), 0.5, dt_),
            "conv_b": normal(next(ks), (L, taps, ns), 0.5, dt_),
            "conv_c": normal(next(ks), (L, taps, ns), 0.5, dt_),
            "norm": jnp.ones((L, di), dt_),
            # out-projection scaled by 1/sqrt(n_layers), as mamba_ssm's
            # init (rescale_prenorm_residual) scales out_proj
            "wo": normal(next(ks), (L, di, d), 1 / math.sqrt(di * L), dt_),
        },
    }


def _conv(x, w):
    """Causal depthwise convolution: y_t = sum_i w_i · x_{t-taps+1+i}."""
    taps, S = w.shape[0], x.shape[1]
    ext = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(ext[:, i:i + S] * w[i] for i in range(taps))


def _scan(u, dt, A, b, c, D):
    """The recurrence, position by position. u: (B,S,nh,hp); dt: (B,S,nh);
    b, c: (B,S,ns)."""
    B, S, nh, hp = u.shape

    def step(s, inp):
        u_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t * A)[..., None, None] * s
             + (dt_t[..., None] * u_t)[..., None] * b_t[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HIGHEST)
        return s, y + D[:, None] * u_t

    s0 = jnp.zeros((B, nh, hp, b.shape[-1]), jnp.float32)
    tm = lambda t: jnp.moveaxis(t, 1, 0)                     # noqa: E731
    _, y = jax.lax.scan(step, s0, (tm(u), tm(dt), tm(b), tm(c)))
    return jnp.moveaxis(y, 0, 1)


def logits(conf: dict, params: dict, tokens, start: int,
           lower: bool = False):
    """float32 logits over the vocabulary at positions start..S-1 of
    ``tokens`` (B, S), each predicting the token that follows it."""
    B, S = tokens.shape
    di, nh, hp, ns, _ = dims(conf)
    eps = conf["norm_eps"]
    f32 = lambda t: jax.tree_util.tree_map(                  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    embed = params["embed"].astype(jnp.float32)
    x = embed[tokens]

    def layer(x, p):
        p = f32(p)
        z = matmul(x, p["wz"], lower)
        u = silu(_conv(matmul(x, p["wx"], lower), p["conv_x"]))
        b = silu(_conv(matmul(x, p["wb"], lower), p["conv_b"]))
        c = silu(_conv(matmul(x, p["wc"], lower), p["conv_c"]))
        dt = softplus(matmul(x, p["wdt"], lower) + p["dt_bias"])
        y = _scan(u.reshape(B, S, nh, hp), dt, -jnp.exp(p["A_log"]), b, c,
                  p["D"]).reshape(B, S, di)
        y = rms_norm(y * silu(z), p["norm"], eps)
        return x + matmul(y, p["wo"], lower), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x[:, start:], params["final_norm"].astype(jnp.float32), eps)
    return matmul(x, embed[:conf["vocab_size"]].T, lower)
