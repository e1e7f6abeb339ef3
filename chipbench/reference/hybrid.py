"""Plain reference of the Zamba2 hybrid decoder as the program states it
(transformers' ``modeling_zamba2``: Zamba2HybridLayer and its parts).

Every layer i is a Mamba-2 layer x += Mamba(RMSNorm(x + t_i)), where t_i
is 0 except before the layers of ``hybrid_layer_ids`` (those below
``n_layers``). Before the k-th of them, shared block k % num_mem_blocks
reads the residual x and the embedding output e:
    h = RMSNorm([x, e]),  a = Wo·attn(rope(Wq·h), rope(Wk·h), Wv·h),
causal softmax scaled by (head_dim / 2) ** -0.5, rotary over the whole
head; then with n = RMSNorm(a) and the site's adapter (A_k, B_k)
    [g, u] = W_gu·n + B_k·(A_k·n),   t_k = L_k·W_down·(gelu(g) * u),
GELU exact (erf), L_k the site's linear; the block has no residual.

The Mamba-2 mixer: z = u·Wz, x' = silu(conv(u·Wx) + bx), b = silu(conv(
u·Wb) + bb), c = silu(conv(u·Wc) + bc) with B and C in ``ngroups``
groups (head h reads group h // (heads / ngroups)), dt = max(softplus(
u·Wdt + dt_bias), dt_min), and per head the state
    s_t = exp(dt_t·A_h)·s_{t-1} + dt_t·x'_t ⊗ b_t,
    y_t = s_t·c_t + D_h·x'_t,
one position after another (no chunks); then Wo·RMSNorm_g(y * silu(z)),
the norm taken over each group's channels. A final RMSNorm and the tied
embedding give the logits. float32 throughout at the highest precision;
attention in blocks of query rows so that long prompts fit; ``lower=True``
rounds both inputs of every matmul to float8 (the control that must fail
the check).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.common import (HIGHEST, matmul, normal, operands,
                                        padded_vocab, rms_norm, silu,
                                        softplus)

QUERY_BLOCK = 512


def dims(conf: dict):
    s, d = conf["ssm"], conf["d_model"]
    di = s["expand"] * d
    return (di, di // s["headdim"], s["headdim"], s["d_state"], s["d_conv"],
            s.get("ngroups", 1))


def sites(conf: dict) -> list:
    return [i for i in conf["hybrid_layer_ids"] if i < conf["n_layers"]]


def init_params(conf: dict, key) -> dict:
    """Seeded weights in the layout the program serves (stacked Mamba
    layers, a list of shared blocks, a list of sites)."""
    L, d, ff = conf["n_layers"], conf["d_model"], conf["d_ff"]
    H, KH, hd = conf["n_heads"], conf["n_kv_heads"], conf["head_dim"]
    r = conf["adapter_rank"]
    di, nh, hp, ns, taps, G = dims(conf)
    V = padded_vocab(conf["vocab_size"])
    dt_ = jnp.dtype(conf["param_dtype"])
    ks = iter(jax.random.split(key, 20))
    u = lambda lo, hi: jax.random.uniform(                   # noqa: E731
        next(ks), (L, nh), jnp.float32, lo, hi)

    def w(k, shape, std=None):
        """Normal with std 1/sqrt(fan_in) (the next-to-last axis), or
        ``std``, drawn in slices of the leading axis of about 2**24
        elements, one after another: no float32 copy of the whole array
        is made, so that the weights fit beside their own drawing."""
        std = 1 / math.sqrt(shape[-2]) if std is None else std
        parts = min(shape[0], max(1, math.prod(shape) // 2**24))
        while shape[0] % parts:
            parts -= 1
        part = (shape[0] // parts,) + shape[1:]
        return jax.lax.map(lambda kk: normal(kk, part, std, dt_),
                           jax.random.split(k, parts)).reshape(shape)

    dt0 = jnp.exp(u(math.log(1e-3), math.log(1e-1)))
    layers = {
        "wz": w(next(ks), (L, d, di)), "wx": w(next(ks), (L, d, di)),
        "wb": w(next(ks), (L, d, G * ns)), "wc": w(next(ks), (L, d, G * ns)),
        "wdt": w(next(ks), (L, d, nh)),
        "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt_),
        "A_log": jnp.log(u(1.0, 16.0)).astype(dt_),
        "D": jnp.ones((L, nh), dt_),
        "conv_x": w(next(ks), (L, taps, di), 0.5),
        "conv_b": w(next(ks), (L, taps, G * ns), 0.5),
        "conv_c": w(next(ks), (L, taps, G * ns), 0.5),
        "conv_x_bias": w(next(ks), (L, di), 0.1),
        "conv_b_bias": w(next(ks), (L, G * ns), 0.1),
        "conv_c_bias": w(next(ks), (L, G * ns), 0.1),
        "norm": jnp.ones((L, di), dt_),
        "in_norm": jnp.ones((L, d), dt_),
        # out-projection scaled by 1/sqrt(n_layers), as mamba_ssm's init
        # (rescale_prenorm_residual) scales out_proj
        "wo": w(next(ks), (L, di, d), 1 / math.sqrt(di * L)),
    }

    def block(k):
        kk = iter(jax.random.split(k, 7))
        return {"ln_in": jnp.ones((2 * d,), dt_),
                "attn": {"wq": w(next(kk), (2 * d, H * hd)),
                         "wk": w(next(kk), (2 * d, KH * hd)),
                         "wv": w(next(kk), (2 * d, KH * hd)),
                         "wo": w(next(kk), (H * hd, d))},
                "ln_ff": jnp.ones((d,), dt_),
                "mlp": {"w1": w(next(kk), (d, ff)), "w3": w(next(kk), (d, ff)),
                        "w2": w(next(kk), (ff, d))}}

    def site(k):
        kk = iter(jax.random.split(k, 4))
        out = {"linear": w(next(kk), (d, d))}
        if r:
            out.update(adapter_in=w(next(kk), (d, r)),
                       adapter_g=w(next(kk), (r, ff)),
                       adapter_u=w(next(kk), (r, ff)))
        return out

    kb, kst = next(ks), next(ks)
    return {
        "embed": w(next(ks), (V, d), 1 / math.sqrt(d)),
        "final_norm": jnp.ones((d,), dt_),
        "layers": layers,
        "shared": [block(jax.random.fold_in(kb, i))
                   for i in range(conf["num_mem_blocks"])],
        "sites": [site(jax.random.fold_in(kst, i))
                  for i in range(len(sites(conf)))],
    }


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _conv(x, w, b):
    """Causal depthwise convolution with bias:
    y_t = b + sum_i w_i · x_{t-taps+1+i}."""
    taps, S = w.shape[0], x.shape[1]
    ext = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return b + sum(ext[:, i:i + S] * w[i] for i in range(taps))


def _scan(u, dt, A, b, c, D):
    """The recurrence, position by position. u: (B,S,nh,hp); dt: (B,S,nh);
    b, c: (B,S,G,ns), head h reading group h // (nh/G)."""
    B, S, nh, hp = u.shape
    rep = nh // b.shape[2]

    def step(s, inp):
        u_t, dt_t, b_t, c_t = inp
        b_h, c_h = jnp.repeat(b_t, rep, 1), jnp.repeat(c_t, rep, 1)
        s = (jnp.exp(dt_t * A)[..., None, None] * s
             + (dt_t[..., None] * u_t)[..., None] * b_h[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", s, c_h, precision=HIGHEST)
        return s, y + D[:, None] * u_t

    s0 = jnp.zeros((B, nh, hp, b.shape[-1]), jnp.float32)
    tm = lambda t: jnp.moveaxis(t, 1, 0)                     # noqa: E731
    _, y = jax.lax.scan(step, s0, (tm(u), tm(dt), tm(b), tm(c)))
    return jnp.moveaxis(y, 0, 1)


def _mamba(conf, p, x, lower):
    """Mamba(RMSNorm(x)) of one layer; p in float32."""
    B, S, _ = x.shape
    di, nh, hp, ns, _, G = dims(conf)
    eps = conf["norm_eps"]
    x = rms_norm(x, p["in_norm"], eps)
    z = matmul(x, p["wz"], lower)
    u = silu(_conv(matmul(x, p["wx"], lower), p["conv_x"], p["conv_x_bias"]))
    b = silu(_conv(matmul(x, p["wb"], lower), p["conv_b"], p["conv_b_bias"]))
    c = silu(_conv(matmul(x, p["wc"], lower), p["conv_c"], p["conv_c_bias"]))
    dt = jnp.maximum(softplus(matmul(x, p["wdt"], lower) + p["dt_bias"]),
                     conf["ssm"].get("dt_min", 0.0))
    y = _scan(u.reshape(B, S, nh, hp), dt, -jnp.exp(p["A_log"]),
              b.reshape(B, S, G, ns), c.reshape(B, S, G, ns), p["D"])
    y = (y.reshape(B, S, G, di // G)
         * silu(z).reshape(B, S, G, di // G))
    y = rms_norm(y, p["norm"].reshape(G, di // G), eps).reshape(B, S, di)
    return matmul(y, p["wo"], lower)


def _rope(x, theta):
    """x: (B, S, H, hd) rotated by position along S (first half/second
    half pairing, inverse frequencies theta**(-2i/head_dim))."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(q, k, v, lower):
    """Causal softmax attention scaled by (head_dim/2)**-0.5, QUERY_BLOCK
    query rows at a time against every key; q, k, v: (B, S, H, hd)."""
    B, S, H, hd = q.shape
    qa, ka = operands(q, -1, k, -1, lower)
    nb = -(-S // QUERY_BLOCK)
    qb = jnp.pad(qa, ((0, 0), (0, nb * QUERY_BLOCK - S), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(B, nb, QUERY_BLOCK, H, hd), 1, 0)
    keys = jnp.arange(S)

    def block(args):
        i, q_i = args
        s = jnp.einsum("bqhd,bkhd->bhqk", q_i, ka, precision=HIGHEST)
        s = s * (hd / 2) ** -0.5
        rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        p = jax.nn.softmax(jnp.where(keys[None] <= rows[:, None], s,
                                     -jnp.inf), axis=-1)
        pa, va = operands(p, -1, v, 1, lower)
        return jnp.einsum("bhqk,bkhd->bqhd", pa, va, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb), qb))         # (nb,B,QB,H,hd)
    return jnp.moveaxis(o, 0, 1).reshape(B, nb * QUERY_BLOCK, H, hd)[:, :S]


def _shared(conf, blk, site, x, e, lower):
    """t of one site: the shared block on [x, e], then the site's linear."""
    B, S, d = x.shape
    H, KH, hd = conf["n_heads"], conf["n_kv_heads"], conf["head_dim"]
    eps, theta = conf["norm_eps"], conf["rope_theta"]
    a_ = blk["attn"]
    h = rms_norm(jnp.concatenate([x, e], -1), blk["ln_in"], eps)
    q = _rope(matmul(h, a_["wq"], lower).reshape(B, S, H, hd), theta)
    k = _rope(matmul(h, a_["wk"], lower).reshape(B, S, KH, hd), theta)
    v = matmul(h, a_["wv"], lower).reshape(B, S, KH, hd)
    if KH != H:                                       # grouped KV heads
        k, v = jnp.repeat(k, H // KH, 2), jnp.repeat(v, H // KH, 2)
    a = matmul(_attention(q, k, v, lower).reshape(B, S, H * hd), a_["wo"],
               lower)
    n = rms_norm(a, blk["ln_ff"], eps)
    m = blk["mlp"]
    g, u = matmul(n, m["w1"], lower), matmul(n, m["w3"], lower)
    if "adapter_in" in site:
        r = matmul(n, site["adapter_in"], lower)
        g = g + matmul(r, site["adapter_g"], lower)
        u = u + matmul(r, site["adapter_u"], lower)
    f = matmul(jax.nn.gelu(g, approximate=False) * u, m["w2"], lower)
    return matmul(f, site["linear"], lower)


def logits(conf: dict, params: dict, tokens, start: int,
           lower: bool = False):
    """float32 logits over the vocabulary at positions start..S-1 of
    ``tokens`` (B, S), each predicting the token that follows it."""
    L, M = conf["n_layers"], conf["num_mem_blocks"]
    at = sites(conf)
    embed = params["embed"].astype(jnp.float32)
    x = e = embed[tokens]

    def layer(x, p):
        return x + _mamba(conf, _f32(p), x, lower), None

    bounds = sorted({0, *at}) + [L]
    for lo, hi in zip(bounds, bounds[1:]):
        if lo in at:
            k = at.index(lo)
            t = _shared(conf, _f32(params["shared"][k % M]),
                        _f32(params["sites"][k]), x, e, lower)
            p = jax.tree_util.tree_map(lambda a: a[lo], params["layers"])
            x = x + _mamba(conf, _f32(p), x + t, lower)
            lo += 1
        if lo < hi:
            run = jax.tree_util.tree_map(lambda a: a[lo:hi], params["layers"])
            x, _ = jax.lax.scan(layer, x, run)
    x = rms_norm(x[:, start:], params["final_norm"].astype(jnp.float32),
                 conf["norm_eps"])
    return matmul(x, embed[:conf["vocab_size"]].T, lower)
