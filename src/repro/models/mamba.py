"""Mamba2 (SSD — state-space duality) block, pure-jnp chunked algorithm.

The SSD computation is organized as a scan over sequence chunks: the
quadratic intra-chunk part (attention-like, O(chunk²)) is computed inside
the scan step so live memory stays O(B·chunk²·heads) instead of
O(B·S·chunk·heads); the inter-chunk state is the scan carry — exactly the
"recurrent outer, attention inner" duality of the paper [arXiv:2405.21060].

``kernels/ssd_scan`` provides the Pallas TPU kernels for the intra-chunk
part and for decode's one-token state update; this module is their jnp
oracle and the default (CPU/dry-run) path.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import ParamDef, rms_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def mamba_defs(cfg, ll=()) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gns = s.ngroups * s.d_state
    Lax = tuple("layers" for _ in ll)
    # zamba2 (nh=80) shards SSM heads over `model`; mamba2-130m (nh=24)
    # does not divide the 16-wide axis -> replicated (see DESIGN.md).
    hax = "ssm_heads" if nh % 16 == 0 else "ssm_heads_rep"
    out = {
        "wz": ParamDef(ll + (d, di), Lax + ("embed", hax)),
        "wx": ParamDef(ll + (d, di), Lax + ("embed", hax)),
        "wb": ParamDef(ll + (d, gns), Lax + ("embed", "ssm_state")),
        "wc": ParamDef(ll + (d, gns), Lax + ("embed", "ssm_state")),
        "wdt": ParamDef(ll + (d, nh), Lax + ("embed", hax)),
        "dt_bias": ParamDef(ll + (nh,), Lax + (hax,), init="zeros"),
        "A_log": ParamDef(ll + (nh,), Lax + (hax,), init="ones"),
        "D": ParamDef(ll + (nh,), Lax + (hax,), init="ones"),
        "conv_x": ParamDef(ll + (s.d_conv, di), Lax + ("conv", hax),
                           scale=0.5),
        "conv_b": ParamDef(ll + (s.d_conv, gns), Lax + ("conv", "ssm_state"),
                           scale=0.5),
        "conv_c": ParamDef(ll + (s.d_conv, gns), Lax + ("conv", "ssm_state"),
                           scale=0.5),
        "norm": ParamDef(ll + (di,), Lax + (hax,), init="ones"),
        "wo": ParamDef(ll + (di, d), Lax + (hax, "embed")),
    }
    if s.conv_bias:
        out["conv_x_bias"] = ParamDef(ll + (di,), Lax + (hax,), init="zeros")
        for n in ("conv_b_bias", "conv_c_bias"):
            out[n] = ParamDef(ll + (gns,), Lax + ("ssm_state",), init="zeros")
    if s.pre_norm:
        out["in_norm"] = ParamDef(ll + (d,), Lax + ("embed",), init="ones")
    return out


# ---------------------------------------------------------------------------
# Causal depthwise conv (d_conv taps) as shifted adds — no conv primitive
# ---------------------------------------------------------------------------

def causal_conv(u, w, state=None, bias=None):
    """u: (B, S, C); w: (taps, C); bias: (C,) or None. state: (B, taps-1, C)
    history or None. Returns (y, new_state)."""
    taps = w.shape[0]
    if state is None:
        state = jnp.zeros((u.shape[0], taps - 1, u.shape[2]), u.dtype)
    ext = jnp.concatenate([state, u], axis=1)              # (B, S+taps-1, C)
    y = sum(ext[:, i:i + u.shape[1]] * w[i] for i in range(taps))
    if bias is not None:
        y = y + bias
    return y, ext[:, -(taps - 1):]


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A_log, B_, C_, D_, chunk: int, state=None,
                return_state: bool = False, einsum_dtype=jnp.float32):
    """x: (B,S,nh,hp); dt: (B,S,nh) (post-softplus); A_log: (nh,);
    B_/C_: (B,S,ns), one group shared by all heads, or (B,S,G,ns), where
    head h reads group h // (nh/G); D_: (nh,).
    state: (B,nh,hp,ns) initial inter-chunk state."""
    if B_.ndim == 4:
        return _ssd_grouped(x, dt, A_log, B_, C_, D_, chunk, state,
                            return_state, einsum_dtype)
    B, S, nh, hp = x.shape
    ns = B_.shape[-1]
    cl = min(chunk, S)
    S_orig = S
    if S % cl:                 # pad with dt=0 tokens: no state contribution
        pad = cl - S % cl
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // cl
    A = -jnp.exp(A_log.astype(jnp.float32))                # (nh,)
    dtf = dt.astype(jnp.float32)
    dA = dtf * A                                           # (B,S,nh)
    xdt = x.astype(jnp.float32) * dtf[..., None]

    # chunked views, scan axis first
    def chunked(t, extra=()):
        return jnp.moveaxis(t.reshape((B, nc, cl) + t.shape[2:]), 1, 0)

    dA_c = chunked(dA)                                     # (nc,B,cl,nh)
    x_c = chunked(xdt)                                     # (nc,B,cl,nh,hp)
    B_c = chunked(B_.astype(jnp.float32))                  # (nc,B,cl,ns)
    C_c = chunked(C_.astype(jnp.float32))

    tri = jnp.tril(jnp.ones((cl, cl), bool))

    if state is None:
        state = jnp.zeros((B, nh, hp, ns), jnp.float32)

    def step(carry, inp):
        st = carry                                         # (B,nh,hp,ns)
        dA_k, x_k, B_k, C_k = inp
        cs = jnp.cumsum(dA_k, axis=1)                      # (B,cl,nh)
        # intra-chunk: y[i] += sum_{j<=i} exp(cs_i - cs_j) (C_i·B_j) xdt_j
        seg = cs[:, :, None, :] - cs[:, None, :, :]        # (B,cl,cl,nh)
        # mask BEFORE exp: exp of masked (positive) entries overflows to
        # inf, and inf*0 in the backward pass is NaN
        seg = jnp.where(tri[None, :, :, None], seg, -1e9)
        L = jnp.exp(seg).astype(einsum_dtype)
        sc = jnp.einsum("bin,bjn->bij", C_k.astype(einsum_dtype),
                        B_k.astype(einsum_dtype),
                        preferred_element_type=jnp.float32)
        y_diag = jnp.einsum("bij,bijh,bjhp->bihp",
                            sc.astype(einsum_dtype), L,
                            x_k.astype(einsum_dtype),
                            preferred_element_type=jnp.float32)
        # contribution of the carried state
        dec_in = jnp.exp(cs)                               # (B,cl,nh)
        y_off = jnp.einsum("bin,bhpn,bih->bihp", C_k, st, dec_in)
        # new chunk state
        total = cs[:, -1, :]                               # (B,nh)
        dec_out = jnp.exp(total[:, None, :] - cs)          # (B,cl,nh)
        st_new = jnp.einsum("bjn,bjh,bjhp->bhpn", B_k, dec_out, x_k)
        st = st * jnp.exp(total)[:, :, None, None] + st_new
        return st, (y_diag + y_off)

    # flash-style: recompute the O(cl²) intra-chunk tensors (seg/L/att) in
    # the backward pass instead of saving them per chunk — the L matrices
    # are ~40% of all HBM traffic if persisted (see EXPERIMENTS §Perf)
    step = jax.checkpoint(step,
                          policy=jax.checkpoint_policies.nothing_saveable)
    state, y = jax.lax.scan(step, state, (dA_c, x_c, B_c, C_c))
    y = jnp.moveaxis(y, 0, 1).reshape(B, S, nh, hp)        # (B,S,nh,hp)
    y = y + x.astype(jnp.float32) * D_.astype(jnp.float32)[None, None, :, None]
    y = y.astype(x.dtype)[:, :S_orig]
    return (y, state) if return_state else y


def _ssd_grouped(x, dt, A_log, B_, C_, D_, chunk, state, return_state,
                 einsum_dtype):
    """Grouped B/C: the heads of one group share its B and C and no head
    reads another group, so the SSD is the single-group SSD of each group."""
    B, S, nh, hp = x.shape
    G = B_.shape[2]

    def heads(t, ax):                       # split the head axis into groups
        return t.reshape(t.shape[:ax] + (G, nh // G) + t.shape[ax + 1:])

    def one(x, dt, A_log, B_, C_, D_, st):
        return ssd_chunked(x, dt, A_log, B_, C_, D_, chunk, state=st,
                           return_state=True, einsum_dtype=einsum_dtype)

    if state is None:
        state = jnp.zeros((B, nh, hp, B_.shape[-1]), jnp.float32)
    y, st = jax.vmap(one, in_axes=(2, 2, 0, 2, 2, 0, 1), out_axes=(2, 1))(
        heads(x, 2), heads(dt, 2), heads(A_log, 0), B_, C_, heads(D_, 0),
        heads(state, 1))
    y, st = y.reshape(x.shape), st.reshape(state.shape)
    return (y, st) if return_state else y


def ssd_decode_step(x, dt, A_log, B_, C_, D_, state):
    """Single-token recurrence. x: (B,nh,hp); dt: (B,nh); B_/C_: (B,ns), or
    (B,G,ns) where head h reads group h // (nh/G); state: (B,nh,hp,ns) →
    (y, new_state). The state is read once: y = C·(state·dA + xdt⊗B)."""
    if B_.ndim == 2:
        B_, C_ = B_[:, None], C_[:, None]
    per = x.shape[1] // B_.shape[1]                    # heads of a group
    Bh, Ch = (jnp.repeat(t.astype(jnp.float32), per, axis=1)
              for t in (B_, C_))                       # (B,nh,ns)
    A = -jnp.exp(A_log.astype(jnp.float32))
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A)                              # (B,nh)
    xf = x.astype(jnp.float32)
    upd = (xf * dtf[..., None])[..., None] * Bh[:, :, None, :]
    state = state * dA[..., None, None] + upd
    y = jnp.sum(state * Ch[:, :, None, :], axis=-1)
    y = y + xf * D_.astype(jnp.float32)[None, :, None]
    return y.astype(x.dtype), state


def state_layout(hp: int, ns: int) -> tuple:
    """How the decode cache stores one head's (hp, ns) float32 state: as
    (hp/k, k·ns), k positions side by side in a row, so that a row fills
    the 128 lanes of a TPU vector register where ns < 128 (zamba2's 64: k
    = 2) and the decode kernel reads it without a relayout. A row-major
    reshape: element (p, n) lies at (p // k, (p % k)·ns + n)."""
    k = math.gcd(hp, max(1, 128 // ns))
    return hp // k, k * ns


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------

def _mixer_in(cfg, p, u, dtype, conv_state=None, mesh=None, rules=None):
    """u: (B, S, D) → z (B,S,di), x (B,S,nh,hp), dt (B,S,nh) float32 after
    softplus, B/C (B,S,ns), or (B,S,G,ns) where head h reads group
    h // (nh/G), and the convolution's new state."""
    s = cfg.ssm
    B, S, D = u.shape
    nh = s.n_heads(D)
    ns = s.d_state
    G = s.ngroups
    if s.pre_norm:
        u = rms_norm(u, p["in_norm"], cfg.norm_eps)

    z = jnp.einsum("bsd,de->bse", u, p["wz"].astype(dtype))
    xs = jnp.einsum("bsd,de->bse", u, p["wx"].astype(dtype))
    bs = jnp.einsum("bsd,dn->bsn", u, p["wb"].astype(dtype))
    cs = jnp.einsum("bsd,dn->bsn", u, p["wc"].astype(dtype))
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", u, p["wdt"].astype(dtype))
        .astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if s.dt_min:
        dt = jnp.maximum(dt, s.dt_min)

    cx = cb = cc = None
    if conv_state is not None:
        cx, cb, cc = conv_state
    bias = {n: p[n + "_bias"].astype(dtype) if s.conv_bias else None
            for n in ("conv_x", "conv_b", "conv_c")}
    xs, cx = causal_conv(xs, p["conv_x"].astype(dtype), cx, bias["conv_x"])
    bs, cb = causal_conv(bs, p["conv_b"].astype(dtype), cb, bias["conv_b"])
    cs2, cc = causal_conv(cs, p["conv_c"].astype(dtype), cc, bias["conv_c"])
    xs = jax.nn.silu(xs)
    bs = jax.nn.silu(bs)
    cs2 = jax.nn.silu(cs2)

    if mesh is not None:
        from repro.models.partitioning import constrain
        hax = "ssm_heads" if nh % 16 == 0 else "ssm_heads_rep"
        xs = constrain(xs, mesh, "batch", None, hax, rules=rules)
        z = constrain(z, mesh, "batch", None, hax, rules=rules)
        dt = constrain(dt, mesh, "batch", None, hax, rules=rules)
        bs = constrain(bs, mesh, "batch", None, None, rules=rules)
        cs2 = constrain(cs2, mesh, "batch", None, None, rules=rules)
    xh = xs.reshape(B, S, nh, s.headdim)
    if G > 1:                  # (B, S, G, ns): head h reads group h // (nh/G)
        bs, cs2 = bs.reshape(B, S, G, ns), cs2.reshape(B, S, G, ns)
    return z, xh, dt, bs, cs2, (cx, cb, cc)


def _mixer_out(cfg, p, y, z, dtype):
    """y (B,S,nh,hp) → the gated RMSNorm (Mamba2): norm(y * silu(z)), over
    each group's channels, then the out-projection (B,S,D)."""
    B, S = y.shape[:2]
    di = z.shape[-1]
    G = cfg.ssm.ngroups
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    if G > 1:
        y = rms_norm(y.reshape(B, S, G, di // G),
                     p["norm"].reshape(G, di // G),
                     cfg.norm_eps).reshape(B, S, di)
    else:
        y = rms_norm(y, p["norm"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, p["wo"].astype(dtype))


def mamba_block(cfg, p, u, dtype, *, return_state: bool = False,
                use_pallas: bool = False, mesh=None, rules=None):
    """u: (B, S, D) from zero state (training, prefill). With
    ``return_state`` also the final SSM state (B,nh,hp,ns) and the
    convolution's last inputs, which ``mamba_decode`` continues from."""
    z, xh, dt, bs, cs2, conv = _mixer_in(cfg, p, u, dtype, mesh=mesh,
                                         rules=rules)
    chunk = cfg.ssm_chunk or cfg.ssm.chunk
    if use_pallas:
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, new_state = ssd_ops.ssd(xh, dt, p["A_log"], bs, cs2, p["D"],
                                   chunk=chunk)
    else:
        y, new_state = ssd_chunked(
            xh, dt, p["A_log"], bs, cs2, p["D"], chunk, return_state=True,
            einsum_dtype=jnp.bfloat16 if cfg.ssm_bf16 else jnp.float32)
    out = _mixer_out(cfg, p, y, z, dtype)
    if return_state:
        return out, new_state, conv
    return out


def mamba_decode(cfg, p, u, ssm, layer, conv_state, dtype, *,
                 use_pallas: bool = False):
    """One token, u: (B, 1, D). ``ssm`` is every layer's state as the
    decode cache stores it, (L, B, nh) + ``state_layout(hp, ns)`` float32,
    of which row ``layer`` is this block's and is updated in place: by the
    Pallas ``ssd_state`` kernel with ``use_pallas``, else by
    ``ssd_decode_step``. conv_state: this layer's (cx, cb, cc).
    Returns (out (B,1,D), ssm, conv_state)."""
    z, xh, dt, bs, cs, conv = _mixer_in(cfg, p, u, dtype, conv_state)
    x, dt, b, c = xh[:, 0], dt[:, 0], bs[:, 0], cs[:, 0]
    if b.ndim == 2:                                    # one group
        b, c = b[:, None], c[:, None]
    if use_pallas:
        from repro.kernels.ssd_scan import ops as ssd_ops
        xf = x.astype(jnp.float32)
        dA = jnp.exp(dt * -jnp.exp(p["A_log"].astype(jnp.float32)))
        y, ssm = ssd_ops.ssd_state(ssm, layer, xf * dt[..., None], dA, b, c)
        y = (y + xf * p["D"].astype(jnp.float32)[None, :, None]
             ).astype(x.dtype)
    else:
        row = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
        y, st = ssd_decode_step(x, dt, p["A_log"], b, c, p["D"],
                                row.reshape(x.shape + (b.shape[-1],)))
        ssm = jax.lax.dynamic_update_index_in_dim(
            ssm, st.reshape(row.shape), layer, 0)
    return _mixer_out(cfg, p, y[:, None], z, dtype), ssm, conv
