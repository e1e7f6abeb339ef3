"""Attention: chunked online-softmax (flash-style) in pure jnp, decode paths,
sliding-window support and DeepSeek-V2 Multi-head Latent Attention.

Two block schedules for the training/prefill path:

* ``rect``       — scan over q-chunks × *all* k-chunks, causality by mask.
                   Simple, but wastes ~2× attention FLOPs above the diagonal
                   (and much more with a sliding window).
* ``triangular`` — statically enumerate only the (q-chunk, k-chunk) pairs
                   that intersect the causal (and SWA) mask; a single scan
                   over the pair list. Exactly the paper's GL2 move: don't
                   drop the new interface in — restructure the loop so no
                   work is submitted that the mask will discard.

Both produce identical outputs (tests assert allclose); the §Perf log
quantifies the HLO-FLOP difference.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, rms_norm

NEG_INF = -1e30


def _block_pairs(nq: int, nk: int, q_chunk: int, k_chunk: int,
                 causal: bool, window: int) -> Tuple[np.ndarray, np.ndarray]:
    """Static (i, j) block pair list intersecting the causal/SWA mask."""
    pairs = []
    for i in range(nq):
        q_lo, q_hi = i * q_chunk, (i + 1) * q_chunk - 1
        for j in range(nk):
            k_lo, k_hi = j * k_chunk, (j + 1) * k_chunk - 1
            if causal and k_lo > q_hi:
                continue
            if window and k_hi < q_lo - window + 1:
                continue
            pairs.append((i, j))
    arr = np.asarray(pairs, np.int32)
    return arr[:, 0], arr[:, 1]


def _block_scores(q_blk, k_blk, scale, gq, gk, causal, window):
    """One (q_chunk × k_chunk) score block with mask applied. fp32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                   preferred_element_type=jnp.float32) * scale
    allow = jnp.ones((gq.shape[0], gk.shape[0]), bool)
    if causal:
        allow &= gk[None, :] <= gq[:, None]
    if window:
        allow &= gk[None, :] > gq[:, None] - window
    return jnp.where(allow[None, None], s, NEG_INF)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_chunk: int = 512, k_chunk: int = 0,
                    scale: Optional[float] = None,
                    schedule: str = "triangular", mesh=None, rules=None):
    """q: (B, S, H, hd); k, v: (B, Sk, KH, hd_v) with H % KH == 0 (GQA).

    Returns (B, S, H, hd_v). Online softmax over chunk pairs; O(chunk²)
    live score memory instead of O(S²). ``triangular`` statically skips
    fully-masked blocks (≈2× fewer attention FLOPs when causal; O(S·W)
    instead of O(S²) with a sliding window). A custom VJP recomputes
    blocks in the backward pass (flash-attention style) — without it,
    AD of the scan would save every score block (9 GiB/layer at 4k).
    """
    B, S, H, hd = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if H != KH:  # GQA: repeat KV to H heads; AD of repeat sums group grads
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
    q_chunk = min(q_chunk, S)
    k_chunk = min(k_chunk or q_chunk, Sk)
    assert S % q_chunk == 0 and Sk % k_chunk == 0, (S, q_chunk, Sk, k_chunk)
    fn = _flash_core(causal, window, q_chunk, k_chunk, float(scale),
                     schedule, mesh, _rules_key(rules))
    return fn(q, k, v)


def _unblock(yb, B, S, H, hdv):
    """(nq,B,H,qc,d) -> (B,S,H,d)"""
    nq = yb.shape[0]
    qc = yb.shape[3]
    y = jnp.moveaxis(yb, 0, 1)                           # (B,nq,H,qc,d)
    return y.transpose(0, 1, 3, 2, 4).reshape(B, nq * qc, H, hdv)


def _fwd_blocks(q, k, v, causal, window, q_chunk, k_chunk, scale, schedule,
                shard=None):
    """Shared forward: returns (y, lse) with lse (B,H,S) for the backward."""
    shard = shard or (lambda t, kind: t)
    B, S, H, hd = q.shape
    Sk = k.shape[1]
    hdv = v.shape[-1]
    nq, nk = S // q_chunk, Sk // k_chunk
    qb = shard(jnp.moveaxis(q.reshape(B, nq, q_chunk, H, hd), 1, 0), "qkv")
    kb = shard(jnp.moveaxis(k.reshape(B, nk, k_chunk, H, hd), 1, 0), "qkv")
    vb = shard(jnp.moveaxis(v.reshape(B, nk, k_chunk, H, hdv), 1, 0), "qkv")

    if schedule == "rect":
        pairs = [(i, j) for i in range(nq) for j in range(nk)]
        i_arr = np.asarray([p[0] for p in pairs], np.int32)
        j_arr = np.asarray([p[1] for p in pairs], np.int32)
    else:
        i_arr, j_arr = _block_pairs(nq, nk, q_chunk, k_chunk, causal, window)

    def pair_step(carry, ij):
        m, l, acc = carry                                # (nq,B,H,qc[,d])
        i, j = ij
        q_blk = jax.lax.dynamic_index_in_dim(qb, i, 0, keepdims=False)
        k_blk = jax.lax.dynamic_index_in_dim(kb, j, 0, keepdims=False)
        v_blk = jax.lax.dynamic_index_in_dim(vb, j, 0, keepdims=False)
        gq = i * q_chunk + jnp.arange(q_chunk)
        gk = j * k_chunk + jnp.arange(k_chunk)
        s = _block_scores(q_blk, k_blk, scale, gq, gk, causal, window)
        m_i = jax.lax.dynamic_index_in_dim(m, i, 0, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False)
        a_i = jax.lax.dynamic_index_in_dim(acc, i, 0, keepdims=False)
        m_new = jnp.maximum(m_i, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_i - m_new)
        l_i = l_i * corr + p.sum(-1)
        a_i = a_i * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_i, i, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_i, i, 0)
        return (m, l, acc), None

    m0 = shard(jnp.full((nq, B, H, q_chunk), NEG_INF, jnp.float32), "ml")
    l0 = shard(jnp.zeros((nq, B, H, q_chunk), jnp.float32), "ml")
    a0 = shard(jnp.zeros((nq, B, H, q_chunk, hdv), jnp.float32), "acc")
    (m, l, acc), _ = jax.lax.scan(
        pair_step, (m0, l0, a0),
        (jnp.asarray(i_arr), jnp.asarray(j_arr)))
    l_safe = jnp.maximum(l, 1e-30)
    y = _unblock(acc / l_safe[..., None], B, S, H, hdv).astype(q.dtype)
    lse = m + jnp.log(l_safe)                            # (nq,B,H,qc)
    lse = jnp.moveaxis(lse, 0, 1).transpose(0, 2, 1, 3).reshape(B, H, S)
    return y, lse, (i_arr, j_arr)


def _rules_key(rules):
    if rules is None:
        return None
    return tuple(sorted(((k, tuple(v)) for k, v in rules.items()
                         if isinstance(v, (tuple, list))),
                        key=lambda kv: str(kv[0])))


def _constrain_blocks(mesh, rules_key, *, heads_sharded=True):
    """Sharding constraint fn for blocked (n, B, a, H, b)-style tensors.
    GSPMD propagates poorly through scan carries — without explicit
    constraints it re-gathers full score blocks every pair step."""
    if mesh is None:
        return lambda t, kind: t
    from repro.models.partitioning import spec_for
    from jax.sharding import NamedSharding
    rules = dict(kind_v for kind_v in rules_key) if rules_key else None

    def c(t, logical):
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, spec_for(logical, mesh, rules)))

    h = "heads" if heads_sharded else None

    def apply(t, kind):
        if kind == "qkv":      # (n, B, c, H, d)
            return c(t, (None, "batch", None, h, None))
        if kind == "ml":       # (nq, B, H, qc)
            return c(t, (None, "batch", h, None))
        if kind == "acc":      # (nq, B, H, qc, d)
            return c(t, (None, "batch", h, None, None))
        return t
    return apply


_FLASH_CACHE: dict = {}


def _flash_core(causal, window, q_chunk, k_chunk, scale, schedule,
                mesh=None, rules_key=None):
    key = (causal, window, q_chunk, k_chunk, scale, schedule, mesh,
           rules_key)
    if key in _FLASH_CACHE:
        return _FLASH_CACHE[key]
    shard = _constrain_blocks(mesh, rules_key)

    @jax.custom_vjp
    def core(q, k, v):
        y, _, _ = _fwd_blocks(q, k, v, causal, window, q_chunk, k_chunk,
                              scale, schedule, shard)
        return y

    def fwd(q, k, v):
        y, lse, _ = _fwd_blocks(q, k, v, causal, window, q_chunk, k_chunk,
                                scale, schedule, shard)
        return y, (q, k, v, y, lse)

    def bwd(res, dy):
        q, k, v, y, lse = res
        B, S, H, hd = q.shape
        Sk = k.shape[1]
        hdv = v.shape[-1]
        nq, nk = S // q_chunk, Sk // k_chunk
        if schedule == "rect":
            pairs = [(i, j) for i in range(nq) for j in range(nk)]
            i_arr = np.asarray([p[0] for p in pairs], np.int32)
            j_arr = np.asarray([p[1] for p in pairs], np.int32)
        else:
            i_arr, j_arr = _block_pairs(nq, nk, q_chunk, k_chunk, causal,
                                        window)

        def blk(t, c, d_last):
            n = t.shape[1] // c
            return jnp.moveaxis(t.reshape(B, n, c, H, d_last), 1, 0)

        qb = shard(blk(q, q_chunk, hd), "qkv")
        kb = shard(blk(k, k_chunk, hd), "qkv")
        vb = shard(blk(v, k_chunk, hdv), "qkv")
        dyb = shard(blk(dy.astype(jnp.float32), q_chunk, hdv), "qkv")
        # D_i = rowsum(dy * y)
        Dr = jnp.sum(dy.astype(jnp.float32) * y.astype(jnp.float32), -1)
        Drb = jnp.moveaxis(Dr.reshape(B, nq, q_chunk, H), 1, 0) \
            .transpose(0, 1, 3, 2)                       # (nq,B,H,qc)
        lseb = lse.reshape(B, H, nq, q_chunk).transpose(2, 0, 1, 3)

        def pair_step(carry, ij):
            dq, dk, dv = carry
            i, j = ij
            q_blk = jax.lax.dynamic_index_in_dim(qb, i, 0, keepdims=False)
            k_blk = jax.lax.dynamic_index_in_dim(kb, j, 0, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vb, j, 0, keepdims=False)
            dy_blk = jax.lax.dynamic_index_in_dim(dyb, i, 0, keepdims=False)
            D_blk = jax.lax.dynamic_index_in_dim(Drb, i, 0, keepdims=False)
            lse_blk = jax.lax.dynamic_index_in_dim(lseb, i, 0,
                                                   keepdims=False)
            gq = i * q_chunk + jnp.arange(q_chunk)
            gk = j * k_chunk + jnp.arange(k_chunk)
            s = _block_scores(q_blk, k_blk, scale, gq, gk, causal, window)
            p = jnp.exp(s - lse_blk[..., None])          # (B,H,qc,kc)
            dv_j = jnp.einsum("bhqk,bqhd->bkhd", p, dy_blk)
            dp = jnp.einsum("bqhd,bkhd->bhqk", dy_blk,
                            v_blk.astype(jnp.float32))
            ds = p * (dp - D_blk[..., None]) * scale
            dq_i = jnp.einsum("bhqk,bkhd->bqhd", ds,
                              k_blk.astype(jnp.float32))
            dk_j = jnp.einsum("bhqk,bqhd->bkhd", ds,
                              q_blk.astype(jnp.float32))
            upd = jax.lax.dynamic_update_index_in_dim
            dq = upd(dq, jax.lax.dynamic_index_in_dim(
                dq, i, 0, keepdims=False) + dq_i, i, 0)
            dk = upd(dk, jax.lax.dynamic_index_in_dim(
                dk, j, 0, keepdims=False) + dk_j, j, 0)
            dv = upd(dv, jax.lax.dynamic_index_in_dim(
                dv, j, 0, keepdims=False) + dv_j, j, 0)
            return (dq, dk, dv), None

        dq0 = shard(jnp.zeros((nq, B, q_chunk, H, hd), jnp.float32), "qkv")
        dk0 = shard(jnp.zeros((nk, B, k_chunk, H, hd), jnp.float32), "qkv")
        dv0 = shard(jnp.zeros((nk, B, k_chunk, H, hdv), jnp.float32), "qkv")
        (dq, dk, dv), _ = jax.lax.scan(
            pair_step, (dq0, dk0, dv0),
            (jnp.asarray(i_arr), jnp.asarray(j_arr)))

        def unblk(t, c, d_last, n):
            return jnp.moveaxis(t, 0, 1).reshape(B, n * c, H, d_last)

        return (unblk(dq, q_chunk, hd, nq).astype(q.dtype),
                unblk(dk, k_chunk, hd, nk).astype(k.dtype),
                unblk(dv, k_chunk, hdv, nk).astype(v.dtype))

    core.defvjp(fwd, bwd)
    _FLASH_CACHE[key] = core
    return core


def reference_attention(q, k, v, *, causal=True, window=0, scale=None):
    """O(S²)-memory oracle for tests."""
    B, S, H, hd = q.shape
    KH = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if H != KH:
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    gq = jnp.arange(S)
    gk = jnp.arange(k.shape[1])
    allow = jnp.ones((S, k.shape[1]), bool)
    if causal:
        allow &= gk[None, :] <= gq[:, None]
    if window:
        allow &= gk[None, :] > gq[:, None] - window
    s = jnp.where(allow[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    y = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return y.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode (one query token against a KV cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     scale: Optional[float] = None):
    """q: (B, H, hd); caches: (B, Smax, KH, hd). ``pos``: current position
    (the new token's K/V must already be written at index ``pos`` — or at
    ``pos % window`` for a ring-buffer SWA cache)."""
    B, H, hd = q.shape
    Smax, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KH, G, hd)
    # keep the cache in bf16: a cast to f32 here would double the bytes
    # the contractions read. MXU accumulates in f32 via
    # preferred_element_type.
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(k_cache.dtype), k_cache,
                   preferred_element_type=jnp.float32) * scale
    if window:
        # ring buffer: all slots valid once pos >= window-1
        valid = jnp.arange(Smax) <= pos
        valid = valid | (pos >= Smax)
    else:
        valid = jnp.arange(Smax) <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / p.sum(-1, keepdims=True)
    y = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return y.reshape(B, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# DeepSeek-V2 Multi-head Latent Attention
# ---------------------------------------------------------------------------

def mla_prefill(p, x, cos, sin, cfg, dtype, mesh=None, rules=None):
    """Full (decompressed) MLA for train/prefill. Returns (out, (ckv, k_rope))
    so serving can keep only the compressed cache."""
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dtype))
    q = q.reshape(B, S, H, nope + rope_d)
    qn, qr = q[..., :nope], q[..., nope:]
    qr = apply_rope(qr, cos, sin)

    dkv = jnp.einsum("bsd,de->bse", x, p["wdkv"].astype(dtype))
    ckv = rms_norm(dkv[..., :m.kv_lora_rank], p["ckv_norm"], cfg.norm_eps)
    kr = apply_rope(dkv[..., None, m.kv_lora_rank:], cos, sin)  # (B,S,1,r)

    kn = jnp.einsum("bsl,lhn->bshn", ckv,
                    p["wuk"].reshape(m.kv_lora_rank, H, nope).astype(dtype))
    v = jnp.einsum("bsl,lhv->bshv", ckv,
                   p["wuv"].reshape(m.kv_lora_rank, H, vd).astype(dtype))
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (B, S, H, rope_d))], -1)
    qf = jnp.concatenate([qn, qr], -1)
    y = flash_attention(qf, k, v, causal=True, q_chunk=cfg.attn_q_chunk,
                        scale=1.0 / math.sqrt(nope + rope_d),
                        mesh=mesh, rules=rules)
    out = jnp.einsum("bshv,hvd->bsd", y,
                     p["wo"].reshape(H, vd, D).astype(dtype))
    return out, (ckv, kr[:, :, 0, :])


def mla_decode(p, x, ckv_cache, kr_cache, pos, cos, sin, cfg, dtype):
    """Absorbed-matrix MLA decode: attention runs directly in the latent
    space (scores vs compressed cache), never materializing per-head K/V.
    x: (B, 1, D); caches (B, Smax, lora) / (B, Smax, rope_d)."""
    m = cfg.mla
    B, _, D = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    lora = m.kv_lora_rank

    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dtype))
    q = q.reshape(B, H, nope + rope_d)
    qn, qr = q[..., :nope], q[..., nope:]
    qr = apply_rope(qr[:, None], cos, sin)[:, 0]          # (B,H,r)

    dkv = jnp.einsum("bd,de->be", x[:, 0], p["wdkv"].astype(dtype))
    ckv_new = rms_norm(dkv[..., :lora], p["ckv_norm"], cfg.norm_eps)
    kr_new = apply_rope(dkv[:, None, None, lora:], cos, sin)[:, 0, 0]

    ckv_cache = jax.lax.dynamic_update_slice(
        ckv_cache, ckv_new[:, None].astype(ckv_cache.dtype), (0, pos, 0))
    kr_cache = jax.lax.dynamic_update_slice(
        kr_cache, kr_new[:, None].astype(kr_cache.dtype), (0, pos, 0))

    wuk = p["wuk"].reshape(lora, H, nope).astype(dtype)
    q_abs = jnp.einsum("bhn,lhn->bhl", qn, wuk)           # absorb W_uk
    s = (jnp.einsum("bhl,bsl->bhs", q_abs.astype(ckv_cache.dtype),
                    ckv_cache, preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", qr.astype(kr_cache.dtype), kr_cache,
                      preferred_element_type=jnp.float32))
    s *= 1.0 / math.sqrt(nope + rope_d)
    s = jnp.where((jnp.arange(ckv_cache.shape[1]) <= pos)[None, None], s,
                  NEG_INF)
    p_att = jax.nn.softmax(s, axis=-1)
    ol = jnp.einsum("bhs,bsl->bhl", p_att.astype(ckv_cache.dtype),
                    ckv_cache, preferred_element_type=jnp.float32)
    wuv = p["wuv"].reshape(lora, H, vd).astype(dtype)
    y = jnp.einsum("bhl,lhv->bhv", ol.astype(dtype), wuv)
    out = jnp.einsum("bhv,hvd->bd", y, p["wo"].reshape(H, vd, D).astype(dtype))
    return out[:, None], ckv_cache, kr_cache
