"""Model assembly for every assigned architecture family.

Everything is functional: ``param_defs(cfg)`` declares the parameter tree
(shapes + logical sharding axes), ``forward`` / ``decode_step`` consume it.
Layers are stacked and executed with ``lax.scan`` (+ optional remat) so the
HLO stays compact for 88–95-layer archs.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import moe as moe_mod
from repro.models.layers import (ParamDef, abstract, apply_rope, materialize,
                                 mlp_apply, mlp_defs, padded_vocab,
                                 rms_norm, rope_cos_sin, mrope_cos_sin,
                                 sinusoidal_positions, specs)

# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------

def _attn_defs(cfg, ll=()) -> dict:
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    Lax = tuple("layers" for _ in ll)
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        return {
            "wq": ParamDef(ll + (d, H * qk), Lax + ("embed", "heads")),
            "wdkv": ParamDef(ll + (d, m.kv_lora_rank + m.qk_rope_head_dim),
                             Lax + ("embed", None)),
            "ckv_norm": ParamDef(ll + (m.kv_lora_rank,), Lax + (None,),
                                 init="ones"),
            "wuk": ParamDef(ll + (m.kv_lora_rank, H * m.qk_nope_head_dim),
                            Lax + (None, "heads")),
            "wuv": ParamDef(ll + (m.kv_lora_rank, H * m.v_head_dim),
                            Lax + (None, "heads")),
            "wo": ParamDef(ll + (H * m.v_head_dim, d),
                           Lax + ("heads", "embed")),
        }
    return {
        "wq": ParamDef(ll + (d, H * hd), Lax + ("embed", "heads")),
        "wk": ParamDef(ll + (d, KH * hd), Lax + ("embed", "kv_heads")),
        "wv": ParamDef(ll + (d, KH * hd), Lax + ("embed", "kv_heads")),
        "wo": ParamDef(ll + (H * hd, d), Lax + ("heads", "embed")),
    }


def _block_defs(cfg, ll=(), *, moe_layer: bool) -> dict:
    d = cfg.d_model
    Lax = tuple("layers" for _ in ll)
    out = {
        "ln1": ParamDef(ll + (d,), Lax + ("embed",), init="ones"),
        "ln2": ParamDef(ll + (d,), Lax + ("embed",), init="ones"),
        "attn": _attn_defs(cfg, ll),
    }
    if moe_layer:
        out["moe"] = moe_mod.moe_defs(cfg, ll)
    else:
        out["mlp"] = mlp_defs(cfg, cfg.d_ff, ll=ll)
    return out


def _shared_block_defs(cfg) -> dict:
    """A Zamba2 shared block: attention over [x, embedding] (2·d wide),
    then the gated GELU MLP."""
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return {
        "ln_in": ParamDef((2 * d,), ("embed",), init="ones"),
        "attn": {
            "wq": ParamDef((2 * d, H * hd), ("embed", "heads")),
            "wk": ParamDef((2 * d, KH * hd), ("embed", "kv_heads")),
            "wv": ParamDef((2 * d, KH * hd), ("embed", "kv_heads")),
            "wo": ParamDef((H * hd, d), ("heads", "embed")),
        },
        "ln_ff": ParamDef((d,), ("embed",), init="ones"),
        "mlp": mlp_defs(cfg, cfg.d_ff),
    }


def _site_defs(cfg) -> dict:
    """What one site of a shared block holds alone: the linear into the
    next Mamba layer and the adapter of the MLP's gate/up projection."""
    d, r, ff = cfg.d_model, cfg.adapter_rank, cfg.d_ff
    out = {"linear": ParamDef((d, d), ("embed", None))}
    if r:
        out["adapter_in"] = ParamDef((d, r), ("embed", None))
        out["adapter_g"] = ParamDef((r, ff), (None, "mlp"))
        out["adapter_u"] = ParamDef((r, ff), (None, "mlp"))
    return out


def param_defs(cfg) -> dict:
    d = cfg.d_model
    V = padded_vocab(cfg.vocab_size)
    L = cfg.n_layers
    defs: Dict[str, Any] = {}

    if cfg.n_codebooks:
        defs["embed"] = ParamDef((cfg.n_codebooks, V, d),
                                 (None, "vocab", "embed"))
    else:
        defs["embed"] = ParamDef((V, d), ("vocab", "embed"))
    defs["final_norm"] = ParamDef((d,), ("embed",), init="ones")
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            defs["head"] = ParamDef((d, cfg.n_codebooks * V),
                                    ("embed", "vocab"))
        else:
            defs["head"] = ParamDef((d, V), ("embed", "vocab"))

    fam = cfg.family
    if fam == "ssm":
        defs["layers"] = mam.mamba_defs(cfg, ll=(L,))
    elif fam == "hybrid":
        defs["layers"] = mam.mamba_defs(cfg, ll=(L,))
        defs["shared"] = [_shared_block_defs(cfg)
                          for _ in range(cfg.num_mem_blocks)]
        defs["sites"] = [_site_defs(cfg) for _ in cfg.hybrid_sites]
    elif fam == "moe":
        fk = cfg.moe.first_k_dense
        if fk:
            defs["dense_layers"] = _block_defs(cfg, (fk,), moe_layer=False)
        defs["layers"] = _block_defs(cfg, (L - fk,), moe_layer=True)
    else:  # dense / vlm / audio
        defs["layers"] = _block_defs(cfg, (L,), moe_layer=False)
    return defs


def _apply_param_dtype(cfg, defs):
    """Honor cfg.param_dtype (e.g. bf16 params + fp32 optimizer moments:
    FSDP gathers then move half the bytes; see EXPERIMENTS §Perf)."""
    if cfg.param_dtype == "float32":
        return defs
    import dataclasses as _dc
    return jax.tree_util.tree_map(
        lambda pd: _dc.replace(pd, dtype=cfg.param_dtype)
        if pd.dtype == "float32" else pd,
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def abstract_params(cfg):
    return abstract(_apply_param_dtype(cfg, param_defs(cfg)))


def init_params(cfg, key):
    return materialize(_apply_param_dtype(cfg, param_defs(cfg)), key)


def param_specs(cfg, mesh, rules=None):
    return specs(param_defs(cfg), mesh, rules)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(cfg, params, tokens, dtype):
    emb = params["embed"].astype(dtype)
    if cfg.n_codebooks:                    # (B,S,K) -> sum_k emb[k][tok]
        per = [emb[k][tokens[..., k]] for k in range(cfg.n_codebooks)]
        x = sum(per)
    else:
        x = emb[tokens]
    return x


def lm_head(cfg, params, x, dtype):
    V = padded_vocab(cfg.vocab_size)
    if cfg.tie_embeddings:
        w = params["embed"].astype(dtype)
        return jnp.einsum("bsd,vd->bsv", x, w)
    w = params["head"].astype(dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if cfg.n_codebooks:
        B, S = x.shape[:2]
        return logits.reshape(B, S, cfg.n_codebooks, V)
    return logits


# ---------------------------------------------------------------------------
# Transformer block (train / prefill)
# ---------------------------------------------------------------------------

def _transformer_block(cfg, p, x, cos, sin, dtype, *, moe_layer: bool,
                       collect_cache: bool = False, mesh=None, rules=None):
    from repro.models.partitioning import constrain as _pc
    B, S, D = x.shape
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.sp_norm and mesh is not None:
        # §Perf lever (Megatron-SP): run the norm sequence-sharded, then do
        # ONE explicit bf16 all-gather of the normed activations going into
        # the projections. Without this, GSPMD reshards the GQA-repeated
        # K/V from seq-sharded to head-sharded INSIDE the attention scan —
        # an "involuntary full rematerialization" (548 GB of gathers per
        # step for deepseek-67b; see EXPERIMENTS §Perf).
        h = _pc(h, mesh, "batch", "act_seq", None, rules=rules)
        h = _pc(h, mesh, "batch", None, None, rules=rules)
    cache = None
    if cfg.mla is not None:
        y, cache = attn.mla_prefill(p["attn"], h, cos, sin, cfg, dtype,
                                    mesh=mesh, rules=rules)
    else:
        pa = p["attn"]
        q = jnp.einsum("bsd,de->bse", h, pa["wq"].astype(dtype))
        k = jnp.einsum("bsd,de->bse", h, pa["wk"].astype(dtype))
        v = jnp.einsum("bsd,de->bse", h, pa["wv"].astype(dtype))
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, KH, hd)
        v = v.reshape(B, S, KH, hd)
        if cos is not None:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if collect_cache:
            cache = (k, v)
        # heads that don't divide the model axis (yi: 56, qwen2-vl: 12)
        # are zero-padded AFTER the GQA group expansion so the q→kv-group
        # mapping stays correct; padded heads are sliced off again.
        tp = dict(mesh.shape).get("model", 1) if mesh is not None else 1
        Hp = -(-H // tp) * tp
        if Hp != H:
            k = jnp.repeat(k, H // KH, axis=2)
            v = jnp.repeat(v, H // KH, axis=2)
            padw = ((0, 0), (0, 0), (0, Hp - H), (0, 0))
            q = jnp.pad(q, padw)
            k = jnp.pad(k, padw)
            v = jnp.pad(v, padw)
        o = attn.flash_attention(q, k, v, causal=True,
                                 window=cfg.swa_window,
                                 q_chunk=cfg.attn_q_chunk,
                                 scale=1.0 / math.sqrt(hd),
                                 schedule=cfg.attn_schedule,
                                 mesh=mesh, rules=rules)
        if Hp != H:
            o = o[:, :, :H, :]
        y = jnp.einsum("bshd,hdD->bsD",
                       o, pa["wo"].reshape(H, hd, D).astype(dtype))
    x = x + y
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.sp_norm and mesh is not None and not moe_layer:
        h2 = _pc(h2, mesh, "batch", "act_seq", None, rules=rules)
        h2 = _pc(h2, mesh, "batch", None, None, rules=rules)
    aux = 0.0
    if moe_layer:
        f, aux = moe_mod.moe_ffn(cfg, p["moe"], h2, dtype, mesh=mesh,
                                 rules=rules)
    else:
        f = mlp_apply(cfg, p["mlp"], h2, dtype)
    return x + f, aux, cache


def _maybe_remat(fn, cfg):
    if cfg.remat:
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    return fn


def _cast_stacked(cfg, stacked, dtype):
    """§Perf lever: cast the stacked layer params to the compute dtype
    BEFORE the scan, so per-layer FSDP all-gathers move bf16 (half the
    bytes). Differentiable (grads flow through the convert)."""
    if not cfg.bf16_stacked_params:
        return stacked
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, stacked)


def _scan_blocks(cfg, stacked, x, cos, sin, dtype, *, moe_layer,
                 collect_cache=False, mesh=None, rules=None):
    from repro.models.partitioning import constrain
    stacked = _cast_stacked(cfg, stacked, dtype)

    def body(carry, p_l):
        xc = carry
        if mesh is not None:
            xc = constrain(xc, mesh, "batch", "act_seq", None, rules=rules)
        y, aux, cache = _transformer_block(cfg, p_l, xc, cos, sin, dtype,
                                           moe_layer=moe_layer,
                                           collect_cache=collect_cache,
                                           mesh=mesh, rules=rules)
        return y, (aux, cache) if collect_cache else (aux, None)

    body = _maybe_remat(body, cfg)
    x, (auxs, caches) = jax.lax.scan(body, x, stacked)
    return x, jnp.sum(jnp.asarray(auxs)) if moe_layer else 0.0, caches


# ---------------------------------------------------------------------------
# Zamba2 hybrid stack (train / prefill / decode)
# ---------------------------------------------------------------------------

def _site_scale(cfg) -> float:
    """Zamba2 scales its attention scores by (head_dim / 2) ** -0.5."""
    return (cfg.hd / 2) ** -0.5


def _site_attn_prefill(cfg, blk, h, cos, sin, dtype):
    """Causal attention of a shared block over h = norm([x, e]) (B,S,2D).
    Returns (its output (B,S,D), this site's (k, v))."""
    B, S, _ = h.shape
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pa = blk["attn"]
    q, k, v = (jnp.einsum("bsd,de->bse", h, pa[w].astype(dtype))
               .reshape(B, S, n, hd)
               for w, n in (("wq", H), ("wk", KH), ("wv", KH)))
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    o = attn.flash_attention(q, k, v, causal=True, q_chunk=cfg.attn_q_chunk,
                             scale=_site_scale(cfg),
                             schedule=cfg.attn_schedule)
    y = jnp.einsum("bshd,hdD->bsD",
                   o, pa["wo"].reshape(H, hd, cfg.d_model).astype(dtype))
    return y, (k, v)


def _site_mlp(cfg, blk, site, y, dtype):
    """down(gelu(g) * u) with [g, u] = W·h + B_s(A_s·h), h = norm(y): the
    shared block's MLP with the site's adapter (exact, erf GELU)."""
    h = rms_norm(y, blk["ln_ff"], cfg.norm_eps)
    m = blk["mlp"]
    g = jnp.einsum("bsd,df->bsf", h, m["w1"].astype(dtype))
    u = jnp.einsum("bsd,df->bsf", h, m["w3"].astype(dtype))
    if "adapter_in" in site:
        a = jnp.einsum("bsd,dr->bsr", h, site["adapter_in"].astype(dtype))
        g = g + jnp.einsum("bsr,rf->bsf", a, site["adapter_g"].astype(dtype))
        u = u + jnp.einsum("bsr,rf->bsf", a, site["adapter_u"].astype(dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * u,
                      m["w2"].astype(dtype))


@partial(jax.jit, static_argnames=("cfg", "dtype", "use_pallas"))
def _hybrid_stack(cfg, params, x, states, kv, cos, sin, pos, dtype,
                  use_pallas=False):
    """The Zamba2 pattern over x (B,S,D), the embedding output. Before
    Mamba layer i = hybrid_sites[k], shared block k % num_mem_blocks reads
    [x, embedding] and the site's linear gives t; that layer then computes
    x + Mamba(norm(x + t)), and every other layer x + Mamba(norm(x)).

    ``states`` holds every layer's SSM and conv state (row i: layer i; the
    SSM state in the decode cache's layout), rewritten row by row in
    place. In prefill (``pos`` None) the layers start from zero state and
    each site appends its (k, v) to ``kv``; in decode they continue from
    ``states`` and the sites attend over their rows of the caches ``kv`` =
    (k, v), writing the token at ``pos``. Runs of Mamba layers between
    sites are loops over the stacked layers. One jitted call, so that
    eager callers compile the stack once.
    Returns (x, states, kv)."""
    e = x
    sites = cfg.hybrid_sites
    names = ("ssm", "conv_x", "conv_b", "conv_c")
    decode = pos is not None

    def layer(i, x, t, states):
        at = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,      # noqa
                                                    keepdims=False)
        p = jax.tree_util.tree_map(at, params["layers"])
        u = x if t is None else x + t
        if decode:
            y, ssm, conv = mam.mamba_decode(
                cfg, p, u, states["ssm"], i,
                tuple(at(states[n]) for n in names[1:]), dtype,
                use_pallas=use_pallas)
            states = dict(states, ssm=ssm)
            new = dict(zip(names[1:], conv))
        else:
            y, st, conv = mam.mamba_block(cfg, p, u, dtype,
                                          return_state=True,
                                          use_pallas=use_pallas)
            new = dict(zip(names, (st.reshape(states["ssm"].shape[1:]),)
                           + conv))
        return x + y, {n: jax.lax.dynamic_update_index_in_dim(
            a, new[n].astype(a.dtype), i, 0) if n in new else a
            for n, a in states.items()}

    def site_attn(blk, h, k, kv):
        if not decode:
            y, kv_k = _site_attn_prefill(cfg, blk, h, cos, sin, dtype)
            return y, kv + (kv_k,)
        y, kc, vc = _decode_attn(cfg, blk["attn"], h, *kv, k, pos, cos, sin,
                                 dtype, scale=_site_scale(cfg))
        return y[:, None], (kc, vc)

    starts = sorted({0, *sites})
    for lo, hi in zip(starts, starts[1:] + [cfg.n_layers]):
        t = None
        if lo in sites:
            k = sites.index(lo)
            blk, site = params["shared"][k % cfg.num_mem_blocks], \
                params["sites"][k]
            with jax.named_scope("hybrid.shared"), \
                    jax.named_scope(f"site{k}"):
                h = rms_norm(jnp.concatenate([x, e], -1), blk["ln_in"],
                             cfg.norm_eps)
                y, kv = site_attn(blk, h, k, kv)
                t = _site_mlp(cfg, blk, site, y, dtype)
            with jax.named_scope("hybrid.site_linear"):
                t = jnp.einsum("bsd,de->bse", t, site["linear"].astype(dtype))
        with jax.named_scope("hybrid.mamba"):
            if t is not None:
                x, states = layer(lo, x, t, states)
                lo += 1
            if lo < hi:
                x, states = jax.lax.fori_loop(
                    lo, hi, lambda i, c: layer(i, c[0], None, c[1]),
                    (x, states))
    return x, states, kv


# ---------------------------------------------------------------------------
# Forward (train & prefill share this; prefill also returns the KV cache)
# ---------------------------------------------------------------------------

def forward(cfg, params, batch, *, mesh=None, rules=None,
            collect_cache: bool = False):
    """batch: dict with 'tokens' (B,S[,K]) or 'embeds' (B,S,D) (+ 'pos3').

    Returns (logits, aux_loss, cache_or_None).
    """
    dtype = cfg.compute_dt()
    if "embeds" in batch:
        x = batch["embeds"].astype(dtype)
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape[:2]
        x = embed_tokens(cfg, params, tokens, dtype)

    cos = sin = None
    if cfg.family == "audio":
        pos_tab = jnp.asarray(sinusoidal_positions(S, cfg.d_model), dtype)
        x = x + pos_tab[None]
    elif cfg.family == "vlm":
        pos3 = batch.get("pos3")
        if pos3 is None:
            p1 = jnp.arange(S)[None].repeat(B, 0)
            pos3 = jnp.stack([p1, p1, p1])
        cos, sin = mrope_cos_sin(pos3, cfg.hd, cfg.rope_theta,
                                 cfg.mrope_sections)
    elif cfg.family in ("dense", "moe", "hybrid"):
        rope_dim = (cfg.mla.qk_rope_head_dim if cfg.mla is not None
                    else cfg.hd)
        cos, sin = rope_cos_sin(jnp.arange(S), rope_dim, cfg.rope_theta)

    aux_total = 0.0
    caches: Dict[str, Any] = {}

    from repro.models.partitioning import constrain as _constrain

    def _cstr(t):
        if mesh is None:
            return t
        return _constrain(t, mesh, "batch", "act_seq", None, rules=rules)

    fam = cfg.family
    if fam == "ssm":
        def body(carry, p_l):
            p_l = _cast_stacked(cfg, p_l, dtype)
            xc = _cstr(carry)
            y, st, conv = mam.mamba_block(cfg, p_l, xc, dtype,
                                          return_state=True,
                                          use_pallas=cfg.use_pallas,
                                          mesh=mesh, rules=rules)
            return carry + y, (st, conv)
        body = _maybe_remat(body, cfg)
        x, (states, convs) = jax.lax.scan(body, x, params["layers"])
        caches["ssm"] = states
        caches["conv_x"], caches["conv_b"], caches["conv_c"] = convs
    elif fam == "hybrid":
        states = {n: jnp.zeros(sd.shape, sd.dtype) for n, sd in
                  abstract_cache(cfg, S, B).items() if n not in ("k", "v")}
        x, states, kv = _hybrid_stack(cfg, params, x, states, (), cos, sin,
                                      None, dtype, use_pallas=cfg.use_pallas)
        caches.update(states)
        if collect_cache:
            caches["kv"] = tuple(jnp.stack(t) for t in zip(*kv))
    elif fam == "moe":
        fk = cfg.moe.first_k_dense
        if fk:
            x, aux_d, cache_d = _scan_blocks(
                cfg, params["dense_layers"], x, cos, sin, dtype,
                moe_layer=False, collect_cache=collect_cache,
                mesh=mesh, rules=rules)
            if collect_cache:
                caches["kv_dense"] = cache_d
        x, aux_total, cache_m = _scan_blocks(
            cfg, params["layers"], x, cos, sin, dtype, moe_layer=True,
            collect_cache=collect_cache, mesh=mesh, rules=rules)
        if collect_cache:
            caches["kv"] = cache_m
    else:  # dense / vlm / audio
        x, _, cache = _scan_blocks(
            cfg, params["layers"], x, cos, sin, dtype, moe_layer=False,
            collect_cache=collect_cache, mesh=mesh, rules=rules)
        if collect_cache:
            caches["kv"] = cache

    x = _cstr(rms_norm(x, params["final_norm"], cfg.norm_eps))
    logits = lm_head(cfg, params, x, dtype)
    return logits, aux_total, (caches if (collect_cache or fam in
                                          ("ssm", "hybrid")) else None)




def prefill_cache(cfg, caches, S: int) -> dict:
    """Reformat forward(collect_cache=True) output into the decode cache
    layout (same keys/shapes as cache_spec_defs). SWA archs keep the last
    ``window`` positions — with window | S these land in ring order."""
    out = {}
    win = cfg.swa_window

    def ring(t):                       # t: (L,B,S,KH,hd) -> (L,B,KH·hd,S)
        if win and t.shape[2] > win:
            t = t[:, :, -win:]
        L, B, S = t.shape[:3]
        return jnp.moveaxis(t, 2, -1).reshape(L, B, -1, S).astype(jnp.bfloat16)

    fam = cfg.family
    if fam in ("ssm", "hybrid"):
        st = caches["ssm"]
        out["ssm"] = st.reshape(st.shape[:3] + mam.state_layout(
            cfg.ssm.headdim, cfg.ssm.d_state)).astype(jnp.float32)
        for n in ("conv_x", "conv_b", "conv_c"):
            out[n] = caches[n].astype(jnp.bfloat16)
    if fam == "hybrid":
        k, v = caches["kv"]
        out["k"], out["v"] = ring(k), ring(v)
    elif fam == "moe" and cfg.mla is not None:
        ckv, kr = caches["kv"]
        if "kv_dense" in caches:
            ckv_d, kr_d = caches["kv_dense"]
            ckv = jnp.concatenate([ckv_d, ckv], axis=0)
            kr = jnp.concatenate([kr_d, kr], axis=0)
        out["ckv"] = ckv.astype(jnp.bfloat16)
        out["kr"] = kr.astype(jnp.bfloat16)
    elif fam in ("dense", "vlm", "audio", "moe"):
        k, v = caches["kv"]
        out["k"], out["v"] = ring(k), ring(v)
    return out


# ---------------------------------------------------------------------------
# Decode (serve_step): one token against a KV cache / SSM state
# ---------------------------------------------------------------------------

def cache_spec_defs(cfg, max_len: int, batch: int) -> dict:
    """Declarative cache layout → ParamDefs (reuse abstract/specs helpers).

    A K or V cache is (layers, B, KH·hd, S): the sequence is the minor
    axis, which is how the prefill's attention leaves it and how decode's
    score and value contractions read it, so neither relays it out. The
    SSM state stores each head's (hp, ns) as ``mamba.state_layout`` says,
    rows as wide as the decode kernel reads them."""
    dt = "bfloat16"
    L = cfg.n_layers
    fam = cfg.family
    win = cfg.swa_window
    S = min(max_len, win) if win else max_len
    defs: Dict[str, Any] = {}

    def kv(n):
        return ParamDef((n, batch, cfg.n_kv_heads * cfg.hd, S),
                        ("layers", "batch", "kv_heads", "kv_seq"), dtype=dt)
    if fam in ("dense", "vlm", "audio") or (fam == "moe" and cfg.mla is None):
        defs["k"] = defs["v"] = kv(L)
    elif fam == "moe":                     # MLA: compressed latent cache
        m = cfg.mla
        defs["ckv"] = ParamDef((L, batch, S, m.kv_lora_rank),
                               ("layers", "batch", "kv_seq", None), dtype=dt)
        defs["kr"] = ParamDef((L, batch, S, m.qk_rope_head_dim),
                              ("layers", "batch", "kv_seq", None), dtype=dt)
    if fam in ("ssm", "hybrid"):
        s = cfg.ssm
        di, nh, ns = s.d_inner(cfg.d_model), s.n_heads(cfg.d_model), s.d_state
        gns = s.ngroups * ns
        hax = "ssm_heads" if nh % 16 == 0 else "ssm_heads_rep"
        defs["ssm"] = ParamDef((L, batch, nh)
                               + mam.state_layout(s.headdim, ns),
                               ("layers", "batch", hax, None, "ssm_state"),
                               dtype="float32")
        defs["conv_x"] = ParamDef((L, batch, s.d_conv - 1, di),
                                  ("layers", "batch", None, hax), dtype=dt)
        defs["conv_b"] = ParamDef((L, batch, s.d_conv - 1, gns),
                                  ("layers", "batch", None, "ssm_state"),
                                  dtype=dt)
        defs["conv_c"] = ParamDef((L, batch, s.d_conv - 1, gns),
                                  ("layers", "batch", None, "ssm_state"),
                                  dtype=dt)
    if fam == "hybrid":
        defs["k"] = defs["v"] = kv(len(cfg.hybrid_sites))
    return defs


def abstract_cache(cfg, max_len, batch):
    return abstract(cache_spec_defs(cfg, max_len, batch))


@lru_cache(maxsize=None)
def cache_bytes(cfg, max_len, batch) -> dict:
    """Bytes of a batch's decode cache: attention K/V (or MLA latents) and
    recurrent state (SSM state and convolution taps)."""
    sizes = {n: math.prod(sd.shape) * sd.dtype.itemsize
             for n, sd in abstract_cache(cfg, max_len, batch).items()}
    state = sum(v for n, v in sizes.items() if n == "ssm"
                or n.startswith("conv_"))
    return {"kv_bytes": sum(sizes.values()) - state, "state_bytes": state}


def init_cache(cfg, max_len, batch):
    return jax.tree_util.tree_map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype),
        abstract_cache(cfg, max_len, batch))


def cache_specs(cfg, max_len, batch, mesh, rules=None):
    return specs(cache_spec_defs(cfg, max_len, batch), mesh, rules)


def kv_by_position(c, n_kv_heads):
    """One layer's K or V cache, (B, KH·hd, S), viewed as (B, S, KH, hd)
    (a transpose that XLA folds into the contraction reading it)."""
    B, E, S = c.shape
    return jnp.moveaxis(c.reshape(B, n_kv_heads, E // n_kv_heads, S), -1, 1)


def _decode_attn(cfg, pa, h, kc, vc, layer, pos, cos, sin, dtype,
                 scale=None):
    """h: (B,1,E) the normed input; kc/vc: the stacked (n,B,KH·hd,S)
    caches, of which index ``layer`` is this block's. Writes the token's K/V
    into it in place and attends over it. Returns (y (B,D), kc', vc')."""
    B = h.shape[0]
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    S = kc.shape[-1]
    win = cfg.swa_window
    q = jnp.einsum("bsd,de->bse", h, pa["wq"].astype(dtype)).reshape(B, 1, H, hd)
    k = jnp.einsum("bsd,de->bse", h, pa["wk"].astype(dtype)).reshape(B, 1, KH, hd)
    v = jnp.einsum("bsd,de->bse", h, pa["wv"].astype(dtype)).reshape(B, 1, KH, hd)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    idx = jnp.mod(pos, S) if win else pos

    def write(c, t):                   # t (B,1,KH,hd) -> c[layer, :, :, idx]
        col = t.reshape(1, B, KH * hd, 1).astype(c.dtype)
        return jax.lax.dynamic_update_slice(c, col, (layer, 0, 0, idx))

    def read(c):
        c = jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
        return kv_by_position(c, KH).astype(dtype)

    kc, vc = write(kc, k), write(vc, v)
    o = attn.decode_attention(q[:, 0], read(kc), read(vc), pos, window=win,
                              scale=scale)
    y = jnp.einsum("bhd,hdD->bD", o, pa["wo"].reshape(H, hd, cfg.d_model)
                   .astype(dtype))
    return y, kc, vc


def _decode_attn_block(cfg, p, x, kc, vc, layer, pos, cos, sin, dtype):
    """x: (B,1,D); the block's attention with its residual (see
    ``_decode_attn``). Returns (x', kc', vc')."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    y, kc, vc = _decode_attn(cfg, p["attn"], h, kc, vc, layer, pos, cos, sin,
                             dtype)
    return x + y[:, None], kc, vc


def _decode_ffn(cfg, p, x, dtype, *, moe_layer):
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if moe_layer:
        # route the whole token batch jointly (B plays the sequence role)
        f, _ = moe_mod.moe_ffn(cfg, p["moe"], h2[:, 0][None], dtype)
        f = f[0][:, None]
    else:
        f = mlp_apply(cfg, p["mlp"], h2, dtype)
    return x + f


def decode_step(cfg, params, cache, tokens, pos):
    """One decode step. tokens: (B,1) int32 (audio: (B,1,K)); pos: () int32.
    Returns (logits (B, V[, K]), new_cache)."""
    dtype = cfg.compute_dt()
    B = tokens.shape[0]
    x = embed_tokens(cfg, params, tokens, dtype)           # (B,1,D)

    cos = sin = None
    fam = cfg.family
    if fam == "audio":
        # absolute sinusoidal at position `pos`
        ang = pos.astype(jnp.float32)
        dim = jnp.arange(0, cfg.d_model, 2) / cfg.d_model
        base = ang / jnp.power(10_000.0, dim)
        pe = jnp.zeros((cfg.d_model,), jnp.float32)
        pe = pe.at[0::2].set(jnp.sin(base)).at[1::2].set(jnp.cos(base))
        x = x + pe.astype(dtype)[None, None]
    elif fam == "vlm":
        p3 = jnp.broadcast_to(pos[None, None], (1, B))[None].repeat(3, 0)
        p3 = p3.reshape(3, B, 1)
        cos, sin = mrope_cos_sin(p3, cfg.hd, cfg.rope_theta,
                                 cfg.mrope_sections)
    else:
        rope_dim = (cfg.mla.qk_rope_head_dim if cfg.mla is not None
                    else cfg.hd)
        if fam != "ssm":
            cos, sin = rope_cos_sin(pos[None], rope_dim, cfg.rope_theta)

    new_cache = dict(cache)
    if fam == "ssm":
        def body(carry, xs):
            (xc, ssm), (p_l, layer, cx, cb, cc) = carry, xs
            y, ssm, conv = mam.mamba_decode(cfg, p_l, xc, ssm, layer,
                                            (cx, cb, cc), dtype,
                                            use_pallas=cfg.use_pallas)
            return (xc + y, ssm), conv
        (x, st), (cx, cb, cc) = jax.lax.scan(
            body, (x, cache["ssm"]),
            (params["layers"], jnp.arange(cfg.n_layers), cache["conv_x"],
             cache["conv_b"], cache["conv_c"]))
        new_cache.update(ssm=st, conv_x=cx, conv_b=cb, conv_c=cc)
    elif fam == "hybrid":
        states = {n: cache[n] for n in ("ssm", "conv_x", "conv_b", "conv_c")}
        x, states, (kc, vc) = _hybrid_stack(
            cfg, params, x, states, (cache["k"], cache["v"]), cos, sin, pos,
            dtype, use_pallas=cfg.use_pallas)
        new_cache.update(states, k=kc, v=vc)
    elif fam == "moe" and cfg.mla is not None:
        fk = cfg.moe.first_k_dense

        def mla_body(moe_layer):
            def body(carry, xs):
                p_l, ckv, kr = xs
                h = rms_norm(carry, p_l["ln1"], cfg.norm_eps)
                y, ckv, kr = attn.mla_decode(p_l["attn"], h, ckv, kr, pos,
                                             cos, sin, cfg, dtype)
                xc = carry + y
                xc = _decode_ffn(cfg, p_l, xc, dtype, moe_layer=moe_layer)
                return xc, (ckv, kr)
            return body
        ckv_d, ckv_m = cache["ckv"][:fk], cache["ckv"][fk:]
        kr_d, kr_m = cache["kr"][:fk], cache["kr"][fk:]
        if fk:
            x, (ckv_d, kr_d) = jax.lax.scan(
                mla_body(False), x, (params["dense_layers"], ckv_d, kr_d))
        x, (ckv_m, kr_m) = jax.lax.scan(
            mla_body(True), x, (params["layers"], ckv_m, kr_m))
        new_cache.update(ckv=jnp.concatenate([ckv_d, ckv_m]),
                         kr=jnp.concatenate([kr_d, kr_m]))
    else:  # dense / vlm / audio / moe-GQA (mixtral)
        moe_layer = fam == "moe"

        def body(carry, xs):
            (xc, kc, vc), (p_l, layer) = carry, xs
            xc, kc, vc = _decode_attn_block(cfg, p_l, xc, kc, vc, layer, pos,
                                            cos, sin, dtype)
            xc = _decode_ffn(cfg, p_l, xc, dtype, moe_layer=moe_layer)
            return (xc, kc, vc), None
        (x, kc, vc), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(cache["k"].shape[0])))
        new_cache.update(k=kc, v=vc)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(cfg, params, x, dtype)                # (B,1,V[,K])
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# Input declaration (shapes for dry-run / data pipeline)
# ---------------------------------------------------------------------------

def input_defs(cfg, shape) -> dict:
    """Returns name -> (shape, dtype, logical axes) for the model inputs of
    an (arch × shape) cell. Frontends are stubs per the brief: VLM inputs
    are precomputed patch embeddings, audio inputs are EnCodec token ids."""
    B, S = shape.global_batch, shape.seq_len
    kind = shape.kind
    out = {}
    if kind in ("train", "prefill"):
        if cfg.family == "vlm":
            out["embeds"] = ((B, S, cfg.d_model), "bfloat16",
                             ("batch", None, None))
            out["pos3"] = ((3, B, S), "int32", (None, "batch", None))
        elif cfg.family == "audio":
            out["tokens"] = ((B, S, cfg.n_codebooks), "int32",
                             ("batch", None, None))
        else:
            out["tokens"] = ((B, S), "int32", ("batch", None))
        if kind == "train":
            if cfg.family == "audio":
                out["labels"] = ((B, S, cfg.n_codebooks), "int32",
                                 ("batch", None, None))
            else:
                out["labels"] = ((B, S), "int32", ("batch", None))
    else:  # decode: one new token against a seq_len cache
        if cfg.family == "audio":
            out["tokens"] = ((B, 1, cfg.n_codebooks), "int32",
                             ("batch", None, None))
        else:
            out["tokens"] = ((B, 1), "int32", ("batch", None))
    return out
