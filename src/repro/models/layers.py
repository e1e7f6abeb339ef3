"""Shared building blocks: param declaration, norms, MLPs, rotary embeddings."""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Declarative parameters: one definition drives init, abstract shapes & specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]     # logical axes, len == len(shape)
    init: str = "normal"                   # normal | zeros | ones | small
    scale: float = 1.0                     # fan-in style scale for "normal"
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def materialize(defs: dict, key: jax.Array) -> dict:
    """Real initialization (smoke tests / examples)."""
    flat = jax.tree_util.tree_leaves_with_path(defs,
                                               is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, max(1, len(flat)))

    def init_one(pd: ParamDef, k):
        dt = jnp.dtype(pd.dtype)
        if pd.init == "zeros":
            return jnp.zeros(pd.shape, dt)
        if pd.init == "ones":
            return jnp.ones(pd.shape, dt)
        fan_in = pd.shape[-2] if len(pd.shape) >= 2 else pd.shape[-1]
        std = pd.scale / math.sqrt(max(1, fan_in))
        return (jax.random.normal(k, pd.shape, jnp.float32) * std).astype(dt)

    out = {}
    leaves = {}
    for (path, pd), k in zip(flat, keys):
        leaves[jax.tree_util.keystr(path)] = init_one(pd, k)
    # rebuild tree
    treedef = jax.tree_util.tree_structure(defs,
                                           is_leaf=lambda x: isinstance(x, ParamDef))
    return jax.tree_util.tree_unflatten(treedef, list(leaves.values()))


def abstract(defs: dict) -> dict:
    """ShapeDtypeStruct tree (dry-run: no allocation)."""
    return jax.tree_util.tree_map(
        lambda pd: jax.ShapeDtypeStruct(pd.shape, jnp.dtype(pd.dtype)),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def specs(defs: dict, mesh, rules=None) -> dict:
    from repro.models.partitioning import spec_for
    return jax.tree_util.tree_map(
        lambda pd: spec_for(pd.logical, mesh, rules),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Norms & MLPs (functional)
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def swiglu(x, w1, w3, w2, dtype):
    h = jnp.einsum("bsd,df->bsf", x, w1.astype(dtype))
    g = jnp.einsum("bsd,df->bsf", x, w3.astype(dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(h) * g, w2.astype(dtype))


def gelu_mlp(x, w1, w2, dtype):
    h = jnp.einsum("bsd,df->bsf", x, w1.astype(dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(h), w2.astype(dtype))


def mlp_defs(cfg, d_ff: int, prefix_logical_in="embed", ll=()) -> dict:
    """Param defs for one MLP; ``ll`` prepends stacked-layer axes."""
    d = cfg.d_model
    Lax = tuple("layers" for _ in ll)
    if cfg.mlp_kind in ("swiglu", "geglu"):     # gated: w1 gate, w3 up
        return {
            "w1": ParamDef(ll + (d, d_ff), Lax + ("embed", "mlp")),
            "w3": ParamDef(ll + (d, d_ff), Lax + ("embed", "mlp")),
            "w2": ParamDef(ll + (d_ff, d), Lax + ("mlp", "embed")),
        }
    return {
        "w1": ParamDef(ll + (d, d_ff), Lax + ("embed", "mlp")),
        "w2": ParamDef(ll + (d_ff, d), Lax + ("mlp", "embed")),
    }


def mlp_apply(cfg, p, x, dtype):
    if cfg.mlp_kind == "swiglu":
        return swiglu(x, p["w1"], p["w3"], p["w2"], dtype)
    return gelu_mlp(x, p["w1"], p["w2"], dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE) and sinusoidal positions
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim))


def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions: (...,) int32 → cos/sin of shape positions.shape + (hd/2,)."""
    inv = jnp.asarray(rope_freqs(head_dim, theta), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(pos3, head_dim: int, theta: float, sections):
    """Qwen2-VL M-RoPE. pos3: (3, B, S) temporal/height/width position ids.

    Frequency pairs are split into ``sections`` (t, h, w); each section
    rotates by its own position stream.
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    cos_t, sin_t = rope_cos_sin(pos3, head_dim, theta)   # (3, B, S, hd/2)
    cos_p, sin_p, start = [], [], 0
    for i, sec in enumerate(sections):
        cos_p.append(cos_t[i, :, :, start:start + sec])
        sin_p.append(sin_t[i, :, :, start:start + sec])
        start += sec
    return jnp.concatenate(cos_p, -1), jnp.concatenate(sin_p, -1)  # (B,S,hd/2)


def apply_rope(x, cos, sin):
    """x: (..., S, H, hd); cos/sin broadcastable to (..., S, 1, hd/2)."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    if cos.ndim == 2:        # (S, hd/2) — text rope
        cos = cos[:, None, :]
        sin = sin[:, None, :]
    elif cos.ndim == 3:      # (B, S, hd/2) — M-RoPE
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n_pos: int, d_model: int) -> np.ndarray:
    """MusicGen-style absolute sinusoidal embedding table."""
    pos = np.arange(n_pos)[:, None]
    dim = np.arange(0, d_model, 2)[None, :]
    ang = pos / np.power(10_000, dim / d_model)
    out = np.zeros((n_pos, d_model), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return out


def padded_vocab(v: int, multiple: int = 128) -> int:
    return ((v + multiple - 1) // multiple) * multiple
