"""Mamba2 SSD intra-chunk kernel — Pallas TPU.

The SSD duality splits the computation into a quadratic intra-chunk part
(attention-like, MXU-friendly — this kernel) and a linear inter-chunk
recurrence (tiny, done in jnp by the caller; see ops.py).

Grid: (B·n_chunks, nh). Per step the kernel computes one head of one
chunk, entirely in VMEM (rows are (1, cl) vectors over the chunk):
    cs      = cumsum(dt ⊙ A)                     (1, cl)
    y_diag  = (C·Bᵀ ⊙ L ⊙ dt) · x                (cl, hp)
    states  = (xᵀ ⊙ dt ⊙ decay_out) · B          (hp, ns)
    exp_cs                                       (1, cl)
where L = exp(cs_i − cs_j) on the lower triangle.

Layout is chosen for the TPU lowering: the cumsum is a triangular matmul,
heads are a grid axis (never a lane slice), x arrives transposed to
(hp, cl) so every matmul is a plain or NT contraction, and dt/cs stay
row vectors. C·Bᵀ is shared by the heads of a group (all heads of a
chunk when B and C have one group): it is computed at the group's first
head into VMEM scratch, which is why the head axis is sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _ssd_chunk_kernel(xT_ref, dt_ref, A_ref, B_ref, C_ref,
                      y_ref, st_ref, ecs_ref, sc_ref, *, cl: int,
                      group_heads: int):
    ii = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    tri = ii >= jj
    Bm = B_ref[0].astype(jnp.float32)             # (cl, ns)
    h = pl.program_id(1)                          # group_heads 0: one group

    @pl.when(h == 0 if group_heads == 0 else h % group_heads == 0)
    def _scores():                                # C·Bᵀ, shared by heads
        Cm = C_ref[0].astype(jnp.float32)
        sc_ref[...] = jax.lax.dot_general(
            Cm, Bm, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    xT = xT_ref[0, 0].astype(jnp.float32)         # (hp, cl)
    dt = dt_ref[0, 0].astype(jnp.float32)         # (1, cl)
    A = -jnp.exp(A_ref[0].astype(jnp.float32))    # (1, 1)
    dA = dt * A                                   # (1, cl)

    # cumsum along the chunk as triangular matmuls, once as a row
    # (cs_row[i] = Σ_{j≤i} dA[j]) and once as a column
    cs_row = jax.lax.dot_general(dA, jnp.where(ii <= jj, 1.0, 0.0),
                                 (((1,), (0,)), ((), ())),
                                 precision=_HI,
                                 preferred_element_type=jnp.float32)
    cs_col = jax.lax.dot_general(jnp.where(tri, 1.0, 0.0), dA,
                                 (((1,), (1,)), ((), ())), precision=_HI,
                                 preferred_element_type=jnp.float32)

    L = jnp.exp(jnp.where(tri, cs_col - cs_row, -1e9))        # (cl, cl)
    att = sc_ref[...] * L * dt
    y_ref[0, 0] = jax.lax.dot_general(
        att, xT, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    # chunk state: st[p, n] = Σ_j x[j,p] · dt[j] · exp(total − cs[j]) · B[j,n]
    total = jnp.sum(dA, axis=1, keepdims=True)                 # (1, 1)
    w = dt * jnp.exp(total - cs_row)                           # (1, cl)
    st_ref[0, 0] = jax.lax.dot_general(
        xT * w, Bm, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(st_ref.dtype)
    ecs_ref[0, 0] = jnp.exp(cs_row).astype(ecs_ref.dtype)


def ssd_chunk_call(x, dt, A_log, B_, C_, *, chunk: int,
                   interpret: bool = False):
    """x: (B, S, nh, hp); dt: (B, S, nh); A_log: (nh,); B_/C_: (B, S, ns),
    or (B, S, G, ns) where head h reads group h // (nh/G).

    Returns per-chunk pieces:
      y_diag  (B, nc, cl, nh, hp)
      states  (B, nc, nh, hp, ns)
      exp_cs  (B, nc, cl, nh)
      exp_tot (B, nc, nh)
    """
    B, S, nh, hp = x.shape
    ns = B_.shape[-1]
    cl = min(chunk, S)
    assert S % cl == 0
    nc = S // cl
    G = B * nc

    xT = x.reshape(G, cl, nh, hp).transpose(0, 2, 3, 1)       # (G,nh,hp,cl)
    dtr = dt.reshape(G, cl, nh).transpose(0, 2, 1)[:, :, None]  # (G,nh,1,cl)
    if B_.ndim == 3:
        group_heads = 0
        Bf = B_.reshape(G, cl, ns)
        Cf = C_.reshape(G, cl, ns)
        bc_index = lambda g, h: (g, 0, 0)                      # noqa: E731
    else:                      # (G·ngroups, cl, ns): chunk-major, then group
        ng = B_.shape[2]
        group_heads = nh // ng
        Bf, Cf = (t.reshape(G, cl, ng, ns).transpose(0, 2, 1, 3)
                  .reshape(G * ng, cl, ns) for t in (B_, C_))
        bc_index = lambda g, h: (g * ng + h // group_heads, 0, 0)  # noqa
    A3 = A_log.reshape(nh, 1, 1)

    y, st, ecs = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, cl=cl, group_heads=group_heads),
        grid=(G, nh),
        in_specs=[
            pl.BlockSpec((1, 1, hp, cl), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, cl), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, h: (h, 0, 0)),
            pl.BlockSpec((1, cl, ns), bc_index),
            pl.BlockSpec((1, cl, ns), bc_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cl, hp), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, hp, ns), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, cl), lambda g, h: (g, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, nh, cl, hp), jnp.float32),
            jax.ShapeDtypeStruct((G, nh, hp, ns), jnp.float32),
            jax.ShapeDtypeStruct((G, nh, 1, cl), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((cl, cl), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xT, dtr, A3, Bf, Cf)

    ecs = ecs[:, :, 0].reshape(B, nc, nh, cl).transpose(0, 1, 3, 2)
    return (y.reshape(B, nc, nh, cl, hp).transpose(0, 1, 3, 2, 4),
            st.reshape(B, nc, nh, hp, ns),
            ecs,
            ecs[:, :, -1])                 # exp(total) = exp(cs[last])
