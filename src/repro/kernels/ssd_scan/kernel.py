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

``ssd_state_call`` is decode's one-token step of the same recurrence,
h = h·exp(dt·A) + (x·dt)⊗B and y = h·C, on one layer of the stacked
decode state, updated where it lies (see its docstring).
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


# bytes of one (batch block, head block) tile of the state: four such tiles
# (read and written, each double-buffered) and the loop's temporaries stay
# well inside the VMEM limit below
TILE_BYTES = 4 * 2**20
VMEM_LIMIT = 48 * 2**20


def _ssd_state_kernel(layer_ref, st_ref, xdt_ref, dA_ref, B_ref, C_ref,
                      st_out_ref, y_ref, *, ns: int):
    del layer_ref                             # read by the index maps
    k, bB, _, R = xdt_ref.shape
    # lane l of a stored row holds position p = k·row + l // ns
    part = jax.lax.broadcasted_iota(jnp.int32, (R, st_ref.shape[-1]), 1) // ns

    def one(b, carry):                        # one sequence of the block
        x = xdt_ref[0, b][..., None]          # (bH, R, 1)
        for j in range(1, k):
            x = jnp.where(part == j, xdt_ref[j, b][..., None], x)
        h = st_ref[0, b] * dA_ref[b][..., None] + x * B_ref[0, b]
        st_out_ref[0, b] = h
        hc = h * C_ref[0, b]
        for j in range(k):
            y_ref[j, b] = jnp.sum(
                hc if k == 1 else jnp.where(part == j, hc, 0.0), axis=-1)
        return carry

    jax.lax.fori_loop(0, bB, one, 0)


def ssd_state_call(state, layer, xdt, dA, B_, C_, *,
                   interpret: bool = False):
    """One token of the SSD recurrence on layer ``layer`` of the stacked
    decode state, in place.

    state: (L, B, nh, R, W) float32, each head's (hp, ns) state stored as
    (hp/k, k·ns) (``models.mamba.state_layout``); layer: int32 scalar;
    xdt: (B, nh, hp) x·dt; dA: (B, nh) exp(dt·A); B_/C_: (B, G, ns), head
    h reading group h // (nh/G).

    The state is aliased to output 0: each grid step reads one (batch
    block, head block) tile of the layer once, writes h·dA + xdt⊗B back
    where it was, and emits y = h·C. A head block lies inside one group
    and holds a multiple of 8 heads, or every head: where nh/G has no such
    divisor (G > 1 and nh/G not a multiple of 8) the call is refused.
    Returns (y (B, nh, hp) float32, state)."""
    L, B, nh, R, W = state.shape
    G, ns = B_.shape[1], B_.shape[2]
    hp = xdt.shape[-1]
    k, hpg = W // ns, nh // G
    assert k * ns == W and k * R == hp and hpg * G == nh
    head = R * W * 4                                     # bytes a head
    # a head block lies in one group and, unless it holds every head,
    # fills whole sublane tiles of xdt, dA and y
    small = [d for d in range(1, hpg + 1)
             if hpg % d == 0 and (d % 8 == 0 or d == nh)
             and d * head <= TILE_BYTES]
    assert small, (f"no head block of {nh} heads in {G} groups is a "
                   f"multiple of 8 with a tile of at most {TILE_BYTES} "
                   f"bytes ({head} bytes a head)")
    bH = max(small)
    bB = max(d for d in range(1, B + 1)
             if B % d == 0 and d * bH * head <= TILE_BYTES)

    xk = jnp.moveaxis(xdt.reshape(B, nh, R, k), -1, 0)   # (k, B, nh, R)
    bc = lambda t: jnp.swapaxes(jnp.tile(t.astype(jnp.float32),  # noqa
                                         (1, 1, k)), 0, 1)[:, :, None]
    st_spec = pl.BlockSpec((1, bB, bH, R, W),
                           lambda b, h, l: (l[0], b, h, 0, 0))
    bc_spec = pl.BlockSpec((1, bB, 1, W),
                           lambda b, h, l: (h * bH // hpg, b, 0, 0))
    x_spec = pl.BlockSpec((k, bB, bH, R), lambda b, h, l: (0, b, h, 0))
    state, y = pl.pallas_call(
        functools.partial(_ssd_state_kernel, ns=ns),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // bB, nh // bH),
            in_specs=[st_spec, x_spec,
                      pl.BlockSpec((bB, bH, 1), lambda b, h, l: (b, h, 0)),
                      bc_spec, bc_spec],
            out_specs=[st_spec, x_spec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((k, B, nh, R), jnp.float32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="ssd_state",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state,
      xk.astype(jnp.float32), dA.astype(jnp.float32)[..., None],
      bc(B_), bc(C_))
    return jnp.moveaxis(y, 0, -1).reshape(B, nh, hp), state
