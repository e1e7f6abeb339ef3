"""jit'd SSD wrappers: ``ssd`` (prefill: Pallas intra-chunk kernel + jnp
inter-chunk scan; it compiles unless the caller passes ``interpret=True``)
and ``ssd_state`` (decode: one token on the stacked state, in place).
Each wrapper's name is its Pallas call's name in the compiled program and
the trace."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_chunk_call, ssd_state_call


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A_log, B_, C_, D_, *, chunk: int = 256, state=None,
        interpret: bool = False):
    """Full SSD = Pallas intra-chunk pieces + linear inter-chunk scan.
    B_/C_: (B,S,ns), or (B,S,G,ns) where head h reads group h // (nh/G).
    Returns (y (B,S,nh,hp), final_state (B,nh,hp,ns))."""
    B, S, nh, hp = x.shape
    ns = B_.shape[-1]
    cl = min(chunk, S)
    S_orig = S
    if S % cl:                 # pad with dt=0 tokens (state-neutral)
        pad = cl - S % cl
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bc_pad = ((0, 0), (0, pad)) + ((0, 0),) * (B_.ndim - 2)
        B_ = jnp.pad(B_, bc_pad)
        C_ = jnp.pad(C_, bc_pad)
        S = S + pad
    nc = S // cl

    y_diag, states, exp_cs, exp_tot = ssd_chunk_call(
        x, dt, A_log, B_, C_, chunk=chunk, interpret=interpret)

    if state is None:
        state = jnp.zeros((B, nh, hp, ns), jnp.float32)

    C_c = jnp.moveaxis(C_.reshape((B, nc, cl) + C_.shape[2:]), 1,
                       0).astype(jnp.float32)
    sc = jnp.moveaxis(states, 1, 0)
    ec = jnp.moveaxis(exp_cs, 1, 0)
    et = jnp.moveaxis(exp_tot, 1, 0)

    def off_diagonal(C_k, st, ecs_k):
        if C_k.ndim == 3:
            return jnp.einsum("bin,bhpn,bih->bihp", C_k, st, ecs_k)
        g = C_k.shape[2]                      # heads split into their groups
        st = st.reshape((B, g, nh // g) + st.shape[2:])
        ecs_k = ecs_k.reshape(B, cl, g, nh // g)
        y = jnp.einsum("bign,bgkpn,bigk->bigkp", C_k, st, ecs_k)
        return y.reshape(B, cl, nh, hp)

    def step(carry, inp):
        st = carry
        C_k, st_k, ecs_k, etot_k = inp
        y_off = off_diagonal(C_k, st, ecs_k)
        st = st * etot_k[:, :, None, None] + st_k
        return st, y_off

    state, y_off = jax.lax.scan(step, state, (C_c, sc, ec, et))
    y = jnp.moveaxis(y_diag, 1, 0) + y_off               # (nc,B,cl,nh,hp)
    y = jnp.moveaxis(y, 0, 1).reshape(B, S, nh, hp)
    y = y + x.astype(jnp.float32) * D_.astype(jnp.float32)[None, None, :,
                                                           None]
    return y.astype(x.dtype)[:, :S_orig], state


@partial(jax.jit, static_argnames=("interpret",))
def ssd_state(state, layer, xdt, dA, B_, C_, *, interpret: bool = False):
    """Decode's SSD step on layer ``layer`` of the stacked state (L, B, nh,
    R, W), updated in place (``kernel.ssd_state_call``); it compiles unless
    the caller passes ``interpret=True``.
    Returns (y (B, nh, hp) float32 without the D skip, state)."""
    return ssd_state_call(state, layer, xdt, dA, B_, C_,
                          interpret=interpret)
