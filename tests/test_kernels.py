"""Pallas kernel sweeps (interpret mode) vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention as pk_flash
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd_scan.ops import ssd as pk_ssd
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.paged_attn.ops import paged_attention as pk_paged
from repro.kernels.paged_attn.ref import paged_attention_ref

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KH,hd,win,bq", [
    (2, 256, 4, 2, 64, 0, 64),
    (1, 512, 4, 1, 128, 0, 128),
    (2, 128, 8, 8, 32, 64, 64),
    (1, 256, 2, 2, 64, 128, 128),
])
def test_flash_kernel_sweep(dtype, B, S, H, KH, hd, win, bq):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KH, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KH, hd), dtype)
    out = pk_flash(q, k, v, window=win, block_q=bq, block_k=bq,
                   interpret=True)
    ref = flash_attention_ref(q, k, v, window=win)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])


@pytest.mark.parametrize("B,S,nh,hp,ns,cl", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 8, 16, 32, 64),
    (2, 64, 2, 64, 64, 64),
])
def test_ssd_kernel_sweep(B, S, nh, hp, ns, cl):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (B, S, nh, hp), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A_log = jax.random.normal(ks[2], (nh,)) * 0.3
    B_ = jax.random.normal(ks[3], (B, S, ns)) * 0.5
    C_ = jax.random.normal(ks[4], (B, S, ns)) * 0.5
    D_ = jnp.ones((nh,))
    y, st = pk_ssd(x, dt, A_log, B_, C_, D_, chunk=cl, interpret=True)
    yr, sr = ssd_ref(x, dt, A_log, B_, C_, D_, cl)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=2e-5,
                               rtol=2e-4)


def test_ssd_kernel_with_initial_state():
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    B, S, nh, hp, ns = 1, 64, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, nh, hp)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A_log = jax.random.normal(ks[2], (nh,)) * 0.3
    B_ = jax.random.normal(ks[3], (B, S, ns)) * 0.5
    C_ = jax.random.normal(ks[4], (B, S, ns)) * 0.5
    D_ = jnp.zeros((nh,))
    st0 = jax.random.normal(ks[5], (B, nh, hp, ns)) * 0.2
    y, st = pk_ssd(x, dt, A_log, B_, C_, D_, chunk=32, state=st0,
                   interpret=True)
    yr, sr = ssd_ref(x, dt, A_log, B_, C_, D_, 32, state=st0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5,
                               rtol=2e-4)


@pytest.mark.parametrize("with_state", [False, True])
@pytest.mark.parametrize("B,S,nh,hp,ns,cl,G", [
    (2, 128, 4, 32, 16, 32, 2),
    (1, 96, 8, 16, 32, 64, 4),        # S not a multiple of the chunk
])
def test_grouped_ssd_kernel_sweep(B, S, nh, hp, ns, cl, G, with_state):
    """B and C in G groups: the kernel's per-group C·Bᵀ and the grouped
    inter-chunk scan against the chunked jnp SSD, itself the single-group
    SSD of each group's heads."""
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (B, S, nh, hp), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A_log = jax.random.normal(ks[2], (nh,)) * 0.3
    B_ = jax.random.normal(ks[3], (B, S, G, ns)) * 0.5
    C_ = jax.random.normal(ks[4], (B, S, G, ns)) * 0.5
    D_ = jnp.linspace(0.5, 1.5, nh)
    st0 = (jax.random.normal(ks[5], (B, nh, hp, ns)) * 0.2 if with_state
           else None)
    y, st = pk_ssd(x, dt, A_log, B_, C_, D_, chunk=cl, state=st0,
                   interpret=True)
    yr, sr = ssd_ref(x, dt, A_log, B_, C_, D_, cl, state=st0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=2e-5,
                               rtol=2e-4)
    # head h reads group h // (nh/G): group g alone gives its heads' part
    g, per = 1, nh // G
    heads = slice(g * per, (g + 1) * per)
    y1, _ = ssd_ref(x[:, :, heads], dt[..., heads], A_log[heads],
                    B_[:, :, g], C_[:, :, g], D_[heads], cl,
                    state=None if st0 is None else st0[:, heads])
    np.testing.assert_allclose(np.asarray(yr[:, :, heads]), np.asarray(y1),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("B,H,KH,hd,page,nblk", [
    (2, 4, 2, 64, 32, 4),
    (3, 8, 2, 64, 16, 8),
    (1, 4, 4, 128, 64, 2),
])
def test_paged_attention_sweep(B, H, KH, hd, page, nblk):
    npool = nblk * B + 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (npool, page, KH, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (npool, page, KH, hd), jnp.float32)
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        rng.permutation(npool)[:B * nblk].reshape(B, nblk))
    lens = jnp.asarray(rng.integers(1, nblk * page + 1, B), jnp.int32)
    out = pk_paged(q, kp, vp, table, lens, interpret=True)
    ref = paged_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_model_mamba_uses_kernel_equivalently(monkeypatch):
    """cfg.use_pallas=True must give the same forward as the jnp path."""
    import functools
    from repro.configs import get_smoke_config
    from repro.kernels.ssd_scan import ops as ssd_ops
    from repro.models import lm
    # the model calls the compiled kernel; on the CPU run it interpreted
    monkeypatch.setattr(ssd_ops, "ssd",
                        functools.partial(ssd_ops.ssd, interpret=True))
    cfg = get_smoke_config("mamba2-130m")
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    toks = jax.random.randint(key, (2, 64), 0, cfg.vocab_size)
    l0, _, _ = lm.forward(cfg, params, {"tokens": toks})
    l1, _, _ = lm.forward(cfg.replace(use_pallas=True), params,
                          {"tokens": toks})
    np.testing.assert_allclose(np.asarray(l0, np.float32),
                               np.asarray(l1, np.float32),
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("B,nh,hp,ns,G", [
    (2, 8, 32, 128, 1),
    (3, 16, 64, 64, 2),       # two positions a stored row; a block a group
    (2, 32, 32, 16, 4),       # eight positions a row, four groups
])
def test_ssd_state_kernel_matches_the_one_token_recurrence(B, nh, hp, ns,
                                                           G):
    """The decode kernel updates layer ``layer`` of the stacked state in
    place: its y (with the D skip) and new state are ``ssd_decode_step``'s
    and the one-token chunked SSD's, and every other layer is
    bit-identical. Head blocks stop at group boundaries: the head count
    spans G groups, each read by nh/G heads."""
    from repro.kernels.ssd_scan.ops import ssd_state
    from repro.models.mamba import ssd_chunked, ssd_decode_step, state_layout
    L, layer = 3, 1
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    x = jax.random.normal(ks[0], (B, nh, hp)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, nh)))
    A_log = jax.random.normal(ks[2], (nh,)) * 0.3
    B_ = jax.random.normal(ks[3], (B, G, ns)) * 0.5
    C_ = jax.random.normal(ks[4], (B, G, ns)) * 0.5
    D_ = jnp.linspace(0.5, 1.5, nh)
    st = jax.random.normal(ks[5], (L, B, nh, hp, ns)) * 0.2
    stored = st.reshape((L, B, nh) + state_layout(hp, ns))
    dA = jnp.exp(dt * -jnp.exp(A_log))
    y, new = ssd_state(stored, jnp.int32(layer), x * dt[..., None], dA, B_,
                       C_, interpret=True)
    y = y + x * D_[None, :, None]
    new = np.asarray(new).reshape(st.shape)
    yr, sr = ssd_decode_step(x, dt, A_log, B_, C_, D_, st[layer])
    one = (lambda t: t[:, None]) if G > 1 else (lambda t: t[:, None, 0])
    yc, sc = ssd_chunked(x[:, None], dt[:, None], A_log, one(B_), one(C_),
                         D_, 1, state=st[layer], return_state=True)
    for want_y, want_st in ((yr, sr), (yc[:, 0], sc)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(new[layer], np.asarray(want_st),
                                   atol=2e-6, rtol=2e-6)
    others = np.arange(L) != layer
    np.testing.assert_array_equal(new[others], np.asarray(st)[others])


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("arch", ["mamba2-130m", "zamba2-7b"])
def test_decode_with_the_state_kernel_matches_forward(arch, use_pallas,
                                                      monkeypatch):
    """Prefill, then 8 decode steps through the cache reproduce the full
    forward's logits, whether the SSM state is updated by the Pallas
    ``ssd_state`` kernel (interpreted on the CPU) or by
    ``ssd_decode_step``."""
    import dataclasses
    import functools
    from repro.configs import get_smoke_config
    from repro.kernels.ssd_scan import ops as ssd_ops
    from repro.launch.steps import make_prefill_step
    from repro.models import lm
    # prefill's and decode's kernels compile unless asked for the
    # interpreter
    for name in ("ssd", "ssd_state"):
        monkeypatch.setattr(ssd_ops, name, functools.partial(
            getattr(ssd_ops, name), interpret=True))
    cfg = get_smoke_config(arch).replace(compute_dtype="float32",
                                         use_pallas=use_pallas)
    if cfg.ssm.ngroups > 1:
        # 16 heads in 2 groups: the decode kernel's head blocks hold 8
        cfg = cfg.replace(ssm=dataclasses.replace(cfg.ssm, headdim=8))
    key = jax.random.PRNGKey(8)
    params = lm.init_params(cfg, key)
    S0, S1, B = 24, 32, 2
    toks = jax.random.randint(key, (B, S1), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, _, _ = lm.forward(cfg, params, {"tokens": toks})
        _, cache = make_prefill_step(cfg)(params, {"tokens": toks[:, :S0]})
        full = lm.init_cache(cfg, S1, B)
        cache = {n: full[n].at[tuple(slice(0, s) for s in c.shape)].set(c)
                 for n, c in cache.items()}
        for pos in range(S0, S1):
            lg, cache = lm.decode_step(cfg, params, cache,
                                       toks[:, pos:pos + 1], jnp.int32(pos))
            np.testing.assert_allclose(np.asarray(lg),
                                       np.asarray(want[:, pos]),
                                       atol=5e-2, rtol=1e-2)
