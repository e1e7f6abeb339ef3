"""Per-arch smoke tests (reduced configs) + attention/MoE correctness +
prefill↔decode consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, get_smoke_config, list_archs
from repro.models import lm
from repro.models.attention import flash_attention, reference_attention

B, S = 2, 64


def make_batch(cfg, key, with_labels=False):
    batch = {}
    if cfg.family == "vlm":
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.bfloat16)
        p1 = jnp.arange(S)[None].repeat(B, 0)
        batch["pos3"] = jnp.stack([p1, p1, p1])
    elif cfg.family == "audio":
        batch["tokens"] = jax.random.randint(key, (B, S, cfg.n_codebooks),
                                             0, cfg.vocab_size)
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    if with_labels:
        shape = (B, S, cfg.n_codebooks) if cfg.family == "audio" else (B, S)
        batch["labels"] = jax.random.randint(key, shape, 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_forward_and_decode(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    logits, aux, _ = lm.forward(cfg, params, make_batch(cfg, key))
    V = lm.padded_vocab(cfg.vocab_size)
    expect = (B, S, cfg.n_codebooks, V) if cfg.n_codebooks else (B, S, V)
    assert logits.shape == expect
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())

    cache = lm.init_cache(cfg, max_len=S, batch=B)
    tok = (jnp.zeros((B, 1, cfg.n_codebooks), jnp.int32)
           if cfg.family == "audio" else jnp.zeros((B, 1), jnp.int32))
    lg, cache = lm.decode_step(cfg, params, cache, tok, jnp.int32(0))
    assert not bool(jnp.isnan(lg.astype(jnp.float32)).any())


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_train_step(arch):
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(1)
    params = lm.init_params(cfg, key)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, peak_lr=1e-2, warmup=1))
    batch = make_batch(cfg, key, with_labels=True)
    p2, o2, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    assert float(m["grad_norm"]) > 0
    # params actually changed
    l0 = jax.tree_util.tree_leaves(params)[1]
    l1 = jax.tree_util.tree_leaves(p2)[1]
    assert not np.allclose(np.asarray(l0), np.asarray(l1))


@pytest.mark.parametrize("schedule", ["rect", "triangular"])
@pytest.mark.parametrize("window", [0, 48])
def test_flash_matches_reference(schedule, window):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          q_chunk=32, schedule=schedule)
    ref = reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients_match_reference():
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 1, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 1, 16), jnp.float32)
    f = lambda *a: flash_attention(*a, q_chunk=16).sum()
    r = lambda *a: reference_attention(*a).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "mixtral-8x22b",
                                  "deepseek-v2-lite-16b", "mamba2-130m",
                                  "zamba2-2.7b", "musicgen-large"])
def test_prefill_decode_consistency(arch):
    """decode_step continuing a prefill cache must produce the same logits
    as a fresh full forward — the strongest cache-correctness check.

    MoE capacity is raised so no tokens drop: capacity-dropping is
    group-dependent by design (GShard), so drop-free is the only regime
    where bitwise forward/decode agreement is defined."""
    import dataclasses
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
    key = jax.random.PRNGKey(7)
    params = lm.init_params(cfg, key)
    S0, S1 = 32, 36
    if cfg.family == "audio":
        toks = jax.random.randint(key, (B, S1, cfg.n_codebooks), 0,
                                  cfg.vocab_size)
    else:
        toks = jax.random.randint(key, (B, S1), 0, cfg.vocab_size)

    # ground truth: full forward logits at each position
    full_logits, _, _ = lm.forward(cfg, params, {"tokens": toks})

    # prefill on the first S0 tokens
    from repro.launch.steps import make_prefill_step
    prefill = make_prefill_step(cfg)
    lg, cache = prefill(params, {"tokens": toks[:, :S0]})
    # tolerances: bf16 compute; SSM archs accumulate state through two
    # different summation orders (chunked prefill vs step decode), which
    # occasionally pushes a single logit to ~0.08 abs (zamba2 flake)
    np.testing.assert_allclose(
        np.asarray(lg, np.float32),
        np.asarray(full_logits[:, S0 - 1], np.float32), atol=1e-1,
        rtol=3e-2)

    # grow cache to S1 and decode the remaining tokens
    fullc = lm.init_cache(cfg, S1, B)
    for k in cache:
        if cache[k].shape == fullc[k].shape:
            fullc[k] = cache[k]
        else:
            sl = tuple(slice(0, s) for s in cache[k].shape)
            fullc[k] = fullc[k].at[sl].set(cache[k])
    cache = fullc
    for pos in range(S0, S1):
        tok = toks[:, pos:pos + 1]
        lg, cache = lm.decode_step(cfg, params, cache, tok, jnp.int32(pos))
        np.testing.assert_allclose(
            np.asarray(lg, np.float32),
            np.asarray(full_logits[:, pos], np.float32), atol=1e-1,
            rtol=3e-2)


@pytest.mark.parametrize("arch,window", [("stablelm-1.6b", 0),
                                         ("zamba2-2.7b", 0),
                                         ("mixtral-8x22b", 16)])
def test_decode_step_writes_only_its_position(arch, window):
    """decode_step at ``pos`` changes each layer's K/V cache at sequence
    index ``pos`` (``pos % window`` in a ring) and nowhere else: every
    other element is bit-identical to the input cache."""
    cfg = get_smoke_config(arch).replace(swa_window=window)
    key = jax.random.PRNGKey(5)
    params = lm.init_params(cfg, key)
    cache = lm.init_cache(cfg, max_len=24, batch=B)
    for i, n in enumerate(("k", "v")):
        cache[n] = jax.random.normal(jax.random.fold_in(key, i),
                                     cache[n].shape).astype(cache[n].dtype)
    S = cache["k"].shape[-1]
    assert S == (window or 24)
    for pos in (5, 22):
        tok = jnp.full((B, 1), pos, jnp.int32)
        _, new = lm.decode_step(cfg, params, cache, tok, jnp.int32(pos))
        idx = pos % S if window else pos
        others = np.arange(S) != idx
        for n in ("k", "v"):
            old = np.asarray(cache[n]).view(np.uint16)
            upd = np.asarray(new[n]).view(np.uint16)
            np.testing.assert_array_equal(upd[..., others], old[..., others])
            written = (upd[..., idx] != old[..., idx]).any(axis=(1, 2))
            assert written.all(), (n, pos, written)


def test_moe_capacity_drops_are_bounded():
    from repro.models import moe as moe_mod
    cfg = get_smoke_config("mixtral-8x22b")
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    p_moe = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(key, (2, 64, cfg.d_model), jnp.bfloat16)
    y, aux = moe_mod.moe_ffn(cfg, p_moe, x, jnp.bfloat16)
    assert y.shape == x.shape
    assert float(aux) >= 0
    assert not bool(jnp.isnan(y.astype(jnp.float32)).any())


def test_cell_enumeration():
    from repro.configs import cells
    all_cells = cells(include_skipped=True)
    assert len(all_cells) == 44
    skipped = [c for c in all_cells if not c[2]]
    assert len(skipped) == 7          # pure full-attention archs x long_500k
    assert all(c[1] == "long_500k" for c in skipped)
