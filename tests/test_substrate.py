"""Data pipeline, checkpointing, optimizer, serve loop, KV pager."""

import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import RingLoader, TokenStore, make_synthetic_corpus
from repro.checkpoint import (latest_step, load_checkpoint, save_checkpoint)
from repro.models import lm
from repro.optim import adamw_init, adamw_update, cosine_schedule


@pytest.fixture
def tmpdir():
    d = tempfile.mkdtemp()
    yield d
    shutil.rmtree(d, ignore_errors=True)


def test_pipeline_token_integrity(tmpdir):
    """Corpus = arange -> every loaded row must be consecutive ints and
    labels must be tokens shifted by one."""
    path = os.path.join(tmpdir, "tok.bin")
    np.arange(100_000, dtype=np.int32).tofile(path)
    loader = RingLoader(TokenStore(path), batch=4, seq=32, prefetch=2)
    it = iter(loader)
    for _ in range(5):
        b = next(it)
        t, l = b["tokens"], b["labels"]
        assert t.shape == (4, 32) and l.shape == (4, 32)
        assert np.all(np.diff(t, axis=1) == 1)
        assert np.all(l == t + 1)
    assert loader.stats.batch_efficiency() > 1.5   # batched submission


def test_checkpoint_roundtrip_and_retention(tmpdir):
    tree = {"a": jnp.arange(100, dtype=jnp.float32).reshape(10, 10),
            "b": {"c": jnp.ones((3,), jnp.int32),
                  "d": jnp.asarray(2.5, jnp.float32)}}
    for step in (10, 20, 30, 40):
        save_checkpoint(tmpdir, step, tree, keep=2)
    assert latest_step(tmpdir) == 40
    # retention keeps only the last 2
    steps = [int(n.split("_")[1]) for n in os.listdir(tmpdir)
             if n.startswith("step_")]
    assert sorted(steps) == [30, 40]
    out = load_checkpoint(tmpdir, 40, tree)
    for l0, l1 in zip(jax.tree_util.tree_leaves(tree),
                      jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


def test_partial_checkpoint_invisible(tmpdir):
    tree = {"a": jnp.ones((4,))}
    save_checkpoint(tmpdir, 10, tree)
    # a torn checkpoint: data but no manifest
    os.makedirs(os.path.join(tmpdir, "step_20"))
    with open(os.path.join(tmpdir, "step_20", "data.bin"), "wb") as f:
        f.write(b"garbage")
    assert latest_step(tmpdir) == 10


def test_train_restart_matches_uninterrupted(tmpdir):
    """Fault tolerance: crash at step 8, restore from 5, final params must
    match the uninterrupted run exactly (same data order per step)."""
    from repro.launch.steps import make_train_step
    cfg = get_smoke_config("stablelm-1.6b")
    key = jax.random.PRNGKey(0)
    params0 = lm.init_params(cfg, key)
    step_fn = jax.jit(make_train_step(cfg))

    def batch_for(i):
        k = jax.random.PRNGKey(1000 + i)
        t = jax.random.randint(k, (2, 32), 0, cfg.vocab_size)
        return {"tokens": t, "labels": jnp.roll(t, -1, axis=1)}

    # uninterrupted
    p, o = params0, adamw_init(params0)
    for i in range(10):
        p, o, _ = step_fn(p, o, batch_for(i))
    ref = p

    # interrupted at 8, checkpoint at 5, resume
    p, o = params0, adamw_init(params0)
    for i in range(8):
        if i == 5:
            save_checkpoint(tmpdir, 5, {"p": p, "o": o})
        p, o, _ = step_fn(p, o, batch_for(i))
        if i == 7:
            break  # "crash"
    st = latest_step(tmpdir)
    restored = load_checkpoint(tmpdir, st, {"p": p, "o": o})
    p, o = restored["p"], restored["o"]
    for i in range(st, 10):
        p, o, _ = step_fn(p, o, batch_for(i))
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adamw_against_numpy_reference():
    p = {"w": jnp.asarray([[1.0, -2.0], [0.5, 3.0]])}
    g = {"w": jnp.asarray([[0.1, 0.2], [-0.3, 0.4]])}
    st = adamw_init(p)
    p2, st2, gn = adamw_update(g, st, p, lr=0.1, weight_decay=0.0,
                               clip_norm=1e9)
    # numpy adam step 1: m=0.1g, v=0.05g^2, bias-corrected => update = g/|g|
    gw = np.asarray(g["w"])
    m = 0.1 * gw / (1 - 0.9)
    v = 0.05 * gw ** 2 / (1 - 0.95)
    exp = np.asarray(p["w"]) - 0.1 * m / (np.sqrt(v) + 1e-8)
    np.testing.assert_allclose(np.asarray(p2["w"]), exp, atol=1e-6)
    np.testing.assert_allclose(float(gn), np.linalg.norm(gw), atol=1e-6)


def test_cosine_schedule_shape():
    lrs = [float(cosine_schedule(jnp.asarray(s), peak_lr=1.0, warmup=10,
                                 total=100)) for s in range(100)]
    assert lrs[0] < lrs[9]                 # warmup rises
    assert abs(lrs[10] - 1.0) < 0.05       # peak
    assert lrs[-1] < 0.2                   # decays toward floor*peak
    assert min(lrs[10:]) >= 0.099          # floor


def test_serve_greedy_matches_forward():
    """Teacher forcing: greedy decode continuation must equal argmax of a
    full forward at each position."""
    from repro.serve import ServeLoop
    cfg = get_smoke_config("stablelm-1.6b")
    key = jax.random.PRNGKey(5)
    params = lm.init_params(cfg, key)
    prompt = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    sv = ServeLoop(cfg, params, max_len=48)
    gen = sv.generate(prompt, 6)

    # replay: forward over prompt+gen, check greedy consistency
    seq = jnp.concatenate([prompt, gen], axis=1)
    logits, _, _ = lm.forward(cfg, params, {"tokens": seq})
    for j in range(6):
        pos = 16 + j - 1
        expect = jnp.argmax(logits[:, pos, :cfg.vocab_size], -1)
        np.testing.assert_array_equal(np.asarray(gen[:, j]),
                                      np.asarray(expect))


def test_kv_pager_spill_and_restore():
    from repro.serve.kv_paging import KVPager, PagerConfig
    cfg = PagerConfig(n_hbm_pages=8, page_tokens=8, kv_heads=2, head_dim=16)
    pager = KVPager(cfg)
    key = jax.random.PRNGKey(0)
    ref = {}
    for blk in range(24):                  # 3x pool size
        kp = jax.random.normal(jax.random.fold_in(key, blk),
                               (8, 2, 16), jnp.bfloat16)
        vp = jax.random.normal(jax.random.fold_in(key, 100 + blk),
                               (8, 2, 16), jnp.bfloat16)
        ref[blk] = kp
        pager.put_page_sync((0, blk), kp, vp)
    assert pager.spilled_pages() > 0       # overflowed the frame pool
    assert pager.pool.writebacks > 0       # dirty pages hit the spill fd
    for blk in (0, 3, 11):
        kp, _ = pager.unpack_page(pager.read_page_sync((0, blk)))
        np.testing.assert_array_equal(
            np.asarray(kp.astype(jnp.float32)),
            np.asarray(ref[blk].astype(jnp.float32)))


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and code sets no other directory;
    without it the cache is the fixed .jax_cache/ at the checkout root."""
    from repro.launch import compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == saved[keys[0]]
        else:
            root = compile_cache.CHECKOUT_CACHE.parent
            assert (root / "chip_smoke.py").exists()
            assert got == jax.config.jax_compilation_cache_dir == \
                str(root / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_gradient_compression_error_feedback():
    """EF must make the AVERAGE of compressed grads track the true grads:
    after N steps, sum(compressed) ~= sum(true) despite int8 rounding."""
    from repro.optim.compression import compress_decompress, ef_init
    key = jax.random.PRNGKey(0)
    tree = {"w": jax.random.normal(key, (64, 64)) * 0.01}
    ef = ef_init(tree)
    acc_true = np.zeros((64, 64))
    acc_hat = np.zeros((64, 64))
    for i in range(20):
        g = {"w": jax.random.normal(jax.random.fold_in(key, i),
                                    (64, 64)) * 0.01}
        g_hat, ef = compress_decompress(g, ef)
        acc_true += np.asarray(g["w"])
        acc_hat += np.asarray(g_hat["w"])
    # single-shot int8 error is ~scale/2; EF keeps the accumulated error
    # bounded by ONE step's quantization error instead of N steps' worth
    resid = np.abs(acc_true - acc_hat).max()
    assert resid < 5e-4, resid


def test_train_step_with_compression_converges():
    from repro.configs import get_smoke_config
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    from repro.optim.compression import ef_init
    cfg = get_smoke_config("stablelm-1.6b").replace(grad_compression=True)
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    opt = adamw_init(params)
    ef = ef_init(params)
    step = jax.jit(make_train_step(cfg, peak_lr=1e-2, warmup=1))
    losses = []
    for i in range(8):
        k = jax.random.fold_in(key, i)
        t = jax.random.randint(k, (2, 32), 0, cfg.vocab_size)
        batch = {"tokens": t, "labels": jnp.roll(t, -1, 1)}
        params, opt, ef, m = step(params, opt, ef, batch)
        losses.append(float(m["loss"]))
    # learning with int8 grads: at this lr on random tokens the loss
    # oscillates, so require a clear dip rather than last < first
    # (the strict form flakes on platform-dependent float rounding)
    assert min(losses[1:]) < losses[0] - 0.05
