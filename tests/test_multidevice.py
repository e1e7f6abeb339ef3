"""Multi-device semantics, run in a SUBPROCESS with 8 forced host devices
(the main test process must keep seeing 1 device — see conftest)."""

import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert len(jax.devices()) == 8
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))

    # --- shard_map all-to-all dispatch/combine round trip -----------------
    from repro.distributed.a2a import moe_dispatch_combine
    B, G, E, C, D = 2, 4, 4, 3, 5
    x = jnp.arange(B * G * E * C * D, dtype=jnp.float32).reshape(
        B, G, E, C, D)
    xg = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
    dispatch, combine = moe_dispatch_combine(mesh, ("data",))
    xe = dispatch(xg)
    back = combine(xe)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    # dispatch is the (G<->E) shard transpose: contents preserved
    np.testing.assert_allclose(np.asarray(xe).sum(), np.asarray(x).sum())

    # --- sharded train step == single-device train step -------------------
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.models.partitioning import rules_for
    from repro.launch.steps import make_train_step, shardings_for_cell
    from repro.optim import adamw_init

    cfg = get_smoke_config("stablelm-1.6b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=256)
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    opt = adamw_init(params)
    toks = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}

    # reference: single-device
    ref_step = jax.jit(make_train_step(cfg))
    p_ref, _, m_ref = ref_step(params, opt, batch)

    # sharded: 2-way data x 4-way model
    rules = rules_for(mesh, 4)
    pspecs = lm.param_specs(cfg, mesh, rules)
    psh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    params_s = jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, s), params, psh)
    opt_s = adamw_init(params_s)
    step_s = jax.jit(make_train_step(cfg, mesh, rules))
    with mesh:
        p_s, _, m_s = step_s(params_s, opt_s, batch)
    assert abs(float(m_ref["loss"]) - float(m_s["loss"])) < 2e-2, \\
        (float(m_ref["loss"]), float(m_s["loss"]))
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_s)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2)
    print("MULTIDEVICE_OK")
""")


def test_multidevice_a2a_and_sharded_train():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "MULTIDEVICE_OK" in r.stdout


DECODE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from functools import partial
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert len(jax.devices()) == 8
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.models.partitioning import rules_for

    mesh = make_mesh((2, 4), ("data", "model"))
    B, max_len, pos = 4, 32, 9
    rules = rules_for(mesh, B)
    for arch in ("stablelm-1.6b", "zamba2-2.7b"):
        cfg = get_smoke_config(arch)
        key = jax.random.PRNGKey(0)
        params = lm.init_params(cfg, key)
        cache = lm.init_cache(cfg, max_len, B)
        for i, n in enumerate(("k", "v")):
            cache[n] = jax.random.normal(
                jax.random.fold_in(key, i), cache[n].shape).astype(
                    cache[n].dtype)
        tok = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
        step = partial(lm.decode_step, cfg)
        ref_lg, ref_cache = jax.jit(step)(params, cache, tok, jnp.int32(pos))

        tree = jax.tree_util.tree_map
        psh = tree(lambda s: NamedSharding(mesh, s),
                   lm.param_specs(cfg, mesh, rules))
        csh = tree(lambda s: NamedSharding(mesh, s),
                   lm.cache_specs(cfg, max_len, B, mesh, rules))
        assert csh["k"].spec == P(None, "data", None, "model"), csh["k"]
        params_s = tree(jax.device_put, params, psh)
        cache_s = tree(jax.device_put, cache, csh)
        scalar = NamedSharding(mesh, P())
        with mesh:
            lg, cache_s = jax.jit(
                step, in_shardings=(psh, csh, scalar, scalar),
                out_shardings=(scalar, csh), donate_argnums=(1,))(
                    params_s, cache_s, tok, jnp.int32(pos))
        # bf16 compute summed in another order: a few ulps at |x| ~ 4
        np.testing.assert_allclose(np.asarray(lg, np.float32),
                                   np.asarray(ref_lg, np.float32),
                                   atol=1e-1, rtol=3e-2)
        others = np.arange(max_len) != pos
        for n in ("k", "v"):
            assert cache_s[n].sharding.spec == csh[n].spec
            got = np.asarray(cache_s[n], np.float32)
            np.testing.assert_array_equal(
                got[..., others], np.asarray(cache[n], np.float32)[..., others])
            np.testing.assert_allclose(got[..., pos],
                                       np.asarray(ref_cache[n], np.float32)
                                       [..., pos], atol=1e-1, rtol=3e-2)
    print("SHARDED_DECODE_OK")
""")


def test_multidevice_sharded_decode():
    """decode_step with the K/V cache sharded by the rules (batch over
    ``data``, the minor sequence axis over ``model``) lowers on a 2x4 mesh,
    keeps that sharding through the in-place write, and matches one
    device."""
    r = subprocess.run([sys.executable, "-c", DECODE_SCRIPT],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "SHARDED_DECODE_OK" in r.stdout
