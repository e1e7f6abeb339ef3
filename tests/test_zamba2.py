"""Zamba2 (family ``hybrid``) at a small Zamba2-shaped size on the CPU: 2
B/C groups, 2 shared blocks, sites before layers 3, 5, 8 and 10 of 11,
rank-8 adapters and a tied embedding (``get_smoke_config("zamba2-7b")``).

The program is held to the plain reference (``chipbench/reference/
hybrid.py``) on seeded weights, through the forward pass and through
prefill then decode over the cache; the float8 control misses the same
tolerance; the reference is held to transformers' ``Zamba2ForCausalLM``.
Beside them: the cells that were there keep their weights' trees, and the
work counts of the new cell against hand calculations at its real sizes."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program
from chipbench.reference import dense, hybrid, ssm
from chipbench.reference.common import seed_key
from chipbench.spec import HERE, load_json
from chipbench.work import hybrid as work
from repro.configs import get_smoke_config
from repro.launch.steps import make_prefill_step
from repro.models import lm

# float32 compute at the highest precision: program and reference differ
# only in the order of their sums (chunked against one position at a time,
# flash against whole-row softmax), about 4e-5 on logits of about 4
TOL = 2e-4
# decode reads the cache, which holds K/V and the convolution's last inputs
# in bfloat16 (the SSM state in float32) whatever the compute dtype: up to
# 5e-2 on logits of about 4, as in tests/test_models.py's prefill/decode
# check of every family
DECODE_TOL = 1e-1
S0, S1 = 32, 40           # prompt, then 8 tokens decoded through the cache


def conf_of(cfg) -> dict:
    """The configuration file of the cell at the preset's sizes, float32."""
    return dict(load_json(HERE / "configs" / "zamba2-7b.json"),
                n_layers=cfg.n_layers, d_model=cfg.d_model,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, d_ff=cfg.d_ff, vocab_size=cfg.vocab_size,
                ssm=dataclasses.asdict(cfg.ssm),
                hybrid_layer_ids=list(cfg.hybrid_layer_ids),
                num_mem_blocks=cfg.num_mem_blocks,
                adapter_rank=cfg.adapter_rank, param_dtype="float32",
                compute_dtype="float32", use_pallas=False)


@pytest.fixture(scope="module")
def zamba():
    cfg = get_smoke_config("zamba2-7b").replace(compute_dtype="float32")
    conf = conf_of(cfg)
    params = jax.jit(functools.partial(hybrid.init_params, conf))(
        seed_key(2**33 + 7))
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, conf["vocab_size"], (2, S1)), jnp.int32)
    ref = jax.jit(functools.partial(hybrid.logits, conf),
                  static_argnums=(2, 3))
    return cfg, conf, params, tokens, np.asarray(ref(params, tokens, 0))


def test_preset_is_zamba2_shaped(zamba):
    cfg, conf, params, _, _ = zamba
    assert cfg.ssm.ngroups == 2 and cfg.num_mem_blocks == 2
    assert cfg.hybrid_sites == (3, 5, 8, 10) and cfg.n_layers == 11
    assert cfg.adapter_rank and cfg.tie_embeddings
    program.check_layout(cfg, params)


def test_forward_matches_reference(zamba):
    cfg, conf, params, tokens, want = zamba
    with jax.default_matmul_precision("highest"):
        got, _, _ = jax.jit(functools.partial(lm.forward, cfg))(
            params, {"tokens": tokens})
    np.testing.assert_allclose(got[..., :conf["vocab_size"]], want,
                               atol=TOL, rtol=TOL)


def test_prefill_then_decode_matches_full_forward(zamba):
    """Prefill the first S0 tokens, then decode the rest one at a time
    through the cache (SSM and conv state, each site's K/V written in
    place): every step's logits are the reference's at that position."""
    cfg, conf, params, tokens, want = zamba
    V = conf["vocab_size"]
    with jax.default_matmul_precision("highest"):
        lg, cache = jax.jit(make_prefill_step(cfg))(
            params, {"tokens": tokens[:, :S0]})
        np.testing.assert_allclose(lg[:, :V], want[:, S0 - 1], atol=TOL,
                                   rtol=TOL)
        full = lm.init_cache(cfg, S1, 2)
        cache = {n: full[n].at[tuple(slice(0, s) for s in c.shape)].set(c)
                 for n, c in cache.items()}
        step = jax.jit(functools.partial(lm.decode_step, cfg))
        for pos in range(S0, S1):
            lg, cache = step(params, cache, tokens[:, pos:pos + 1],
                             jnp.int32(pos))
            np.testing.assert_allclose(lg[:, :V], want[:, pos],
                                       atol=DECODE_TOL, rtol=3e-2)


def test_float8_control_fails(zamba):
    """Both tolerances above are tight enough that the reference with
    every matmul's inputs rounded to float8 misses them, by far."""
    _, conf, params, tokens, want = zamba
    low = np.asarray(jax.jit(functools.partial(
        hybrid.logits, conf, start=0, lower=True))(params, tokens))
    assert np.abs(low - want).max() > 10 * DECODE_TOL


def test_reference_matches_transformers(zamba):
    """The same weights in transformers' torch Zamba2ForCausalLM give the
    reference's logits. Its torch path sums the inter-chunk state decay
    over the wrong axis (``new_states = (...).sum(dim=2)``), so the prompt
    lies within one of its chunks (chunk_size 64, 40 tokens), where that
    sum is exact; the program's own chunking is checked above."""
    pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    import torch
    cfg, conf, params, tokens, want = zamba
    at = hybrid.sites(conf)
    s = conf["ssm"]
    hc = transformers.Zamba2Config(
        vocab_size=conf["vocab_size"], hidden_size=conf["d_model"],
        num_hidden_layers=conf["n_layers"],
        layers_block_type=["hybrid" if i in at else "mamba"
                           for i in range(conf["n_layers"])],
        mamba_d_state=s["d_state"], mamba_d_conv=s["d_conv"],
        mamba_expand=s["expand"], mamba_ngroups=s["ngroups"],
        n_mamba_heads=s["expand"] * conf["d_model"] // s["headdim"],
        use_conv_bias=True, chunk_size=64, time_step_min=s["dt_min"],
        intermediate_size=conf["d_ff"], hidden_act="gelu",
        num_attention_heads=conf["n_heads"],
        num_mem_blocks=conf["num_mem_blocks"],
        use_shared_attention_adapter=False,
        adapter_rank=conf["adapter_rank"], use_mem_rope=True,
        rope_theta=conf["rope_theta"], rms_norm_eps=conf["norm_eps"],
        tie_word_embeddings=True, attn_implementation="eager")
    assert hc.attention_head_dim == conf["head_dim"]
    model = transformers.Zamba2ForCausalLM(hc).eval()
    P = jax.tree_util.tree_map(
        lambda a: torch.from_numpy(np.array(a, np.float32)), params)
    cat = lambda *ts: torch.cat(ts, -1).T                    # noqa: E731

    def put(dst, src):
        with torch.no_grad():
            dst.copy_(src)

    put(model.model.embed_tokens.weight, P["embed"][:conf["vocab_size"]])
    put(model.model.final_layernorm.weight, P["final_norm"])
    for i, layer in enumerate(model.model.layers):
        mamba = layer.mamba_decoder if i in at else layer
        p = {n: w[i] for n, w in P["layers"].items()}
        mx = mamba.mamba
        put(mamba.input_layernorm.weight, p["in_norm"])
        put(mx.in_proj.weight,
            cat(p["wz"], p["wx"], p["wb"], p["wc"], p["wdt"]))
        put(mx.conv1d.weight, cat(p["conv_x"], p["conv_b"],
                                  p["conv_c"])[:, None])
        put(mx.conv1d.bias, torch.cat([p["conv_x_bias"], p["conv_b_bias"],
                                       p["conv_c_bias"]]))
        for n in ("dt_bias", "A_log", "D"):
            put(getattr(mx, n), p[n])
        put(mx.norm.weight, p["norm"])
        put(mx.out_proj.weight, p["wo"].T)
        if i in at:
            k = at.index(i)
            blk = P["shared"][k % conf["num_mem_blocks"]]
            site = P["sites"][k]
            put(layer.linear.weight, site["linear"].T)
            sh = layer.shared_transformer
            put(sh.input_layernorm.weight, blk["ln_in"])
            put(sh.pre_ff_layernorm.weight, blk["ln_ff"])
            for n in "qkvo":
                put(getattr(sh.self_attn, f"{n}_proj").weight,
                    blk["attn"][f"w{n}"].T)
            ff = sh.feed_forward
            put(ff.gate_up_proj.weight, cat(blk["mlp"]["w1"],
                                            blk["mlp"]["w3"]))
            put(ff.down_proj.weight, blk["mlp"]["w2"].T)
            adapter = ff.gate_up_proj_adapter_list[k]
            put(adapter[0].weight, site["adapter_in"].T)
            put(adapter[1].weight, cat(site["adapter_g"], site["adapter_u"]))
    with torch.no_grad():
        got = model(torch.from_numpy(np.asarray(tokens, np.int64)),
                    use_cache=False).logits.numpy()
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("name,ref", [("stablelm-1.6b", dense),
                                      ("mamba2-130m", ssm),
                                      ("zamba2-7b", hybrid)])
def test_seeded_weights_have_the_program_tree(name, ref):
    """At the cells' real sizes (shapes only): the benchmark's seeded
    weights and the program's parameter tree agree, so that no leaf the
    hybrid family added reaches the other families' trees."""
    conf = load_json(HERE / "configs" / f"{name}.json")
    seeded = jax.eval_shape(functools.partial(ref.init_params, conf),
                            seed_key(1))
    assert lm.abstract_params(program.model_config(conf)) == seeded


def test_work_counts_at_the_cells_sizes():
    c = load_json(HERE / "configs" / "zamba2-7b.json")
    # Mamba layer: 3584 x (7168 z + 7168 x + 2·2·64 B, C + 112 dt) in,
    # 7168 x 3584 out
    mamba = 3584 * (7168 + 7168 + 256 + 112) + 7168 * 3584
    # shared block: q, k, v 7168 x 7168 each, o 7168 x 3584, MLP 3 of
    # 3584 x 14336; a site: linear 3584², adapter 3584 x 128 + 128 x 28672
    block = 3 * 7168 * 7168 + 7168 * 3584 + 3 * 3584 * 14336
    site = 3584 * 3584 + 128 * (3584 + 2 * 14336)
    assert work.n_sites(c) == 4
    assert (work.mamba_params(c), work.block_params(c),
            work.site_params(c)) == (mamba, block, site)
    other = 5 * (7168 + 256) + 3 * 112 + 7168 + 3584
    assert work.param_bytes(c) == 2 * (24 * (mamba + other)
                                       + 4 * (block + 3 * 3584 + site)
                                       + 3584 * 32000 + 3584)
    state = 24 * (112 * 64 * 64 * 4 + 3 * (7168 + 256) * 2)
    assert work.state_bytes(c) == state
    kv = 4 * 2 * 32 * 224 * 2                      # 4 sites, K and V, bf16
    flops, byts = work.decode_step(c, [3100] * 8)
    assert byts == (work.param_bytes(c) + 8 * 3584 * 2 + kv * 3100 * 8
                    + kv * 8 + 2 * 8 * state)
    assert flops > 2 * 8 * (24 * mamba + 4 * (block + site))
    # about 10.4 GB a step at 3.1k live positions: the floor of the step
    assert 9.5e9 < byts < 11e9
    # the kernel's C·Bᵀ once per group per chunk: 12 chunks of 256 a prompt
    f, _ = work.ssd_intra_chunk(c, 8, 3072)
    assert f == 8 * 12 * (2 * 2 * 256 * 256 * 64
                          + 112 * (2 * 256 * 256 * 64 + 2 * 256 * 64 * 64))


def test_cache_bytes_split_kv_and_state():
    cfg = get_smoke_config("zamba2-7b")
    got = lm.cache_bytes(cfg, 24, 2)
    d = lm.abstract_cache(cfg, 24, 2)
    nbytes = lambda n: d[n].size * d[n].dtype.itemsize       # noqa: E731
    assert got == {"kv_bytes": nbytes("k") + nbytes("v"),
                   "state_bytes": sum(nbytes(n) for n in
                                      ("ssm", "conv_x", "conv_b", "conv_c"))}
    assert lm.cache_bytes(get_smoke_config("stablelm-1.6b"), 24,
                          2)["state_bytes"] == 0
