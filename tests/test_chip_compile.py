"""Compile-only rehearsals for one TPU v5e chip, at real model widths.

The chip is described, not attached: the TPU compiler refuses here what
it would refuse on the chip (unsupported lowerings, tile misalignment,
VMEM or HBM overflow), and nothing runs. Each kernel test asserts that
the kernel survived into the compiled program as a Mosaic custom call.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.paged_attn.kernel import paged_attention
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.kernel import ssd_chunk_call
from repro.launch.mesh import HBM_BYTES
from repro.launch.steps import make_serve_step
from repro.models import lm


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e, with the persistent compile cache
    off (what is compiled for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(os.environ, "TPU_LOG_DIR",
                   os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _kernels(compiled) -> set:
    """Names of the Pallas calls in a compiled program, as the trace
    reduction names them."""
    from chipbench import tracing
    return {tracing.kernel_name(line.strip())
            for line in compiled.as_text().splitlines()} - {None}


def _metric_kernel(metric: str) -> str:
    from chipbench.spec import load_module
    return load_module("metrics", metric).KERNEL


def _moved(compiled, sizes) -> list:
    """Copies and transposes (fusions' bodies included) whose result has
    one of ``sizes`` elements."""
    moved = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                     r"(copy|transpose)\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",") if d) \
                in sizes:
            moved.append(line.strip()[:120])
    return moved


def _cell_serve_step(one_chip, name, B, max_len):
    """The decode step of a benchmark configuration (its file's sizes and
    dtypes) as its cell serves it, the cache donated, compiled; with the
    cache's shapes."""
    from chipbench import program
    from chipbench.spec import HERE, load_json
    cfg = program.model_config(load_json(HERE / "configs" / f"{name}.json"))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,   # noqa: E731
                                           sharding=one_chip)
    params = jax.tree_util.tree_map(place, lm.abstract_params(cfg))
    cache = jax.tree_util.tree_map(place, lm.abstract_cache(cfg, max_len, B))
    arg = lambda s: jax.ShapeDtypeStruct(s, jnp.int32,         # noqa: E731
                                         sharding=one_chip)
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, arg((B, 1)), arg(())).compile()
    return cfg, params, serve, {n: c.shape for n, c in cache.items()}


@pytest.mark.parametrize("arch,dtype", [
    ("stablelm-1.6b", jnp.bfloat16),
    ("stablelm-1.6b", jnp.float32),
    ("qwen2-vl-2b", jnp.bfloat16),
])
def test_paged_attention_compiles(one_chip, arch, dtype):
    cfg = get_config(arch)
    B, page, nblk, pool = 8, 16, 64, 160
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    c = _compile(paged_attention, one_chip,
                 ((B, H, hd), dtype), ((pool, page, KH, hd), dtype),
                 ((pool, page, KH, hd), dtype), ((B, nblk), jnp.int32),
                 ((B,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles(one_chip):
    cfg = get_config("stablelm-1.6b")
    B, S = 1, 2048
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    c = _compile(flash_attention_fwd, one_chip,
                 ((B, S, H, hd), jnp.bfloat16), ((B, S, KH, hd), jnp.bfloat16),
                 ((B, S, KH, hd), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_chunk_compiles(one_chip):
    cfg = get_config("mamba2-130m")
    s = cfg.ssm
    B, S = 2, 2 * s.chunk
    nh, hp, ns = s.n_heads(cfg.d_model), s.headdim, s.d_state
    c = _compile(lambda *a: ssd_chunk_call(*a, chunk=s.chunk), one_chip,
                 ((B, S, nh, hp), jnp.float32), ((B, S, nh), jnp.float32),
                 ((nh,), jnp.float32), ((B, S, ns), jnp.float32),
                 ((B, S, ns), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_kernel_keeps_the_name_its_roofline_reads(one_chip):
    """The compiled ``ops.ssd`` at mamba2-130m's sizes holds a Pallas call
    that the trace reduction names as ``ssd_scan_roofline`` expects."""
    cfg = get_config("mamba2-130m")
    s = cfg.ssm
    B, S = 8, 2 * s.chunk
    nh, hp, ns = s.n_heads(cfg.d_model), s.headdim, s.d_state
    args = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
            for shape in ((B, S, nh, hp), (B, S, nh), (nh,), (B, S, ns),
                          (B, S, ns), (nh,))]
    c = ssd_ops.ssd.lower(*args, chunk=s.chunk).compile()
    assert _metric_kernel("ssd_scan_roofline") in _kernels(c)


@pytest.fixture(scope="module")
def stablelm_serve_step(one_chip):
    """The decode step the benchmark serves for stablelm-1.6b (B=8,
    max_len=1024, the cache donated), compiled at full width; with the
    shape of its K cache."""
    cfg = get_config("stablelm-1.6b")
    B, max_len = 8, 1024
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,   # noqa: E731
                                           sharding=one_chip)
    params = jax.tree_util.tree_map(place, lm.abstract_params(cfg))
    cache = jax.tree_util.tree_map(place, lm.abstract_cache(cfg, max_len, B))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    c = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, tokens, pos).compile()
    return c, cache["k"].shape


def test_stablelm_serve_step_fits_one_chip(stablelm_serve_step):
    """The decode step chip_smoke.py serves (B=8, max_len=1024) at full
    width fits one chip's HBM: arguments plus temporaries."""
    c, _ = stablelm_serve_step
    m = c.memory_analysis()
    need = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert need < HBM_BYTES, (m.argument_size_in_bytes, m.temp_size_in_bytes)


def test_stablelm_serve_step_never_copies_the_kv_cache(stablelm_serve_step):
    """The decode step reads the K/V cache where it lies and writes one
    token into it in place: no copy or transpose in the compiled program
    (fusions' bodies included) moves the whole stack or one layer of it,
    in any shape, and the temporaries stay below the 3.92 GiB that such
    copies took."""
    c, shape = stablelm_serve_step
    stack = math.prod(shape)
    moved = _moved(c, {stack, stack // shape[0]})
    assert not moved, moved
    assert c.memory_analysis().temp_size_in_bytes < 3.92 * 2**30


@pytest.fixture(scope="module")
def zamba2_cell(one_chip):
    """zamba2-7b as its benchmark cell serves it (the configuration file:
    24 layers, bfloat16 weights; B=8, 3072-token prompts, max_len 3328):
    its serve step with the cache donated and its prefill step, compiled;
    with the cache's shapes."""
    from repro.launch.steps import make_prefill_step
    B, S0 = 8, 3072
    cfg, params, serve, shapes = _cell_serve_step(one_chip, "zamba2-7b", B,
                                                  3328)
    prefill = jax.jit(make_prefill_step(cfg)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((B, S0), jnp.int32,
                                                sharding=one_chip)}).compile()
    return serve, prefill, shapes


@pytest.fixture(scope="module")
def mamba2_serve_step(one_chip):
    """mamba2-130m's decode step as ``mamba2-130m.chat-gen`` serves it
    (B=128, max_len 512, the cache donated), compiled; with the cache's
    shapes."""
    _, _, serve, shapes = _cell_serve_step(one_chip, "mamba2-130m", 128, 512)
    return serve, shapes


@pytest.mark.parametrize("cell", ["mamba2_serve_step", "zamba2_cell"])
def test_serve_step_updates_the_ssm_state_in_place(cell, request):
    """Decode updates each layer's SSM state where it lies, in the layout
    the cache stores: no copy or transpose (fusions' bodies included) moves
    the whole state stack or one layer of it, in any shape. The update is
    the Pallas call that ``ssd_state_roofline`` counts; the prefill
    kernel's, which ``ssd_scan_roofline`` counts, is absent."""
    fixture = request.getfixturevalue(cell)
    serve, shape = fixture[0], fixture[-1]["ssm"]
    stack = math.prod(shape)
    moved = _moved(serve, {stack, stack // shape[0]})
    assert not moved, moved
    names = _kernels(serve)
    assert _metric_kernel("ssd_state_roofline") in names
    assert _metric_kernel("ssd_scan_roofline") not in names


def test_mamba2_serve_step_temporaries_under_1gib(mamba2_serve_step):
    """Without the stacked copy of the 2.42 GB state that the layer scan
    used to build, the step's temporaries stay under 1 GiB."""
    serve, _ = mamba2_serve_step
    assert serve.memory_analysis().temp_size_in_bytes < 2**30


def test_zamba2_steps_fit_one_chip(zamba2_cell):
    """Arguments, temporaries and outputs of each step fit one chip's HBM
    with room left (described v5e: serve step 8.32 + 1.80 GiB, prefill
    5.09 + 5.08 + 3.01 GiB)."""
    serve, prefill, _ = zamba2_cell
    for c in (serve, prefill):
        m = c.memory_analysis()
        need = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        assert need < 15 * 2**30, (m.argument_size_in_bytes,
                                   m.temp_size_in_bytes,
                                   m.output_size_in_bytes)


def test_zamba2_serve_step_never_copies_the_kv_cache(zamba2_cell):
    """Each site writes its token into its K/V rows in place: no copy or
    transpose moves the sites' stack or one site of it."""
    serve, _, shapes = zamba2_cell
    stack = math.prod(shapes["k"])
    moved = _moved(serve, {stack, stack // shapes["k"][0]})
    assert not moved, moved
    # the prefill kernel's roofline counts prefill calls alone
    assert _metric_kernel("ssd_scan_roofline") not in _kernels(serve)


def test_zamba2_prefill_keeps_the_kernel_name_its_roofline_reads(
        zamba2_cell):
    """The grouped SSD kernel in zamba2-7b's prefill is the Pallas call
    that ``ssd_scan_roofline.gen`` counts (the reader of
    ``ssd_scan_roofline``)."""
    _, prefill, _ = zamba2_cell
    assert _metric_kernel("ssd_scan_roofline") in _kernels(prefill)
