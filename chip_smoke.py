#!/usr/bin/env python3
"""Bring-up smoke run of the serving path on one TPU chip.

    python3 chip_smoke.py [--seed N]

One process, one chip, everything made from ``--seed``:

* preflight — stop at once unless JAX's first device is a TPU;
* phase A — stablelm-1.6b at full width (random weights) behind
  ``ServeLoop``: 8 prompts of 512 tokens, 32 new tokens each. One
  ``lm.decode_step`` is checked against ``lm.forward`` over the same
  prefix; compile seconds and peak device memory are printed as bring-up
  observations;
* phase B — the pager→kernel seam at this model's cache geometry: layer
  0 of phase A's prefill cache goes through a ``KVPager`` too small to
  hold it (pages spill to the host and NVMe tiers and refault), and the
  compiled ``kernels/paged_attn`` over ``device_pools()`` is checked
  against ``paged_attention_ref`` and against dense decode attention over
  the original K/V.

The last line of stdout is one JSON object naming the device. Any failed
check exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict
from functools import partial
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.configs import get_config                         # noqa: E402
from repro.kernels.paged_attn.ops import paged_attention     # noqa: E402
from repro.kernels.paged_attn.ref import paged_attention_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import lm                                  # noqa: E402
from repro.models.attention import decode_attention          # noqa: E402
from repro.models.layers import apply_rope, rms_norm, rope_cos_sin  # noqa: E402
from repro.serve import KVPager, ServeLoop                   # noqa: E402
from repro.serve.kv_paging import PagerConfig                # noqa: E402

ARCH = "stablelm-1.6b"
BATCH, PROMPT, NEW, MAX_LEN = 8, 512, 32, 1024
PAGE_TOKENS, HBM_PAGES, HOST_PAGES, NVME_PAGES = 16, 160, 128, 256
PIN_SEQS = 4                 # sequences whose pages are fixed at a time
# tests/test_models.py::test_prefill_decode_consistency (bf16 compute)
LOGIT_ATOL, LOGIT_RTOL = 1e-1, 3e-2
# tests/test_kernels.py TOLS[bfloat16]: the pages are bf16
ATTN_TOL = 2e-2



class Compiles:
    """Compile seconds (a compile, or a load from the persistent cache)
    from JAX's monitoring events, and calls timed against them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        self.by_fn = defaultdict(float)      # "jit(<name>)" -> seconds
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, fun_name="", **_):
        if event == self.EVENT:
            self.seconds += seconds
            self.count += 1
            self.by_fn[fun_name] += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def timed(self, fn, *args):
        """(result, wall seconds, compile seconds, compiles) of one call,
        blocked on its result."""
        s0, n0 = self.seconds, self.count
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        return (out, time.perf_counter() - t0, self.seconds - s0,
                self.count - n0)


def say(*parts):
    print(*parts, flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    say(f"  ok: {what}")


def preflight():
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: JAX found platform {d.platform!r} "
            f"({d.device_kind}); this run needs a TPU and does not fall "
            "back to the CPU")
    say(f"preflight: platform={d.platform} device_kind={d.device_kind} "
        f"device_count={len(devs)} jax={jax.__version__}")
    return d


def max_err(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float32) -
                               np.asarray(b, np.float32))))


def phase_a(comp, cfg, seed):
    say(f"phase A: {cfg.arch_id} L={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
        f"vocab={cfg.vocab_size}; batch={BATCH} prompt={PROMPT} "
        f"new={NEW} max_len={MAX_LEN}")
    params, t, c, _ = comp.timed(jax.jit(lm.init_params, static_argnums=0),
                                 cfg, jax.random.PRNGKey(seed))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    say(f"  params: {n} made on the device in {t} s (compile_s={c})")
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (BATCH, PROMPT)),
                          jnp.int32)

    sv = ServeLoop(cfg, params, max_len=MAX_LEN)
    batch = {"tokens": prompts}
    (logits, pcache), t1, c1, n1 = comp.timed(sv.prefill, params, batch)
    _, t2, _, _ = comp.timed(sv.prefill, params, batch)
    say(f"  prefill: compile_s={c1} ({n1} programs) first_call_s="
        f"{t1} steady_ms={t2 * 1e3}")
    check(bool(jnp.all(jnp.isfinite(logits))), "prefill logits finite")

    # reference: decode_step writing position PROMPT-1 into the prefill
    # cache must reproduce forward's logits at that position
    dec = jax.jit(lm.decode_step, static_argnums=0)
    (lg, _), t3, c3, _ = comp.timed(dec, cfg, params, pcache,
                                    prompts[:, PROMPT - 1:],
                                    jnp.int32(PROMPT - 1))
    say(f"  decode_step (reference check): compile_s={c3} "
        f"first_call_s={t3}")
    check(bool(jnp.all(jnp.isfinite(lg))), "decode_step logits finite")
    err = max_err(lg, logits)
    ref_max = float(jnp.max(jnp.abs(logits.astype(jnp.float32))))
    agree = float(jnp.mean(jnp.argmax(lg, -1) == jnp.argmax(logits, -1)))
    say(f"  decode_step vs forward: max_abs_err={err} max_abs_ref={ref_max} "
        f"argmax_agree={agree}")
    check(np.allclose(np.asarray(lg, np.float32),
                      np.asarray(logits, np.float32),
                      atol=LOGIT_ATOL, rtol=LOGIT_RTOL),
          f"decode_step matches forward (atol={LOGIT_ATOL}, "
          f"rtol={LOGIT_RTOL})")
    k0, v0 = (lm.kv_by_position(pcache[n][0], cfg.n_kv_heads)
              for n in ("k", "v"))                  # (B, S, KH, hd)
    del pcache, dec

    out, t4, c4, n4 = comp.timed(sv.generate, prompts, NEW)
    say(f"  generate: compile_s={c4} ({n4} programs; serve_step "
        f"{comp.by_fn['jit(serve_step)']}) wall_s={t4}")
    check(out.shape == (BATCH, NEW), f"generated shape {out.shape}")
    check(bool(jnp.all((out >= 0) & (out < cfg.vocab_size))),
          "generated token ids in [0, vocab)")

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    say(f"  peak_bytes_in_use={peak}")
    return params, out, k0, v0


def layer0_query(cfg, params, tok, pos):
    """Layer 0's attention query for ``tok`` at position ``pos`` (as the
    model's decode step computes it)."""
    dtype = cfg.compute_dt()
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    h = rms_norm(lm.embed_tokens(cfg, params, tok, dtype), p["ln1"],
                 cfg.norm_eps)
    q = jnp.einsum("bsd,de->bse", h, p["attn"]["wq"].astype(dtype))
    q = q.reshape(tok.shape[0], 1, cfg.n_heads, cfg.hd)
    cos, sin = rope_cos_sin(jnp.asarray([pos]), cfg.hd, cfg.rope_theta)
    return apply_rope(q, cos, sin)[:, 0]


def phase_b(comp, cfg, params, out, k0, v0):
    pcfg = PagerConfig(kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                       page_tokens=PAGE_TOKENS, n_hbm_pages=HBM_PAGES,
                       host_pages=HOST_PAGES, nvme_pages=NVME_PAGES)
    nblk = PROMPT // PAGE_TOKENS
    say(f"phase B: pager page={pcfg.page_bytes} B ({PAGE_TOKENS} tokens x "
        f"{cfg.n_kv_heads} kv heads x {cfg.hd}), frames={HBM_PAGES}, "
        f"pages={BATCH * nblk}, host_pages={HOST_PAGES}")
    pager = KVPager(pcfg)
    kh, vh = np.asarray(k0), np.asarray(v0)
    for s in range(BATCH):
        for b in range(nblk):
            sl = slice(b * PAGE_TOKENS, (b + 1) * PAGE_TOKENS)
            pager.put_page_sync((s, b), kh[s, sl], vh[s, sl])
    say(f"  filled: spilled={pager.spilled_pages()} "
        f"writebacks={pager.pool.writebacks}")

    q = layer0_query(cfg, params, out[:, :1], PROMPT).astype(jnp.float32)
    lengths = jnp.full((PIN_SEQS,), PROMPT, jnp.int32)
    for g in range(0, BATCH, PIN_SEQS):
        seqs = range(g, g + PIN_SEQS)
        slots = [[pager.fix_page_sync((s, b)) for b in range(nblk)]
                 for s in seqs]
        k_pool, v_pool = pager.device_pools()
        table = jnp.asarray(slots, jnp.int32)
        gs = slice(g, g + PIN_SEQS)
        check(np.array_equal(
            np.asarray(k_pool[table]).reshape(kh[gs].shape), kh[gs]) and
            np.array_equal(
            np.asarray(v_pool[table]).reshape(vh[gs].shape), vh[gs]),
            f"seqs {g}-{g + PIN_SEQS - 1}: pages read back bit-exact "
            "through the pager")
        o, t, c, _ = comp.timed(partial(paged_attention, interpret=False),
                                q[gs], k_pool, v_pool, table, lengths)
        ref = paged_attention_ref(q[gs], k_pool, v_pool, table, lengths)
        dense = decode_attention(q[gs], k0[gs], v0[gs], jnp.int32(PROMPT - 1))
        e_ref, e_dense = max_err(o, ref), max_err(o, dense)
        say(f"  seqs {g}-{g + PIN_SEQS - 1}: paged_attention compiled "
            f"call_s={t} compile_s={c} max_abs_err vs ref={e_ref} "
            f"vs dense={e_dense}")
        check(bool(jnp.all(jnp.isfinite(o))), "paged attention finite")
        for name, r in (("paged_attention_ref", ref), ("dense", dense)):
            check(np.allclose(np.asarray(o), np.asarray(r, np.float32),
                              atol=ATTN_TOL, rtol=ATTN_TOL),
                  f"paged kernel matches {name} (tol {ATTN_TOL})")
        for row in slots:
            for idx in row:
                pager.pool.unfix(idx)
    say(f"  pager: faults={pager.faults} writebacks={pager.pool.writebacks} "
        f"host_reads={pager.host_reads} cold_reads={pager.cold_reads} "
        f"spilled={pager.spilled_pages()}")
    check(pager.pool.writebacks > 0 and pager.cold_reads > 0,
          "pages spilled to the tiers and refaulted from NVMe")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = preflight()
    say(f"compile_cache: {enable_compile_cache()}")
    comp = Compiles()
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    params, out, k0, v0 = phase_a(comp, cfg, args.seed)
    phase_b(comp, cfg, params, out, k0, v0)
    say(f"total: wall_s={time.perf_counter() - t0} "
        f"compile_s={comp.seconds} programs={comp.count} "
        f"persistent_cache_hits={comp.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
