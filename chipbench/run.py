#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the seeded weights on the device in one jitted call, builds
the program's ``ServeLoop`` and warms every shape the cell's traffic
uses; ``setup_s`` runs from process start to the first timed batch. The
window then drives ``ServeLoop.generate`` in a closed loop, batch after
batch of the cell's mix, until ``--seconds`` have passed. With
``--trace 1`` the window is traced and the cell's per-layer metrics are
read from the trace; otherwise its end-to-end metrics are reported. Every
run then checks the served tokens against the plain reference.

The last line of stdout is one JSON object. A run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse                                              # noqa: E402
import contextlib                                            # noqa: E402
import functools                                             # noqa: E402
import importlib                                             # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import statistics                                            # noqa: E402
import sys                                                   # noqa: E402
import tempfile                                              # noqa: E402
from dataclasses import dataclass                            # noqa: E402
from pathlib import Path                                     # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


@dataclass
class Batch:
    prompts: object        # (B, S0) int32, host
    served: object         # (B, n_new) int32, host
    seconds: float         # generate called -> tokens on the host


def require_chip(chips: int):
    """The device to measure on, or exit: no TPU, too few chips, or a
    device kind missing from the peaks table."""
    import jax
    from chipbench.spec import HERE, load_json
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chipbench: no TPU: JAX found {d.platform!r} "
                         f"({d.device_kind}); this benchmark runs only on "
                         "the chip")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    peaks = load_json(HERE / "peaks.json")
    if d.device_kind not in peaks:
        raise SystemExit(f"chipbench: device kind {d.device_kind!r} is not "
                         "in peaks.json")
    return d


def percentile(values, q):
    """The q-th percentile (0..100) of raw samples, linear between
    neighbours."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


@dataclass
class Served:
    """What set-up leaves for the window: the program's serving loop over
    the benchmark's seeded weights, and the family's reference and work
    counts."""
    cfg: object
    params: dict
    loop: object
    ref: object
    work: object
    weights_s: float


def set_up(cell, seed: int, t0: float = T0, warm: bool = True) -> Served:
    """Weights from the seed in one jitted call on the device, the
    program's ServeLoop, and one generate per prompt length of the mix so
    that every program the window runs is compiled (or loaded from the
    persistent cache) here."""
    import jax
    import numpy as np

    from chipbench import program
    from chipbench import traffic as tr
    from chipbench.reference.common import seed_key

    conf, mix = cell.conf, cell.traffic
    cfg = program.model_config(conf)
    ref = importlib.import_module(f"chipbench.reference.{conf['family']}")
    work = importlib.import_module(f"chipbench.work.{conf['family']}")
    params = jax.block_until_ready(jax.jit(
        functools.partial(ref.init_params, conf))(seed_key(seed)))
    program.check_layout(cfg, params)
    weights_s = time.perf_counter() - t0
    loop = program.serve_loop(cfg, params, mix["max_len"])
    rng = tr.rng_for(seed, 4)
    for S0 in tr.distinct_lengths(mix) if warm else ():
        np.asarray(loop.generate(
            tr.prompt_ids(rng, conf["vocab_size"], mix["batch"], S0),
            mix["new_tokens"]))
    return Served(cfg, params, loop, ref, work, weights_s)


def drive(loop, cell, seed: int, seconds: float, min_requests: int = 0):
    """The closed loop: batch after batch of the mix, each timed from the
    call to generate until its tokens are on the host, started until
    ``seconds`` have passed and ``min_requests`` requests have finished
    (and at least one).
    Returns (batches, window seconds)."""
    import numpy as np

    from chipbench import tracing
    from chipbench import traffic as tr

    mix, vocab = cell.traffic, cell.conf["vocab_size"]
    B, n_new = mix["batch"], mix["new_tokens"]
    rng, lengths = tr.rng_for(seed, 2), tr.lengths(mix, seed)
    batches = []
    start = time.perf_counter()
    while (not batches or time.perf_counter() - start < seconds
           or B * len(batches) < min_requests):
        with tracing.annotate("prepare"):
            ids = tr.prompt_ids(rng, vocab, B, next(lengths))
        t = time.perf_counter()
        with tracing.annotate("generate"):
            out = loop.generate(ids, n_new)
        with tracing.annotate("fetch"):
            served = np.asarray(out)
        batches.append(Batch(ids, served, time.perf_counter() - t))
    return batches, time.perf_counter() - start


def malformed(batches, cell) -> int:
    """Requests that came back without exactly ``new_tokens`` ids in the
    vocabulary."""
    B, n_new = cell.traffic["batch"], cell.traffic["new_tokens"]
    vocab = cell.conf["vocab_size"]
    bad = 0
    for b in batches:
        if b.served.shape != (B, n_new):
            bad += B
        else:
            bad += int(((b.served < 0) | (b.served >= vocab)).any(1).sum())
    return bad


def requests_of(batches) -> list:
    return [(b.prompts[i], b.served[i]) for b in batches
            for i in range(len(b.prompts))]


def run_cell(cell, seed: int, seconds: float, trace: bool, device,
             t0: float = T0) -> dict:
    """Set-up, window, metrics and the check of one run; returns the
    result object. ``device`` is the chip that ``require_chip`` found
    (tests hand in another)."""
    from chipbench import judge, program, tracing
    from chipbench.compiles import Compiles
    from chipbench.spec import HERE, load_json, load_module

    comp = Compiles()
    cache_dir = program.enable_compile_cache()
    sv = set_up(cell, seed, t0)
    compiles_setup = (comp.count, comp.seconds, comp.cache_hits)
    B, n_new = cell.traffic["batch"], cell.traffic["new_tokens"]

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    with (tracing.record(log_dir) if trace else contextlib.nullcontext()):
        setup_s = time.perf_counter() - t0
        batches, window_s = drive(sv.loop, cell, seed, seconds)
    window_compiles = comp.count - compiles_setup[0]
    mem = device.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use", 0)
    sv.loop = None                       # the program's state goes here

    attempted, failed = B * len(batches), malformed(batches, cell)
    say(f"setup: {setup_s} s (weights {sv.weights_s} s); compile cache "
        f"{cache_dir}; compiles in set-up {compiles_setup[0]} "
        f"({compiles_setup[1]} s, {compiles_setup[2]} cache hits)")
    say(f"window: {window_s} s, {len(batches)} batches, {attempted} "
        f"requests, compiles in the window {window_compiles}")

    result = {"correct": None, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": {
                  "platform": device.platform, "kind": device.device_kind,
                  "count": cell.chips, "memory_peak_bytes": int(peak)}}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        latencies = [b.seconds for b in batches for _ in range(B)]
        say(f"ttft samples: {len(latencies)}; median "
            f"{statistics.median(latencies) * 1e3} ms")
        e2e = {
            "setup_s": setup_s,
            "gen_tokens_per_s": attempted * n_new / window_s,
            "prompt_tokens_per_s":
                sum(b.prompts.size for b in batches) / window_s,
            "ttft_p95_ms": percentile(latencies, 95) * 1e3,
        }
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": units[m["name"]]}
    else:
        reduced = tracing.reduce(tracing.load(tracing.find_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = Context(conf=cell.conf, work=sv.work,
                      trace=reduced,
                      peaks=load_json(HERE / "peaks.json")[device.device_kind],
                      batches=[(B, b.prompts.shape[1], n_new)
                               for b in batches])
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": units[m["name"]]}
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        top = sorted(reduced.ops.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[label, s] for s, label in reduced.gaps[:10]]}

    # the check: after the window, with the program's state freed
    check = cell.check
    picked = judge.sample(requests_of(batches), check["requests"], seed)
    t = time.perf_counter()
    gaps = judge.gaps(sv.ref, cell.conf, sv.params, picked,
                      check["block_tokens"])
    say(f"reference: {len(picked)} requests, {gaps.size} served tokens, "
        f"{time.perf_counter() - t} s")
    numbers = judge.compared(check, gaps, failed)
    result["correct"] = judge.passes(numbers)
    for name, n in numbers.items():
        say(f"compared: {name} {n['value']} limit {n['limit']}")
    result["compared"] = numbers
    return result


@dataclass
class Context:
    """What a per-layer metric's reader gets: the cell's configuration, the
    work counts of its family, the reduced trace, the chip's peaks, and
    the window's batches as (batch, prompt length, new tokens)."""
    conf: dict
    work: object
    trace: object
    peaks: dict
    batches: list


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench.spec import resolve
    cell = resolve(args.workload)
    device = require_chip(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
