#!/usr/bin/env python3
"""Readings that a cell's limit on ``max_logit_gap`` is set from, on the
chip at the cell's own sizes.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1-12 \
        --control-seeds 1-3

For each seed: the seeded weights, the program's ServeLoop, batches of the
cell's mix until as many requests have finished as a run compares (and a
whole cycle of prompt lengths), and the same seeded sample a run takes.
The program's numbers (the widest and the mean gap) are the lower
readings' raw material; on the control seeds the float8 reference, put in
the program's place, reads the gap of the token it would pick at each
position of the same prompts and tokens. One JSON line per seed goes to stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def seeds(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def readings(gaps) -> dict:
    """Every number a check can compare, and the tokens off the
    reference's argmax."""
    from chipbench.judge import NUMBERS
    out = {name: float(fn(gaps)) for name, fn in NUMBERS.items()}
    out["tokens_off_argmax"] = int((gaps > 0).sum())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import judge, program, run
    from chipbench.spec import resolve

    cell = resolve(args.workload)
    run.require_chip(cell.chips)
    program.enable_compile_cache()
    mix, check = cell.traffic, cell.check
    cycle = mix["batch"] * sum(int(c) for _, c in mix["prompt_tokens"])
    for seed in args.seeds:
        t = time.perf_counter()
        sv = run.set_up(cell, seed, t, warm=False)
        batches, _ = run.drive(sv.loop, cell, seed, 0.0,
                               max(check["requests"], cycle))
        sv.loop = None
        picked = judge.sample(run.requests_of(batches), check["requests"],
                              seed)
        t_ref = time.perf_counter()
        gaps = judge.gaps(sv.ref, cell.conf, sv.params, picked,
                          check["block_tokens"])
        line = {"workload": cell.name, "seed": seed,
                "requests": len(picked), "tokens": int(gaps.size),
                "reference_s": time.perf_counter() - t_ref,
                "program": readings(gaps)}
        if seed in args.control_seeds:
            line["control"] = readings(judge.gaps(
                sv.ref, cell.conf, sv.params, picked,
                check["block_tokens"], lower=True))
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
        del sv
    return 0


if __name__ == "__main__":
    sys.exit(main())
