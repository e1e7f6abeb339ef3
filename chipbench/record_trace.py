#!/usr/bin/env python3
"""A trace of the harness's closed loop, recorded on the chip, and what
it shows of the serving loop's host path.

    python3 chipbench/record_trace.py --out <file>.xplane.pb.gz [--seed N]
    python3 chipbench/record_trace.py --out <file>.xplane.pb.gz \
        --workload <cell> --seconds <s> [--seed N]

Without ``--workload``: the small trace of the trace tests
(``chipbench/tests/data/``), mamba2-130m at its published sizes, batches
of 32 prompts of 256 tokens and 8 new tokens, two batches after the
warm-up. With it: the cell's own traffic for ``--seconds``. Either way
the window is traced with the harness's own ``tracing.record``, the
``.xplane.pb`` is written gzipped, and ``serve_spans.summary`` of it
(idle time by host label, ``serve_idle_share``, ``prefill_wait_ms``,
idle gaps of 30 ms or more) goes to stdout as one JSON line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CELL = "mamba2-130m.chat-gen"
TRAFFIC = {"batch": 32, "prompt_tokens": [[256, 1]], "new_tokens": 8,
           "max_len": 264}
BATCHES = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import program, run, serve_spans, tracing
    from chipbench.spec import resolve

    if args.workload:
        cell, min_requests = resolve(args.workload), 0
    else:
        cell = resolve(CELL)
        cell = dataclasses.replace(cell,
                                   traffic=dict(cell.traffic, **TRAFFIC))
        min_requests = BATCHES * TRAFFIC["batch"]
    run.require_chip(cell.chips)
    program.enable_compile_cache()
    sv = run.set_up(cell, args.seed)
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    with tracing.record(log_dir):
        batches, _ = run.drive(sv.loop, cell, args.seed, args.seconds,
                               min_requests)
    Path(args.out).write_bytes(
        gzip.compress(tracing.find_xplane(log_dir).read_bytes()))
    shutil.rmtree(log_dir, ignore_errors=True)
    trace = serve_spans.reduce(tracing.load(args.out))
    shapes = [(b.prompts.shape[0], b.prompts.shape[1],
               cell.traffic["new_tokens"]) for b in batches]
    print(json.dumps(dict(serve_spans.summary(trace, shapes),
                          workload=cell.name, batches=len(batches))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
