"""Cells of BENCHMARK.json cut to CPU test sizes: the repository's smoke
widths for the model and a few short batches of traffic."""

from __future__ import annotations

import dataclasses
import functools

from chipbench.spec import ROOT, load_json, resolve

CELLS = [w["name"] for w in load_json(ROOT / "BENCHMARK.json")["workloads"]]


def smoke_traffic(mix: dict) -> dict:
    """The mix cut to four requests a batch, one or two short prompt
    lengths and at most eight new tokens."""
    n = min(len(mix["prompt_tokens"]), 2)
    lengths = [[16 * (i + 1), 1] for i in range(n)]
    new = min(mix["new_tokens"], 8)
    return dict(mix, batch=4, prompt_tokens=lengths, new_tokens=new,
                max_len=lengths[-1][0] + new)


def smoke_conf(conf: dict) -> dict:
    from repro.configs import get_smoke_config
    cfg = get_smoke_config(conf["arch_id"])
    out = dict(conf, n_layers=cfg.n_layers, d_model=cfg.d_model,
               vocab_size=cfg.vocab_size, use_pallas=False)
    if conf["family"] == "dense":
        out.update(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                   head_dim=cfg.hd, d_ff=cfg.d_ff)
    else:
        out["ssm"] = dataclasses.asdict(cfg.ssm)
    return out


@functools.lru_cache(maxsize=None)
def _cell(name):
    return resolve(name)


def smoke_cell(name: str):
    """The cell at smoke size, judged by its own numbers and limits on a
    sample of four requests."""
    cell = _cell(name)
    return dataclasses.replace(
        cell, conf=smoke_conf(cell.conf),
        traffic=smoke_traffic(cell.traffic),
        check=dict(cell.check, requests=4))
