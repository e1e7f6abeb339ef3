"""What decides ``correct``: the served tokens against the plain
reference.

Once the window has closed, a sample of the finished requests drawn from
the seed, the longest among them, is run through the reference over its
prompt and served tokens. For each served token the gap is the
reference's best logit at that position minus the reference's logit of
the served token: 0 when the program picked the reference's argmax, and
small when rounding flipped a near tie. ``checks/<cell>.json`` names the
numbers of the gaps that decide (the widest, ``max_logit_gap``, and the
mean, ``mean_logit_gap``), each with its limit and the readings it was
set from.
"""

from __future__ import annotations

import functools
import json
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic import rng_for


def sample(requests: list, k: int, seed: int) -> list:
    """``k`` of ``requests`` (each (prompt, served)), drawn from the seed,
    with one of the longest always among them."""
    size = [len(p) + len(s) for p, s in requests]
    longest = [i for i, n in enumerate(size) if n == max(size)]
    rng = rng_for(seed, 3)
    first = int(rng.choice(longest))
    rest = [i for i in rng.permutation(len(requests)) if i != first]
    return [requests[i] for i in [first] + rest[:k - 1]]


@functools.lru_cache(maxsize=None)
def _gap_fn(ref, conf_json: str, start: int, lower: bool):
    conf = json.loads(conf_json)

    def gaps(params, tokens, served):
        lg = ref.logits(conf, params, tokens, start)
        best = lg.max(-1)
        if lower:        # the control's own choice, read on the reference
            served = jnp.argmax(ref.logits(conf, params, tokens, start,
                                           lower=True), -1)
        return best - jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
    return jax.jit(gaps)


def gaps(ref, conf: dict, params, requests: list, block_tokens: int,
         lower: bool = False) -> np.ndarray:
    """Gap of every served token of ``requests`` under the reference;
    with ``lower`` the gap of the token the float8 control puts first
    instead. Requests of one prompt length run together, in blocks of at
    most ``block_tokens`` positions."""
    groups = defaultdict(list)
    for p, s in requests:
        groups[(len(p), len(s))].append((p, s))
    out = []
    for (S0, n), reqs in sorted(groups.items()):
        fn = _gap_fn(ref, json.dumps(conf, sort_keys=True), S0 - 1, lower)
        rows = max(1, block_tokens // (S0 + n - 1))
        for i in range(0, len(reqs), rows):
            blk = reqs[i:i + rows]
            tokens = np.stack([np.concatenate([p, s[:-1]]) for p, s in blk])
            served = np.stack([s for _, s in blk])
            out.append(np.asarray(fn(params, jnp.asarray(tokens),
                                     jnp.asarray(served))).ravel())
    return np.concatenate(out)


NUMBERS = {"max_logit_gap": np.max, "mean_logit_gap": np.mean}


def compared(check: dict, gaps: np.ndarray, failed: int) -> dict:
    """Each number the cell compares, beside its limit; requests that came
    back malformed are compared with 0."""
    out = {name: {"value": float(NUMBERS[name](gaps)),
                  "limit": spec["limit"]}
           for name, spec in check["compare"].items()}
    out["failed_requests"] = {"value": failed, "limit": 0}
    return out


def passes(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())
