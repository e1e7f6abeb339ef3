"""The one traffic generator: closed-loop batches from a mix file.

A mix names its batch size, its prompt lengths as ``[length, count]``
pairs over one cycle of batches, the tokens to generate and the cache
length. Every seed serves the same multiset of lengths in each cycle, in
an order of its own, so the seed changes which ids are sent and in what
order, not how much work a window holds.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A host generator for one purpose (``stream``) of a run."""
    return np.random.default_rng([stream, int(seed)])


def lengths(traffic: dict, seed: int) -> Iterator[int]:
    """Prompt length of each batch, cycle after cycle."""
    cycle = [int(n) for n, count in traffic["prompt_tokens"]
             for _ in range(int(count))]
    rng = rng_for(seed, 1)
    while True:
        yield from (cycle[i] for i in rng.permutation(len(cycle)))


def distinct_lengths(traffic: dict) -> list:
    return sorted({int(n) for n, _ in traffic["prompt_tokens"]})


def prompt_ids(rng: np.random.Generator, vocab: int, batch: int,
               length: int) -> np.ndarray:
    return rng.integers(0, vocab, (batch, length), dtype=np.int32)
