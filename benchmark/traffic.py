"""The one traffic generator. A mix is a data file under
``benchmark/traffic/``; this module turns it and ``--seed`` into inputs.
The program sees only what comes out of here.

Serving mixes (closed loops of ``clients``): the mix's file states the
two length distributions; their ``distinct_sizes`` stratified quantiles
are the set of prompt lengths and the set of output lengths that every
seed sends. The seed pairs a prompt length with an output length, puts
the pairs in an order of its own, and draws every token id. So every
seed offers the same amount of work, in another order.

Training mixes: batches of ``batch_size`` rows of ``seq_len`` + 1 ids,
one per step, each from (seed, step).
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped log-normal length."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    q = (np.arange(n) + 0.5) / n
    z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def size_table(mix: dict, seed: int) -> list[tuple[int, int]]:
    """The (prompt, output) sizes of one seed, in its order: the mix's
    sets of lengths, paired and ordered by the seed."""
    n = mix["distinct_sizes"]
    rng = np.random.default_rng([int(seed), 0])
    prompts = lengths(mix["prompt_tokens"], n)[rng.permutation(n)]
    outputs = lengths(mix["output_tokens"], n)[rng.permutation(n)]
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


@dataclasses.dataclass
class Request:
    ids: np.ndarray  # int32 prompt
    max_new: int


class RequestStream:
    """Endless requests of a serving mix for one seed: its table of
    sizes, walked round and round."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed), 1])
        self.table = size_table(mix, seed)
        self._next = 0

    def __iter__(self):
        return self

    def __next__(self) -> Request:
        p, o = self.table[self._next % len(self.table)]
        self._next += 1
        ids = self.rng.integers(0, self.vocab, (p,)).astype(np.int32)
        return Request(ids=ids, max_new=o)


def train_batch(mix: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """[batch_size, seq_len + 1] int32 ids of one step; every row
    differs. Inputs are [:, :-1], labels [:, 1:]."""
    rng = np.random.default_rng([int(seed), 3, int(step)])
    return rng.integers(
        0, vocab, (mix["batch_size"], mix["seq_len"] + 1)
    ).astype(np.int32)
