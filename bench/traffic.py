"""One generator for every traffic mix in ``bench/traffic/<mix>.json``.

A mix file holds parameters only:

* ``loop``: ``"closed"``: ``clients`` calls always in flight, a settled
  call replaced at once;
* ``prompt_mix``: ``[{"tokens": n, "count": k}, ...]``, one block of
  prompt lengths; blocks are shuffled and repeated.

Every seed gets the same work in another order: the same prompt lengths in
every block.  Token ids are uniform over the vocabulary.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    spec = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    if spec["loop"] != "closed":
        raise ValueError(f"traffic {name}: loop must be closed")
    return spec


def seed_seq(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per stream (window prompts, warm-up,
    sample), so drawing more of one never shifts another."""
    return np.random.default_rng([seed % (1 << 64), stream])


def lengths(spec: dict) -> list:
    return sorted({m["tokens"] for m in spec["prompt_mix"]})


class Prompts:
    """Prompts in shuffled blocks of the mix's lengths."""

    def __init__(self, spec: dict, vocab: int, rng: np.random.Generator):
        self.block = np.array([m["tokens"] for m in spec["prompt_mix"]
                               for _ in range(m["count"])])
        self.vocab = vocab
        self.rng = rng
        self._queue: list = []

    def next(self) -> np.ndarray:
        if not self._queue:
            self._queue = list(self.rng.permutation(self.block))
        n = int(self._queue.pop())
        return self.rng.integers(0, self.vocab, n, dtype=np.int32)

    def take(self, n: int) -> list:
        return [self.next() for _ in range(n)]
