"""What decides ``correct``: served tokens against the float32 reference,
and the shared ``serve/stats`` vector against the tokens the calls returned.

Two numbers are compared, each against its limit in the configuration
file (``limits``):

* ``token_gap``: over a sample of served calls drawn from the seed (the
  longest prompts always in it), the widest gap by which the logit of the
  token a call returned lies below the reference's best logit at that
  position.  Greedy serving at full precision gives about zero; a wrong
  token, or logits computed too coarsely, give a gap of the size of the
  logits' spread.
* ``stats_miscount``: the ``serve/stats`` vector read back from the
  global tier against the count of each token among every served call's
  answer (warm-up included): the sum over the vocabulary of the absolute
  difference, as a share of the calls.  Each call adds one at its token and
  pushes the delta over the int8 wire, which carries a one-hot delta
  exactly.  ``serve/stats`` is a HOGWILD vector (``VectorAsync``, "eventual
  consistency ... as tolerated by SGD"): co-located executors add to one
  shared replica without a lock, so a race may drop or repeat an increment
  and sound runs read a small share.  A call that pushes nothing reads 1; an
  answer altered after it was counted reads 2.
"""
from __future__ import annotations

import importlib

import numpy as np

SAMPLE = 200          # served tokens compared with the reference per run
REF_BATCH = 8         # prompts of one length per reference call


def reference(cfg: dict):
    return importlib.import_module(f"bench.reference.{cfg['reference']}")


def sample(records, rng: np.random.Generator, n: int = SAMPLE) -> list:
    """``n`` served records drawn from ``rng``, with at least one of the
    longest prompts among them."""
    served = [r for r in records if r.rc == 0]
    if len(served) <= n:
        return served
    idx = rng.choice(len(served), n, replace=False)
    picked = [served[i] for i in idx]
    longest = max(r.length for r in served)
    if not any(r.length == longest for r in picked):
        picked[0] = next(r for r in served if r.length == longest)
    return picked


def last_logits(ref, params, cfg: dict, prompts: list, mode: str = "f32"):
    """Reference logits at the last position of each prompt, computed in
    batches of one length (the last batch padded by repetition, so every
    call of a length has one shape)."""
    out = [None] * len(prompts)
    by_len: dict = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(len(p), []).append(i)
    for idx in by_len.values():
        for lo in range(0, len(idx), REF_BATCH):
            part = idx[lo:lo + REF_BATCH]
            rows = part + [part[-1]] * (REF_BATCH - len(part))
            logits = ref.last_logits(params, cfg,
                                     np.stack([prompts[i] for i in rows]),
                                     mode=mode)
            for j, i in enumerate(part):
                out[i] = logits[j]
    return np.stack(out)


def widest_gap(ref_logits: np.ndarray, tokens) -> float:
    """max over rows of (best logit - logit of the given token)."""
    tokens = np.asarray(tokens)
    if tokens.min() < 0 or tokens.max() >= ref_logits.shape[-1]:
        return float("inf")
    rows = np.arange(len(tokens))
    return float(np.max(ref_logits.max(-1) - ref_logits[rows, tokens]))


def stats_miscount(stats: np.ndarray, tokens, vocab: int) -> float:
    """sum(|stats - counts|) / calls, over the ``vocab`` entries."""
    tokens = np.asarray(tokens, np.int64)
    if not len(tokens):
        return 0.0
    if tokens.min() < 0 or tokens.max() >= vocab:
        return float("inf")
    counts = np.bincount(tokens, minlength=vocab)
    diff = stats[:vocab].astype(np.float64) - counts
    return float(np.abs(diff).sum() / len(tokens))
