"""The comparison that decides ``correct``.

After the window, a sample of the requests the server finished -- the
longest among them, the rest drawn from the seed -- is run once through
the configuration's plain reference over prompt + served tokens.  For
each served token, the gap by which the reference's logit of that token
lies below the reference's best logit at that position is read; the
number compared is the widest gap.  Greedy decoding makes the gap 0 up
to rounding: a served token is wrong by exactly how much worse the
reference thinks it is.

The control reads the same gap for the token the reference computed at
a lower precision puts first, at each position of the same sequences:
float8 products (the limit's control), or keys and values stored in
bfloat16 (a reading of what a bfloat16 page pool would do).
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

__all__ = ["Finished", "sample", "served_gap", "control_gap"]

#: served tokens the sample holds at least (with the longest request)
SAMPLE_TOKENS = 768

Finished = Tuple[np.ndarray, List[int]]       # (prompt, served tokens)


def sample(finished: Sequence[Finished], seed: int,
           min_tokens: int = SAMPLE_TOKENS) -> List[Finished]:
    """The longest finished request plus others drawn from ``seed`` until
    the sample holds ``min_tokens`` served tokens (or every request)."""
    if not finished:
        return []
    order = list(np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
                 .permutation(len(finished)))
    longest = max(range(len(finished)),
                  key=lambda i: (len(finished[i][0]) + len(finished[i][1]),
                                 -i))
    order.remove(longest)
    picks, n = [finished[longest]], len(finished[longest][1])
    for i in order:
        if n >= min_tokens:
            break
        picks.append(finished[i])
        n += len(finished[i][1])
    return picks


def _ref(conf: Dict):
    return importlib.import_module(f"bench.reference.{conf['reference']}")


def _reference_logits(conf, seed, picks, precision):
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t[:-1], np.int32)]) for p, t in picks]
    starts = [len(p) - 1 for p, _ in picks]
    return _ref(conf).served_logits(conf, seed, seqs, starts, precision)


def served_gap(conf: Dict, seed: int, picks: Sequence[Finished]
               ) -> Tuple[float, int]:
    """(widest gap of a served token below the reference's best, tokens
    compared)."""
    worst, n = 0.0, 0
    for (_, toks), lg in zip(picks, _reference_logits(conf, seed, picks,
                                                      "f32")):
        t = np.asarray(toks, np.int64)
        gap = lg.max(axis=-1) - lg[np.arange(len(t)), t]
        worst, n = max(worst, float(gap.max())), n + len(t)
    return worst, n


def control_gap(conf: Dict, seed: int, picks: Sequence[Finished],
                precision: str = "fp8") -> Tuple[float, int]:
    """The same reading for the tokens the reference at ``precision``
    puts first."""
    ref = _reference_logits(conf, seed, picks, "f32")
    low = _reference_logits(conf, seed, picks, precision)
    worst, n = 0.0, 0
    for r, lo in zip(ref, low):
        t = lo.argmax(axis=-1)
        gap = r.max(axis=-1) - r[np.arange(len(t)), t]
        worst, n = max(worst, float(gap.max())), n + len(t)
    return worst, n
