"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) names its arrival process
(``bench/arrivals/<kind>.py``) and the length distributions of prompts
and answers (``bench/lengths/<dist>.py``); a cell
(``bench/cells/<cell>.json``) gives the offered rate.  Every seed gets
the same multiset of prompt and output lengths -- stratified quantiles
of the mix's distributions, so the work in a window does not depend on
the seed -- in an order, with arrival jitter and token ids, drawn from
the seed.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Tuple

import numpy as np

__all__ = ["Request", "lengths", "generate"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # int32 token ids
    max_new: int


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles (k + 1/2) / n: the same
    multiset for every seed."""
    dist = importlib.import_module(f"bench.lengths.{spec['dist']}")
    x = dist.quantile(spec, (np.arange(n) + 0.5) / n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def generate(mix: Dict, rate: float, seconds: float, seed: int,
             vocab: int) -> Tuple[List[Request], object]:
    """The requests a window of ``seconds`` at ``rate`` may release, in
    release order, and the arrival process that releases them."""
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 0])
    kind = importlib.import_module(
        f"bench.arrivals.{mix['arrivals']['kind']}")
    proc = kind.Process(mix["arrivals"], rate, seconds, rng)
    plens = rng.permutation(lengths(mix["prompt"], proc.n))
    outs = rng.permutation(lengths(mix["output"], proc.n))
    return [Request(rid=i, prompt=rng.integers(0, vocab, int(plens[i]),
                                               dtype=np.int32),
                    max_new=int(outs[i]))
            for i in range(proc.n)], proc
