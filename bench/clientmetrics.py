"""Client-side metrics from the delivery log of one window.

Each request is logged with the second (from the window's start) it was
due and the second each of its tokens reached the client -- the return
of the ``step()`` that produced it.  Tails are taken over every request
of the window; a tail at quantile q needs at least 10 / (1 - q) samples
(ten beyond it), else it is refused.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence

import numpy as np

__all__ = ["Delivery", "TooFewSamples", "min_samples", "tail", "ttft_ms",
           "tpot_ms", "stall_ms", "output_tok_s"]


class TooFewSamples(ValueError):
    pass


@dataclasses.dataclass
class Delivery:
    due_s: float
    max_new: int
    prompt_len: int = 0
    times: List[float] = dataclasses.field(default_factory=list)

    def within(self, window_s: float) -> List[float]:
        return [t for t in self.times if t <= window_s]


def min_samples(q: float) -> int:
    return math.ceil(10.0 / (1.0 - q) - 1e-9)


def tail(values: Sequence[float], q: float) -> float:
    """The q-quantile (linear interpolation between order statistics)."""
    if len(values) < min_samples(q):
        raise TooFewSamples(f"a {q:g} quantile needs {min_samples(q)} "
                            f"samples, the window gave {len(values)}")
    return float(np.quantile(np.asarray(values, np.float64), q))


def ttft_ms(log: Sequence[Delivery], window_s: float, q: float) -> float:
    """Due time to first token, over every request due in the window; one
    still without a token enters with its wait to the window's end."""
    waits = []
    for r in log:
        if r.due_s >= window_s:
            continue
        first = r.times[0] if r.times and r.times[0] <= window_s \
            else window_s
        waits.append((first - r.due_s) * 1e3)
    return tail(waits, q)


def tpot_ms(log: Sequence[Delivery], window_s: float, q: float) -> float:
    """(last - first delivery) / (tokens - 1) over requests that finished
    in the window."""
    vals = [(r.times[-1] - r.times[0]) / (len(r.times) - 1) * 1e3
            for r in log
            if r.max_new >= 2 and len(r.within(window_s)) == r.max_new]
    return tail(vals, q)


def stall_ms(log: Sequence[Delivery], window_s: float, q: float) -> float:
    """Each request's longest gap between consecutive deliveries, over
    requests with two or more tokens delivered in the window."""
    vals = []
    for r in log:
        t = r.within(window_s)
        if len(t) >= 2:
            vals.append(float(np.max(np.diff(t))) * 1e3)
    return tail(vals, q)


def output_tok_s(log: Sequence[Delivery], window_s: float) -> float:
    """Every token delivered in the window over the window's seconds."""
    return sum(len(r.within(window_s)) for r in log) / window_s
