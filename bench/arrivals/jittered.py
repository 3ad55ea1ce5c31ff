"""Open loop at a steady rate with bounded bursts: request i is due at
(i + jitter * u_i) / rate, u_i uniform on [0, 1), so at most
1 + jitter requests fall due in any 1 / rate seconds.  With jitter at
most 1 every one of the floor(rate * seconds) requests is due inside the
window, so every seed serves the same number."""
from __future__ import annotations

import math

import numpy as np


class Process:
    def __init__(self, spec, rate: float, seconds: float,
                 rng: np.random.Generator):
        if rate <= 0:
            raise ValueError(f"rate must be positive: {rate}")
        n = max(1, math.floor(rate * seconds))
        due = np.sort((np.arange(n) + float(spec["jitter"])
                       * rng.random(n)) / rate)
        self.due = due[due < seconds]
        self.n = len(self.due)
        self._next = 0

    def release(self, now: float, in_system: int):
        j = int(np.searchsorted(self.due, now, side="right"))
        out = [float(t) for t in self.due[self._next:j]]
        self._next = max(self._next, j)
        return out

    def wake(self, now: float) -> float:
        return (float(self.due[self._next]) if self._next < self.n
                else math.inf)
