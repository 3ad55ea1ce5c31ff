"""Program spans on the device trace's clock: what the host was doing in
each idle gap of the chip.

The served path opens ``repro.obs`` spans (``Recorder.span``, each a
``jax.profiler.TraceAnnotation``) at its layer boundaries inside
``ContinuousBatcher.step``; a running profiler records them on the host
plane beside the benchmark's own ``bench.*`` spans.  The window is
``bench.trace``'s, the extent of the ``bench.*`` spans alone.  Each idle
gap of the first chip is named by the innermost span, the program's or
the benchmark's, open at its midpoint (``<span> #<k>``, as
``bench.trace`` names them), and idle time is summed per span name and
per ``serve.step`` span.  On a trace with no program spans every gap
keeps ``bench.trace``'s name.

    python3 -m bench.spans TRACE [--compact OUT.json.gz]

reads a profiler trace (the directory of ``bench/run.py --trace 1
--keep-trace DIR``, an ``.xplane.pb`` or a compact ``.json.gz``) and
prints its breakdown as JSON; ``--compact`` also writes the trace cut to
what this reduction and ``bench.trace``'s read.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import gzip
import json
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as TR

__all__ = ["PROGRAM_PREFIXES", "Breakdown", "compact", "reduce",
           "host_spans", "innermost"]

#: name prefixes of the program's spans (``docs/observability.md``)
PROGRAM_PREFIXES = ("serve.", "pool.", "tier.", "tuner.")
STEP = "serve.step"
LAUNCH = "serve.macro.launch"
BENCH_STEP = TR.SPAN_PREFIX + "step"

Span = Tuple[float, float, str]          # (start_ns, end_ns, name)


def host_spans(pd, prefixes: Tuple[str, ...]) -> List[Span]:
    """Host-plane spans whose names start with one of ``prefixes``,
    sorted by start, an enclosing span before the spans it holds."""
    out = []
    for pl in pd.planes:
        if pl.name != TR.HOST_PLANE:
            continue
        for ln in pl.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in ln.events if e.name.startswith(prefixes))
    return sorted(out, key=lambda x: (x[0], -x[1]))


def compact(pd) -> Dict:
    """``bench.trace.compact`` plus the program's spans on the host
    plane; ``bench.trace.load`` reads it back."""
    planes = TR.compact(pd)
    for pl in pd.planes:
        if pl.name != TR.HOST_PLANE:
            continue
        for ln in pl.lines:
            ev = [[e.name, e.start_ns, e.duration_ns] for e in ln.events
                  if e.name.startswith(PROGRAM_PREFIXES)]
            if ev:
                line = planes.setdefault(pl.name, {}).setdefault(ln.name, [])
                line.extend(ev)
                line.sort(key=lambda x: x[1])
    return planes


def innermost(spans: Sequence[Span], points: Sequence[float]
              ) -> List[List[str]]:
    """For each point (ascending), the names of the spans open there,
    outermost first (the last is the innermost).  ``spans`` come from
    ``host_spans``; spans of one thread nest, so a stack sweep finds
    them."""
    out, stack, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append([name for s, e, name in stack if s <= t <= e])
    return out


@dataclasses.dataclass
class Step:
    """One ``serve.step`` span of the window."""
    at_s: float                          # start, from the window's start
    ms: float
    idle_ms: float                       # the chip's idle time inside it
    launched: bool                       # it launched a macro
    parts: List[Tuple[str, float]]       # spans inside it, longest first


@dataclasses.dataclass
class Breakdown:
    """The chip's idle time of one traced window, by span (seconds)."""
    window_s: float
    idle_s: float
    gap_list: List[Tuple[str, float]]    # (``<span> #<k>``, seconds)
    idle_by_span: Dict[str, float]       # by the innermost span's name
    idle_in_bench_steps_s: float         # idle inside ``bench.step`` spans
    named_in_bench_steps_s: float        # ... named below ``serve.step``
    steps: List[Step]

    def boundary_idle_ms(self) -> Optional[float]:
        """Median, over the ``serve.step`` spans that launched a macro,
        of the chip's idle milliseconds inside the span."""
        idle = [s.idle_ms for s in self.steps if s.launched]
        return statistics.median(idle) if idle else None

    def named_share(self) -> Optional[float]:
        """Share of the idle time inside ``bench.step`` spans that a
        program span below ``serve.step`` names."""
        if not self.idle_in_bench_steps_s:
            return None
        return self.named_in_bench_steps_s / self.idle_in_bench_steps_s

    def top_gaps(self, n: int) -> List[List]:
        return [[k, v] for k, v in sorted(self.gap_list,
                                          key=lambda kv: -kv[1])[:n]]

    def slow_steps(self, n: int) -> List[Step]:
        return sorted(self.steps, key=lambda s: -s.ms)[:n]

    def summary(self) -> Dict:
        return {"window_s": self.window_s, "idle_s": self.idle_s,
                "idle_in_bench_steps_s": self.idle_in_bench_steps_s,
                "named_share": self.named_share(),
                "boundary_idle_ms": self.boundary_idle_ms(),
                "idle_by_span": dict(sorted(self.idle_by_span.items(),
                                            key=lambda kv: -kv[1])),
                "idle_gaps": self.top_gaps(10),
                "slow_steps": [dataclasses.asdict(s)
                               for s in self.slow_steps(3)]}


def _idle_gaps(pd, lo: float, hi: float) -> List[TR.Interval]:
    """The idle intervals of the first chip in [lo, hi] (ns)."""
    chips = [pl for pl in pd.planes if pl.name.startswith(TR.DEVICE_PREFIX)]
    if not chips:
        raise ValueError("the trace holds no TPU plane")
    ops = next((ln for ln in chips[0].lines if ln.name == TR.OPS_LINE),
               None)
    if ops is None:
        raise ValueError(f"{chips[0].name} has no {TR.OPS_LINE!r} line")
    busy = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
            for e in ops.events]
    return TR.gaps(TR.union([(s, t) for s, t in busy if t > s]), lo, hi)


def _overlap(gaps: Sequence[TR.Interval], starts: Sequence[float],
             lo: float, hi: float) -> float:
    """Nanoseconds of the sorted, disjoint ``gaps`` inside [lo, hi]."""
    k = max(0, bisect.bisect_right(starts, lo) - 1)
    total = 0.0
    while k < len(gaps) and gaps[k][0] < hi:
        total += max(0.0, min(gaps[k][1], hi) - max(gaps[k][0], lo))
        k += 1
    return total


def reduce(pd) -> Breakdown:
    """Name the first chip's idle gaps by span and sum them per span and
    per ``serve.step`` (see the module docstring)."""
    bench = host_spans(pd, (TR.SPAN_PREFIX,))
    if not bench:
        raise ValueError("the trace holds no bench.* host spans")
    lo, hi = bench[0][0], max(e for _, e, _ in bench)
    spans = host_spans(pd, (TR.SPAN_PREFIX,) + PROGRAM_PREFIXES)
    gaps = _idle_gaps(pd, lo, hi)
    stacks = innermost(spans, [(s + e) / 2 for s, e in gaps])
    named, by_span = [], collections.Counter()
    in_bench_steps = named_in_steps = 0.0
    for (s, e), stack in zip(gaps, stacks):
        name = stack[-1] if stack else "no span"
        sec = (e - s) * 1e-9
        named.append((name, sec))
        by_span[name] += sec
        if BENCH_STEP in stack:
            in_bench_steps += sec
            if name.startswith(PROGRAM_PREFIXES) and name != STEP:
                named_in_steps += sec
    starts = [s for s, _ in gaps]
    span_starts = [s for s, _, _ in spans]
    steps = []
    for s, e, name in spans:
        if name != STEP or e <= lo or s >= hi:
            continue
        inside = spans[bisect.bisect_left(span_starts, s):
                       bisect.bisect_right(span_starts, e)]
        parts = sorted(((n, (pe - ps) * 1e-6) for ps, pe, n in inside
                        if pe <= e and n != STEP), key=lambda p: -p[1])
        steps.append(Step(at_s=(s - lo) * 1e-9, ms=(e - s) * 1e-6,
                          idle_ms=_overlap(gaps, starts, s, e) * 1e-6,
                          launched=any(n == LAUNCH for n, _ in parts),
                          parts=parts[:6]))
    return Breakdown(window_s=(hi - lo) * 1e-9,
                     idle_s=sum(e - s for s, e in gaps) * 1e-9,
                     gap_list=TR._merge_names(named),
                     idle_by_span=dict(by_span),
                     idle_in_bench_steps_s=in_bench_steps,
                     named_in_bench_steps_s=named_in_steps, steps=steps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--compact", default=None,
                    help="also write the compact trace (.json.gz) here")
    args = ap.parse_args(argv)
    pd = TR.load(args.trace)
    if args.compact:
        with open(args.compact, "wb") as f:
            f.write(gzip.compress(json.dumps(compact(pd)).encode(),
                                  mtime=0))
    print(json.dumps(reduce(pd).summary()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
