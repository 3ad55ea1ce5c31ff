"""Reduction of one profiler trace to the numbers the per-layer metrics
read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU is a plane ``/device:TPU:<i>`` whose line ``XLA Ops`` holds one
event per operation run on the device and whose line ``XLA Modules``
holds one event per program launch (named after the jitted function).
The host plane ``/host:CPU`` holds the benchmark's own spans
(``bench.*``, from ``jax.profiler.TraceAnnotation``) on the Python
thread's line.  Device busy time is the union of the operation intervals;
an idle gap is named by the host span open at its midpoint.  Device and
host events share one clock.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import pathlib
from typing import Dict, List, Sequence, Tuple

__all__ = ["Trace", "load", "reduce", "union", "gaps", "SPAN_PREFIX"]

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]          # (start_ns, end_ns)


def union(iv: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Trace:
    """What one traced window reduces to (seconds; per chip averages)."""
    window_s: float
    busy_s: float
    op_s: Dict[str, float]            # device time per operation name
    module_s: Dict[str, float]        # device time per program name
    module_n: Dict[str, int]          # launches per program name
    gap_list: List[Tuple[str, float]]  # (host span at the gap, seconds)

    def op_time(self, op: str) -> float:
        """Seconds of the ops named ``op`` (numeric suffix aside), in any
        program."""
        return sum(v for k, v in self.op_s.items()
                   if base_name(k.rsplit("/", 1)[-1]) == op)

    def program_time(self, program: str) -> Tuple[float, int]:
        """(seconds, launches) of the programs named ``program`` (the
        jitted function's name; ``jit_`` prefix and hash aside)."""
        keys = [k for k in self.module_s
                if k == program or k == f"jit_{program}"]
        return (sum(self.module_s[k] for k in keys),
                sum(self.module_n[k] for k in keys))

    def top_ops(self, n: int) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> List[List]:
        return [[k, v] for k, v in sorted(self.gap_list,
                                          key=lambda kv: -kv[1])[:n]]


def load(path: pathlib.Path):
    """The newest ``.xplane.pb`` under ``path``, or ``path`` itself (also
    gzipped, ``.xplane.pb.gz``, or ``compact``'s ``.json.gz``)."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.name.endswith(".json.gz"):
        return from_compact(json.loads(gzip.decompress(path.read_bytes())))
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    if path.is_file():
        return ProfileData.from_file(str(path))
    files = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return ProfileData.from_file(str(files[-1]))


class _Event:
    __slots__ = ("name", "start_ns", "duration_ns")

    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, \
            duration_ns


class _Node:
    def __init__(self, name, children, attr):
        self.name = name
        setattr(self, attr, children)


def compact(pd) -> Dict:
    """The part of a trace the reduction reads, as plain JSON: the TPU
    planes' op and program lines (op events cut to their own name) and
    the host's ``bench.*`` spans.  ``from_compact`` reads it back."""
    planes = {}
    for pl in pd.planes:
        lines = {}
        for ln in pl.lines:
            if pl.name.startswith(DEVICE_PREFIX) and ln.name in (
                    OPS_LINE, MODULES_LINE):
                cut = (lambda n: f"%{op_name(n)} = ") if ln.name == OPS_LINE \
                    else (lambda n: n)
                lines[ln.name] = [[cut(e.name), e.start_ns, e.duration_ns]
                                  for e in ln.events]
            elif pl.name == HOST_PLANE:
                ev = [[e.name, e.start_ns, e.duration_ns] for e in ln.events
                      if e.name.startswith(SPAN_PREFIX)]
                if ev:
                    lines[ln.name] = ev
        if lines:
            planes[pl.name] = lines
    return planes


def from_compact(planes: Dict):
    """A trace object ``reduce`` reads, from ``compact``'s JSON."""
    return _Node("trace", [
        _Node(pname, [_Node(lname, [_Event(*e) for e in evs], "events")
                      for lname, evs in lines.items()], "lines")
        for pname, lines in planes.items()], "planes")


def _spans(pd) -> List[Tuple[float, float, str]]:
    out = []
    for pl in pd.planes:
        if pl.name != HOST_PLANE:
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return sorted(out)


def op_name(text: str) -> str:
    """An op event's own name: ``%paged_attention.5 = (...) custom-call(...)``
    gives ``paged_attention.5``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """A name without its numeric suffix or hash: ``paged_attention.5`` and
    ``jit_prefill_batched(1234)`` give ``paged_attention`` and
    ``jit_prefill_batched``."""
    name = name.split("(", 1)[0]
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _exclusive(events: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Time of each event not covered by an event nested inside it (the
    ops line nests a loop's body ops inside the loop), summed by name."""
    out: Dict[str, float] = collections.Counter()
    stack: List[Tuple[float, str]] = []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[name] += e - s
        if stack and e <= stack[-1][0]:
            out[stack[-1][1]] -= e - s
        stack.append((e, name))
    return out


def reduce(pd) -> Trace:
    """Reduce a trace to busy, per-op and per-program device time, and
    idle gaps named by the benchmark's host spans.  The window is the
    extent of the host spans; device numbers are averaged over chips.
    An op is keyed ``<program>/<op>`` (the program launch it ran in) and
    timed exclusive of the ops nested in it."""
    spans = _spans(pd)
    if not spans:
        raise ValueError("the trace holds no bench.* host spans")
    lo, hi = spans[0][0], max(e for _, e, _ in spans)
    chips = [pl for pl in pd.planes if pl.name.startswith(DEVICE_PREFIX)]
    if not chips:
        raise ValueError("the trace holds no TPU plane: "
                         + ", ".join(pl.name for pl in pd.planes))
    op_s: Dict[str, float] = collections.Counter()
    module_s: Dict[str, float] = collections.Counter()
    module_n: Dict[str, int] = collections.Counter()
    busy_s = 0.0
    gap_list: List[Tuple[str, float]] = []
    for pl in chips:
        lines = {ln.name: ln for ln in pl.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{pl.name} has no {OPS_LINE!r} line: "
                             f"{sorted(lines)}")
        mods = []
        for e in (lines[MODULES_LINE].events if MODULES_LINE in lines
                  else ()):
            s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if t > s:
                name = base_name(e.name)
                mods.append((s, t, name))
                module_s[name] += (t - s) * 1e-9 / len(chips)
                module_n[name] += 1
        mods.sort()
        starts = [m[0] for m in mods]
        ops = []
        for e in lines[OPS_LINE].events:
            s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if t > s:
                k = bisect.bisect_right(starts, s) - 1
                prog = mods[k][2] if k >= 0 and s < mods[k][1] else "?"
                ops.append((s, t, f"{prog}/{op_name(e.name)}"))
        for name, ns in _exclusive(ops).items():
            op_s[name] += ns * 1e-9 / len(chips)
        merged = union([(s, t) for s, t, _ in ops])
        busy_s += sum(e - s for s, e in merged) * 1e-9 / len(chips)
        if pl is chips[0]:
            for s, e in gaps(merged, lo, hi):
                gap_list.append((_span_at(spans, (s + e) / 2), (e - s) * 1e-9))
    return Trace(window_s=(hi - lo) * 1e-9, busy_s=busy_s, op_s=dict(op_s),
                 module_s=dict(module_s), module_n=dict(module_n),
                 gap_list=_merge_names(gap_list))


def _span_at(spans, t: float) -> str:
    best = "no span"
    for s, e, name in spans:
        if s <= t <= e:
            best = name
        elif s > t:
            break
    return best


def _merge_names(gl: List[Tuple[str, float]]) -> List[Tuple[str, float]]:
    """Keep each gap, named ``<span> #<k>`` in time order, so the longest
    stay distinct in the breakdown."""
    seen: Dict[str, int] = collections.Counter()
    out = []
    for name, sec in gl:
        seen[name] += 1
        out.append((f"{name} #{seen[name]}", sec))
    return out
