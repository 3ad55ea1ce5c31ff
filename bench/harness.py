"""One run of one cell: set up, warm up, measure a window, check it.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration file, a traffic mix (``bench/traffic/<mix>.json``) and has
a file of its own (``bench/cells/<cell>.json``: the offered rate and the
limits of the correctness check).  Every metric, end-to-end and
per-layer, is a reader in ``bench/metrics/<metric>.py``; a mix's arrival
process and length distributions are modules under ``bench/arrivals/``
and ``bench/lengths/``; a configuration's ``reference`` names its
architecture (``bench/arch/<name>.py``) and its plain reference
(``bench/reference/<name>.py``).  Everything is found by name: a new
cell, mix, configuration, architecture or metric is new files plus
entries.

The window drives the program's served path as users get it:
``ContinuousBatcher.submit`` / ``.step`` in its default synchronous
macro loop over ``SharedPagedPools``, ``TieringManager`` and
``OnlineTuner`` behind a ``TrafficMonitor``.  Requests are submitted
when the mix's arrival process releases them, and each token is timed
at the return of the ``step()`` that delivered it.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib
import json
import pathlib
import shutil
import sys
import time
from typing import Dict, List

import numpy as np

from bench import arch

ROOT = pathlib.Path(__file__).resolve().parents[1]

__all__ = ["ROOT", "Cell", "load", "run", "NoChip"]


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict
    mix: Dict
    cell: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Everything one cell needs, found by name from ``BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    wl = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    conf = json.loads((root / entry["file"]).read_text())
    arch.of(conf)                   # an unknown architecture fails here
    mix = json.loads((root / "bench" / "traffic"
                      / f"{wl['traffic']}.json").read_text())
    cell = json.loads((root / "bench" / "cells" / f"{name}.json")
                      .read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name, int(wl["chips"]), conf, mix, cell, e2e, per_layer)


def _reports(metric: Dict, cell: str) -> bool:
    """A metric with no ``workloads`` list (``setup_s``) is every cell's."""
    return cell in metric.get("workloads", [cell])


def reader(metric: str):
    """The metric's reader ``bench/metrics/<metric>.py``."""
    return importlib.import_module(f"bench.metrics.{metric}").read


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and when each
    happened.  A program met for the first time in a process is traced
    and lowered even when its compiled code then comes from the
    persistent cache (which emits no compile event), so traces inside
    the window count every program the warm-up missed."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        import jax
        self.secs = collections.Counter()
        self.at: Dict[str, List[float]] = collections.defaultdict(list)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        kind = self.EVENTS.get(event)
        if kind:
            self.secs[kind] += duration
            self.at[kind].append(time.monotonic())

    def count_between(self, kind: str, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.at[kind])


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


class Server:
    """The serving stack as ``chip_smoke.serve`` builds it."""

    def __init__(self, params, cfg, serving: Dict):
        from repro.core import OnlineTuner
        from repro.memtier import (SharedPagedPools, TierConfig,
                                   TieringManager)
        from repro.serve import sched as S

        n_log, hbm = serving["host_pages"], serving["hbm_pages"]
        ps = serving["page_size"]
        self.pools = SharedPagedPools.create(n_log, hbm)
        self.manager = TieringManager(n_log, TierConfig(
            page_size=ps, hbm_pages=hbm,
            period_steps=serving["default_period"]))
        self.tuner = OnlineTuner(n_log,
                                 default_period=serving["default_period"],
                                 profile_steps=serving["profile_steps"],
                                 trial_steps=serving["trial_steps"])
        self.batcher = S.ContinuousBatcher(
            params, cfg, max_active=serving["max_active"],
            max_len=serving["max_len"], page_size=ps,
            monitor=S.TrafficMonitor(self.pools, self.manager, self.tuner))

    def request(self, rid: int, prompt: np.ndarray, max_new: int):
        from repro.serve import sched as S
        return S.Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                         temperature=0.0)

    def close(self):
        self.batcher.close()


def _widths(mix: Dict) -> List[int]:
    """The packed-prefill widths the mix's prompts reach (pow2 buckets)."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    w = 1 << (lo - 1).bit_length()
    out = []
    while w < hi:
        out.append(w)
        w *= 2
    return out + [1 << (hi - 1).bit_length()]


def warm_up(server: Server, cfg, mix: Dict, serving: Dict,
            rng: np.random.Generator) -> None:
    """Compile (or load) every program the cell's traffic reaches: each
    macro length up to the tuner's largest period, each power-of-two
    migration size, each packed-prefill shape (joiners x width) the mix
    reaches, and the per-row host paths with every row in use.  (A move
    of more than ``warm_moves`` pages that is not a power of two still
    compiles its index conversion in the window, and is counted there.)"""
    import jax
    import jax.numpy as jnp
    from repro.memtier.tiering import PAGE_DROP

    b, pools = server.batcher, server.pools
    rows = serving["max_active"]
    tables, gids = b._tables_for([])
    dead = dict(
        cur=np.full((rows,), -1, np.int32),
        keys=np.zeros((rows, 2), np.uint32),
        iters=np.zeros((rows,), np.int32), em=np.zeros((rows,), np.int32),
        max_new=np.zeros((rows,), np.int32),
        eos=np.full((rows,), -1, np.int32),
        temps=np.zeros((rows,), np.float32))
    for n in serving["warm_macro_steps"]:
        toks, kv, _ = b._macro_fn(n)(
            pools.kv_view(), tables, gids, b.tok,
            *(jnp.asarray(dead[k]) for k in ("cur", "keys", "iters", "em",
                                             "max_new", "eos", "temps")),
            cond=None, state_cols=None)
        pools.set_kv(kv)
        jax.block_until_ready(toks)
    # page moves: the gathered copy is padded to powers of two, but the
    # pool first converts the unpadded index vectors (a program per
    # length), so every length up to ``warm_moves`` is met once here
    for n in range(1, serving["warm_moves"] + 1):
        pools.migrate_slots([int(PAGE_DROP)] * n, np.zeros(n, np.int64))
    n = 1
    while n <= pools.hbm_pages:
        pools.migrate_slots([int(PAGE_DROP)] * n, np.zeros(n, np.int64))
        n *= 2
    jax.block_until_ready(pools.kv_view())

    vocab, lo = cfg.vocab_size, mix["prompt"]["min"]
    rid = 10 ** 9

    def drain():
        while b.active or b.queue:
            b.step()

    for w in _widths(mix):
        for jp in serving["warm_joiners"]:
            for i in range(jp):
                plen = w if i == 0 else min(lo, w)
                b.submit(server.request(rid, rng.integers(
                    0, vocab, plen, dtype=np.int32), 2))
                rid += 1
            drain()
    # every row in use at once: joiners of 8 per step until all are busy
    left = rows
    while left:
        for _ in range(min(8, left)):
            b.submit(server.request(rid, rng.integers(
                0, vocab, lo, dtype=np.int32), 40))
            rid += 1
            left -= 1
        b.step()
    drain()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device(require_chip: bool, chips: int):
    """The first device and the count; without the chips a cell needs,
    ``NoChip`` (a CPU never stands in for them)."""
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} accelerator chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    return devs[0], len(devs)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def setup(cell: Cell, seed: int):
    """Weights from the seed, every program the traffic reaches compiled
    or loaded; returns (cfg, params, clock)."""
    import jax
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock()
    cfg = arch.model_config(cell.conf)
    params = arch.make_params(cell.conf, seed)
    jax.block_until_ready(params)
    warm = Server(params, cfg, cell.conf["serving"])
    warm_up(warm, cfg, cell.mix, cell.conf["serving"],
            np.random.default_rng([int(seed) & (2 ** 63 - 1), 1]))
    warm.close()
    del warm
    gc.collect()
    return cfg, params, clock


@dataclasses.dataclass
class Window:
    """What one measured window leaves: the delivery log and the served
    tokens of every request released in it, the program's events, and
    the window's slowest steps."""
    reqs: list
    log: Dict
    served: Dict
    window_s: float
    t0: float
    t1: float
    late_s: List[float]
    events: List[Dict]
    failed: int
    pool_itemsize: int
    period: int
    slow_steps: List[Dict]
    collections: List              # Python GC in the window: (at, s, gen)

    def deliveries(self):
        return list(self.log.values())

    def finished(self):
        """(prompt, served tokens) of every request served in full."""
        return [(r.prompt, self.served[r.rid]) for r in self.reqs
                if r.rid in self.log
                and len(self.log[r.rid].within(self.window_s)) == r.max_new]

    def due(self):
        return [d for d in self.log.values() if d.due_s < self.window_s]


#: steps of a window whose length and events the run logs
SLOW_STEPS = 3


def window(cell: Cell, cfg, params, *, seed: int, seconds: float,
           rate: float, trace_dir=None) -> Window:
    """Serve the cell's traffic at ``rate`` for ``seconds`` on a fresh
    serving stack: each request is submitted when the mix's arrival
    process releases it, each token timed at the return of the step that
    delivered it."""
    import jax
    from repro.obs import telemetry

    from bench import clientmetrics as CM
    from bench import traffic

    reqs, proc = traffic.generate(cell.mix, rate, seconds, seed,
                                  cfg.vocab_size)
    server = Server(params, cfg, cell.conf["serving"])
    b = server.batcher
    log: Dict = {}
    served: Dict = {}
    late: List[float] = []
    steps: List = []
    collections_: List = []             # (start, seconds, generation)

    def on_gc(phase, info):
        if phase == "start":
            on_gc.t = time.monotonic()
        else:
            collections_.append((on_gc.t, time.monotonic() - on_gc.t,
                                 info["generation"]))

    rec_t0 = time.monotonic()
    recorder = telemetry.install(telemetry.Recorder())
    span = jax.profiler.TraceAnnotation
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    def released(now):
        for due in proc.release(now, len(b.queue) + len(b.active)):
            r = reqs[len(log)]
            log[r.rid] = CM.Delivery(due, r.max_new, len(r.prompt))
            served[r.rid] = []
            yield r, due

    gc.callbacks.append(on_gc)
    t0 = time.monotonic()
    while True:
        now = time.monotonic() - t0
        if now >= seconds:
            break
        for r, due in released(now):
            with span("bench.submit"):
                b.submit(server.request(r.rid, r.prompt, r.max_new))
            late.append(now - due)
        if b.active or b.queue:
            with span("bench.step"):
                out = b.step()
            t = time.monotonic() - t0
            steps.append((t - now, now, b.step_idx))
            for rid, tok in out:
                log[rid].times.append(t)
                served[rid].append(int(tok))
        else:
            with span("bench.wait"):
                time.sleep(max(0.0, min(proc.wake(now), seconds) - now))
    t1 = time.monotonic()
    gc.callbacks.remove(on_gc)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    # due before the window closed but never submitted: they wait to its end
    for _ in released(t1 - t0):
        pass
    events = recorder.events()
    slow = []
    for dur, start, idx in sorted(steps, reverse=True)[:SLOW_STEPS]:
        lo, hi = t0 - rec_t0 + start, t0 - rec_t0 + start + dur
        gc_s = sum(d for c, d, _ in collections_
                   if t0 + start <= c <= t0 + start + dur)
        slow.append({"s": dur, "at_s": start, "step": idx, "gc_s": gc_s,
                     "events": [
            {k: v for k, v in e.items() if k != "seq"}
            for e in events if lo <= e["t"] <= hi]})
    out = Window(
        reqs=reqs, log=log, served=served, window_s=t1 - t0, t0=t0, t1=t1,
        late_s=late, events=events,
        failed=sum(1 for q in b.completed
                   if q.status in ("shed", "expired")),
        pool_itemsize=_pool_itemsize(server.pools.kv_view()),
        period=int(server.manager.period), slow_steps=slow,
        collections=[(c - t0, d, g) for c, d, g in collections_])
    server.close()
    return out


def _pool_itemsize(kv: Dict) -> int:
    """Bytes of one element of the page pool (one dtype for every leaf),
    whatever the geometry names its HBM leaves (``k``/``v``,
    ``ckv``/``krope``, ``state``)."""
    return next(a.dtype.itemsize for name, leaves in kv.items()
                if name.endswith("_hbm") for a in leaves if a is not None)


def check(cell: Cell, seed: int, finished, control: bool = False) -> Dict:
    """The comparison with the plain reference (``bench.correct``): each
    number compared beside its limit.  ``control`` puts the reference at
    the control's precision in the program's place."""
    from bench import correct as K
    limit = float(cell.cell["limits"]["max_logit_gap"])
    picks = K.sample(finished, seed)
    gap_of = K.control_gap if control else K.served_gap
    gap, n = gap_of(cell.conf, seed, picks) if picks else (float("inf"), 0)
    return {"max_logit_gap": {"value": gap, "limit": limit},
            "tokens_compared": {"value": n, "limit": 1}}


def verdict(checks: Dict) -> bool:
    """``correct``: enough tokens compared, and the widest gap within its
    limit."""
    gap = checks["max_logit_gap"]
    return bool(checks["tokens_compared"]["value"]
                >= checks["tokens_compared"]["limit"]
                and gap["value"] <= gap["limit"])


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        trace_dir: pathlib.Path = ROOT / ".bench_trace",
        keep_trace: bool = False) -> Dict:
    """One run; returns the result line's object.  ``trace`` records the
    window with the profiler (under ``trace_dir``, deleted after it is
    read unless ``keep_trace``) and reports the per-layer metrics."""
    dev, count = device(require_chip, cell.chips)
    names = _compile_names()
    cfg, params, clock = setup(cell, seed)
    w = window(cell, cfg, params, seed=seed, seconds=seconds,
               rate=float(cell.cell["rate_per_s"]),
               trace_dir=trace_dir if trace else None)
    setup_s = w.t0 - t_start
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    late = w.late_s
    _log(f"setup setup_s={setup_s} trace_s={clock.secs['trace']} "
         f"lower_s={clock.secs['lower']} compile_s={clock.secs['compile']} "
         f"compiles_in_window={clock.count_between('compile', w.t0, w.t1)} "
         f"traces_in_window={clock.count_between('trace', w.t0, w.t1)}")
    for t, msg in names:
        if w.t0 <= t <= w.t1:
            _log(f"setup lowered_in_window at_s={t - w.t0} {msg}")
    _log(f"setup requests_due={len(w.due())} finished={len(w.finished())} "
         f"window_s={w.window_s} generator_late_p50_ms="
         f"{np.median(late) * 1e3 if late else 0.0} generator_late_max_ms="
         f"{max(late) * 1e3 if late else 0.0} period={w.period}")
    _log(f"setup memory_stats {json.dumps(stats)}")
    gcs = w.collections
    _log(f"setup gc_in_window n={len(gcs)} "
         f"gen2={sum(g == 2 for _, _, g in gcs)} "
         f"total_s={sum(d for _, d, _ in gcs)} "
         f"max_s={max((d for _, d, _ in gcs), default=0.0)}")
    for st in w.slow_steps:
        _log(f"setup slow_step {json.dumps(st, default=str)[:2000]}")

    ctx = Context(conf=cell.conf, cfg=cfg, serving=cell.conf["serving"],
                  log=w.deliveries(), window_s=w.window_s, setup_s=setup_s,
                  events=w.events, trace=None, device_kind=dev.device_kind,
                  pool_itemsize=w.pool_itemsize)
    if trace:
        from bench import trace as TR
        ctx.trace = TR.reduce(TR.load(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the check: the program's state is gone, the reference runs alone
    finished = w.finished()
    del params
    gc.collect()
    t_check = time.monotonic()
    checks = check(cell, seed, finished)
    _log(f"setup check_s={time.monotonic() - t_check}")
    correct = verdict(checks)
    for k, v in checks.items():
        _log(f"check {k}={v['value']} limit={v['limit']}")
    _log(f"check correct={correct}")

    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": count, "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(w.due()),
           "failed": w.failed, "metrics": metrics, "device": info}
    if trace:
        info["busy_s"] = ctx.trace.busy_s
        info["window_s"] = ctx.trace.window_s
        out["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                            "idle_gaps": ctx.trace.top_gaps(10)}
    out["checks"] = checks
    return out


def _compile_names():
    """(time, message) of every program JAX lowers from here on (a
    persistent-cache hit is lowered too): which programs a window met
    for the first time, if any."""
    import logging

    import jax
    got = []

    class _H(logging.Handler):
        def emit(self, rec):
            msg = rec.getMessage()
            if msg.startswith("Compiling "):
                got.append((time.monotonic(), msg[:300]))

    def quiet(rec):
        # the compile log is read here, not printed
        return not rec.getMessage().startswith(
            ("Compiling ", "Finished ", "Persistent compilation cache"))

    jax.config.update("jax_log_compiles", True)
    lg = logging.getLogger("jax")
    for h in lg.handlers:
        h.addFilter(quiet)
    lg.addHandler(_H())
    lg.setLevel(logging.WARNING)
    lg.propagate = False
    return got


@dataclasses.dataclass
class Context:
    """What a metric reader may read (``trace`` only in a traced run)."""
    conf: Dict
    cfg: object
    serving: Dict
    log: list
    window_s: float
    setup_s: float
    events: List[Dict]
    trace: object
    device_kind: str
    pool_itemsize: int
