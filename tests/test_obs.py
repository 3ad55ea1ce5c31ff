"""Flight recorder: ring buffer, metrics, decision-trace regression,
exporters, report, and the zero-cost-when-disabled contract.

Covers the observability PR: the bounded event ring and its closed
taxonomy, streaming-quantile histograms, the exact tuner state-transition
sequences on deterministic streams (converge, poisoned TRIAL, regime
change, HOLD escalation) reconstructed *from the event log alone*, the
JSONL round-trip and Perfetto structural validity, the report CLI, the
StepTimer straggler path, and behavioral identity of the traffic
scheduler with telemetry on vs off."""
import contextlib
import json
import math
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import OnlineTuner
from repro.core.traffic import poisson_request_stream
from repro.ft.monitor import StepTimer
from repro.memtier import SharedPagedPools, TierConfig, TieringManager
from repro.obs import telemetry
from repro.obs import report as obs_report
from repro.serve.sched import TrafficMonitor, TrafficScheduler


@pytest.fixture()
def rec():
    """Fresh recorder installed process-wide; the previous one restored
    afterwards so tests never leak events into each other."""
    prev = telemetry.get()
    r = obs.install(obs.Recorder(enabled=True))
    yield r
    obs.install(prev)


# ---------------------------------------------------------------------------
# Recorder: ring buffer, taxonomy, metrics
# ---------------------------------------------------------------------------


def test_ring_is_bounded_ordered_and_counts_drops():
    r = obs.Recorder(capacity=8, enabled=True)
    for i in range(20):
        r.emit("serve.retire", step=i, rid=i, tokens=1)
    evs = r.events()
    assert len(evs) == 8, "ring must cap at capacity"
    assert [e["step"] for e in evs] == list(range(12, 20)), \
        "ring keeps the newest events in emission order"
    assert [e["seq"] for e in evs] == list(range(12, 20))
    assert r.dropped == 12
    assert r.summary()["events_dropped"] == 12


def test_unregistered_event_type_raises():
    r = obs.Recorder(enabled=True)
    with pytest.raises(KeyError, match="unregistered"):
        r.emit("tuner.bogus", step=0)
    # disabled recorder short-circuits before the registry check
    r.enabled = False
    r.emit("tuner.bogus", step=0)


def test_disabled_recorder_collects_nothing():
    r = obs.Recorder(enabled=False)
    r.emit("serve.retire", step=0, rid=0, tokens=1)
    r.count("x")
    r.gauge("y", 1.0)
    r.observe("z", 1.0)
    assert r.events() == []
    s = r.summary()
    assert s["counters"] == {} and s["gauges"] == {} and s["hists"] == {}


def test_events_filter_by_type_and_prefix():
    r = obs.Recorder(enabled=True)
    r.emit("serve.retire", step=0, rid=0, tokens=1)
    r.emit("serve.admit", step=0, joiners=1, pages=2, queue_depth=0,
           rids=[0], wait_ms=[0.1])
    r.emit("tier.move", manager="m0", step=4, period=4, promoted=1,
           evicted=0, pages_moved=2, cost=1.0)
    assert len(r.events("serve.admit")) == 1
    assert len(r.events(prefix="serve.")) == 2
    assert len(r.events(prefix="tier.")) == 1


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts what opens."""

    def __init__(self):
        self.opened = []

    def __call__(self, name):
        self.opened.append(name)
        return contextlib.nullcontext()


@pytest.fixture()
def annotations(monkeypatch):
    import jax.profiler
    fake = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake)
    return fake


def test_disabled_span_is_one_shared_noop(annotations):
    r = obs.Recorder(enabled=False)
    a, b = r.span("serve.step"), r.span("serve.admit", rids=[1])
    assert a is b, "a disabled recorder hands out one shared no-op context"
    with a, b:
        pass
    assert r.events() == [] and annotations.opened == []


def test_span_emits_duration_and_parent_per_thread(annotations):
    r = obs.Recorder(enabled=True)
    inner_started, outer_done = threading.Event(), threading.Event()

    def other_thread():
        with r.span("tuner.on_step"):
            inner_started.set()
            outer_done.wait(5.0)

    with r.span("serve.step"):
        with r.span("serve.prefill", joiners=2):
            with r.span("serve.prefill.launch"):
                pass
        th = threading.Thread(target=other_thread)
        th.start()
        assert inner_started.wait(5.0)
        with r.span("serve.emit"):
            pass
        outer_done.set()
        th.join(5.0)
    assert not th.is_alive()
    got = [(e["name"], e["parent"]) for e in r.events("obs.span")]
    # spans land as they close; another thread's span has its own stack
    assert got[:2] == [("serve.prefill.launch", "serve.prefill"),
                       ("serve.prefill", "serve.step")]
    assert ("tuner.on_step", "") in got
    assert ("serve.emit", "serve.step") in got
    assert got[-1] == ("serve.step", "")
    ev = r.events("obs.span")
    assert ev[1]["joiners"] == 2
    assert all(e["ms"] >= 0.0 for e in ev)
    children = [e["ms"] for e in ev if e["parent"] == "serve.step"]
    assert ev[-1]["ms"] >= max(children)
    assert sorted(annotations.opened) == sorted(n for n, _ in got)


def test_span_is_registered_and_the_taxonomy_check_passes():
    import importlib.util
    import pathlib
    assert obs.EVENTS["obs.span"].fields == ("name", "parent", "ms")
    assert "serve.pipeline.stage" not in obs.EVENTS
    assert "wall_ms" not in obs.EVENTS["serve.admit"].fields
    root = pathlib.Path(__file__).resolve().parents[1]
    spec = importlib.util.spec_from_file_location(
        "_check_events", root / "scripts" / "check_events.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check(root) == 0


def test_install_swaps_recorder_for_module_attribute_readers(rec):
    """The hot-path idiom reads telemetry.RECORDER per call, so install()
    must redirect everyone at once -- including the obs package alias."""
    assert telemetry.RECORDER is rec and obs.RECORDER is rec
    r2 = obs.install(obs.Recorder(enabled=True))
    assert telemetry.RECORDER is r2 and obs.RECORDER is r2


def test_histogram_quantiles_within_bucket_error():
    h = obs.Histogram()
    xs = np.linspace(1e-3, 10.0, 5000)
    for x in xs:
        h.observe(float(x))
    # geometric buckets at ratio 2**0.25 bound relative error by ~9%
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(xs, q))
        assert h.quantile(q) == pytest.approx(exact, rel=0.10)
    assert h.count == 5000
    assert h.vmin == pytest.approx(1e-3) and h.vmax == pytest.approx(10.0)
    assert h.mean == pytest.approx(float(xs.mean()), rel=1e-6)


def test_histogram_nonfinite_and_extremes_stay_out_of_quantiles():
    h = obs.Histogram()
    for v in (1.0, 2.0, math.nan, math.inf, -5.0, 0.0):
        h.observe(v)
    assert h.nonfinite == 2
    assert h.count == 4                      # finite ones only
    assert h.vmin == -5.0 and h.vmax == 2.0
    assert math.isfinite(h.quantile(0.99))
    s = h.summary()
    assert s["nonfinite"] == 2 and s["count"] == 4


# ---------------------------------------------------------------------------
# Decision-trace regression: exact transition sequences from the log alone
# ---------------------------------------------------------------------------


def _converge(rec, **kw):
    """Drive a tuner to HOLD at period 8 (mirrors test_hostile's helper);
    returns (tuner, ids)."""
    params = dict(default_period=2, profile_steps=32, trial_steps=32,
                  horizon_steps=64, bin_width=1, patience=3)
    params.update(kw)
    tuner = OnlineTuner(64, **params)
    ids = lambda t: np.array([t % 4])
    for t in range(600):
        tuner.on_step(accessed_ids=ids(t), cost=abs(tuner.period - 8) + 1.0)
    assert tuner.state == OnlineTuner.HOLD and tuner.period == 8
    return tuner, ids


def _transitions(rec, tuner):
    return [(e["frm"], e["to"], e["reason"])
            for e in rec.events("tuner.transition")
            if e["tuner"] == tuner.obs_id]


def test_trace_converge_pins_profile_trial_hold_sequence(rec):
    tuner, _ = _converge(rec)
    ts = _transitions(rec, tuner)
    assert ts[0] == ("profile", "trial", "profile-complete")
    assert ts[1] == ("trial", "hold", "sweep-complete")
    assert len(ts) == 2, f"steady convergence must not churn: {ts}"
    # the trial phase switched periods: every change is in the log
    periods = [e for e in rec.events("tuner.period")
               if e["tuner"] == tuner.obs_id]
    assert periods, "candidate switches must emit tuner.period"
    assert all(e["period"] != e["prev"] for e in periods)
    trials = [e for e in rec.events("tuner.trial")
              if e["tuner"] == tuner.obs_id]
    assert trials and trials[-1]["best_period"] == 8
    base = [e for e in rec.events("tuner.baseline")
            if e["tuner"] == tuner.obs_id]
    assert base, "HOLD must attest a baseline"


def test_trace_poisoned_trial_records_burst_verdict_and_revert(rec):
    tuner, ids = _converge(rec)
    rec.clear()
    tuner._reprofile()                        # warm manual re-tune
    for i in range(200):
        if tuner.state != OnlineTuner.TRIAL:
            break
        tuner.on_step(accessed_ids=ids(i),
                      cost=300.0 if (i // 8) % 2 == 0 else 1.0)
    assert _transitions(rec, tuner) == [
        ("hold", "trial", "warm-manual"),
        ("trial", "hold", "guard-abort"),
    ]
    guards = [e for e in rec.events("tuner.guard")
              if e["tuner"] == tuner.obs_id]
    assert len(guards) == 1
    assert guards[0]["where"] == "trial" and guards[0]["verdict"] == "burst"
    # warm sweeps start at the previous winner and the abort reverts to
    # it, so a clean revert means NO period change ever hit the log
    assert tuner.period == 8
    assert [e for e in rec.events("tuner.period")
            if e["tuner"] == tuner.obs_id] == []


def test_trace_uniform_regime_change_records_cold_reprofile(rec):
    tuner, ids = _converge(rec)
    rec.clear()
    tuner._reprofile()
    for i in range(200):
        if tuner.state != OnlineTuner.TRIAL:
            break
        tuner.on_step(accessed_ids=ids(i), cost=300.0)
    assert _transitions(rec, tuner) == [
        ("hold", "trial", "warm-manual"),
        ("trial", "profile", "cold-guard-regime"),
    ]
    g = [e for e in rec.events("tuner.guard")
         if e["tuner"] == tuner.obs_id]
    assert g and g[-1]["verdict"] == "regime"


def test_trace_hold_escalation_records_discard_then_cold(rec):
    tuner, ids = _converge(rec, drift_patience=3)
    rec.clear()
    i = 0
    while tuner.state == OnlineTuner.HOLD and i < 3000:
        tuner.on_step(accessed_ids=ids(i), cost=100.0)
        i += 1
    assert tuner.state == OnlineTuner.PROFILE
    assert _transitions(rec, tuner) == [
        ("hold", "profile", "cold-guard-escalate")]
    kinds = [e["kind"] for e in rec.events("tuner.hold_window")
             if e["tuner"] == tuner.obs_id]
    assert kinds.count("discard-guard") >= 1, \
        "guard windows before escalation must be logged as discarded"
    verdicts = [e["verdict"] for e in rec.events("tuner.guard")
                if e["tuner"] == tuner.obs_id]
    assert verdicts[:-1].count("discard") >= 1
    assert verdicts[-1] == "escalate"


def test_trace_drift_records_strikes_then_warm_retune(rec):
    tuner, ids = _converge(rec, drift_ratio=1.5, drift_patience=2)
    rec.clear()
    i = 0
    # sustained 2x cost: drift strikes accumulate, then a warm re-tune
    while tuner.state == OnlineTuner.HOLD and i < 3000:
        tuner.on_step(accessed_ids=ids(i),
                      cost=2.0 * (abs(tuner.period - 8) + 1.0))
        i += 1
    assert tuner.state == OnlineTuner.TRIAL
    ts = _transitions(rec, tuner)
    assert ts == [("hold", "trial", "warm-drift")]
    kinds = [e["kind"] for e in rec.events("tuner.hold_window")
             if e["tuner"] == tuner.obs_id]
    assert kinds.count("drift-strike") >= 2, \
        "each drifting window before the re-tune must log a strike"


def test_cost_log_and_recorder_histogram_agree(rec):
    tuner, _ = _converge(rec)
    h = rec.hists["tuner.cost_per_step"]
    assert h.count == 600, "every on_step cost lands in the histogram"
    # cost_log is the bounded working window of the same series
    assert list(tuner.cost_log)[-1] == 1.0
    assert h.vmin == pytest.approx(min(tuner.cost_log))


# ---------------------------------------------------------------------------
# Exporters: JSONL round-trip, Perfetto structure
# ---------------------------------------------------------------------------


def test_jsonl_roundtrip_and_metrics_summary(rec, tmp_path):
    tuner, _ = _converge(rec)
    path = obs.write_jsonl(tmp_path / "log.jsonl", rec)
    back = obs.read_jsonl(path)
    assert back[-1]["type"] == "metrics.summary"
    assert back[-1]["schema"] == obs.SCHEMA
    assert "tuner.cost_per_step" in back[-1]["hists"]
    evs = back[:-1]
    assert [e["type"] for e in evs] == [e["type"] for e in rec.events()]
    assert all(set(("seq", "t", "type")) <= set(e) for e in evs)
    # every line is independently parseable (flat records, no nesting
    # beyond the closing summary)
    for line in path.read_text().splitlines():
        json.loads(line)


def test_perfetto_trace_has_phase_spans_windows_and_counters(rec, tmp_path):
    tuner, ids = _converge(rec)
    mgr = TieringManager(32, TierConfig(page_size=4, hbm_pages=4,
                                        period_steps=4))
    resident = np.zeros(32, bool)
    for t in range(16):
        mass = np.zeros(32, np.float32)
        mass[t % 8] = 1.0
        mgr.on_step(mass, resident)
        mgr.maybe_tier_symbolic(resident)
    trace = obs.perfetto_trace(rec.events())
    te = trace["traceEvents"]
    assert trace["otherData"]["schema"] == obs.SCHEMA
    names = {e["name"] for e in te}
    spans = [e for e in te if e["ph"] == "X"]
    assert {"PROFILE", "TRIAL", "HOLD"} <= {e["name"] for e in spans}, \
        "tuner phases must render as duration spans"
    assert any(e["name"].startswith("window(p=") for e in spans), \
        "tiering windows must render as spans"
    assert any(e["ph"] == "C" and e["name"].startswith("period")
               for e in te), "period counter track missing"
    assert any(e["ph"] == "M" for e in te), "process/thread names missing"
    for e in spans:
        assert e["dur"] >= 1
    # file form loads as JSON
    p = obs.write_perfetto(tmp_path / "trace.json", rec.events())
    assert json.loads(p.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# Report: the replay CLI
# ---------------------------------------------------------------------------


def test_report_reconstructs_decision_trace_from_log_alone(rec, tmp_path,
                                                           capsys):
    tuner, ids = _converge(rec)
    tuner._reprofile()
    for i in range(200):
        if tuner.state != OnlineTuner.TRIAL:
            break
        tuner.on_step(accessed_ids=ids(i),
                      cost=300.0 if (i // 8) % 2 == 0 else 1.0)
    path = obs.write_jsonl(tmp_path / "log.jsonl", rec)

    obs_report.main([str(path)])
    out = capsys.readouterr().out
    assert "PROFILE -> TRIAL" in out.upper().replace("  ", " ") or \
        "profile -> trial" in out.lower()
    assert "sweep-complete" in out
    assert "warm-manual" in out
    assert "guard-abort" in out
    assert "burst" in out
    assert "tuner.cost_per_step" in out, "metrics table missing"

    trace = obs_report.decision_trace(obs.read_jsonl(path))
    states = ("PROFILE", "TRIAL", "HOLD")
    trans_lines = [ln for ln in trace if any(
        f"{a} -> {b}" in ln for a in states for b in states)]
    assert len(trans_lines) == 4, \
        "converge (2) + warm re-tune + guard-abort (2) transitions"


def test_report_writes_perfetto_sidecar(rec, tmp_path, capsys):
    _converge(rec)
    log = obs.write_jsonl(tmp_path / "log.jsonl", rec)
    out = tmp_path / "trace.json"
    obs_report.main([str(log), "--perfetto", str(out)])
    assert json.loads(out.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# StepTimer -> recorder
# ---------------------------------------------------------------------------


def test_step_timer_reports_histogram_and_straggler_event(rec, monkeypatch):
    t = StepTimer(threshold=3.0, warmup=1, name="serve.macro")
    now = [0.0]
    monkeypatch.setattr("repro.ft.monitor.time",
                        type("T", (), {"monotonic":
                                       staticmethod(lambda: now[0])}))
    for step, dt in enumerate((0.1, 0.1, 0.1, 1.0)):
        t.start()
        now[0] += dt
        t.stop(step)
    assert t.stragglers == [3]
    ev = rec.events("ft.straggler")
    assert len(ev) == 1
    assert ev[0]["timer"] == "serve.macro" and ev[0]["step"] == 3
    assert ev[0]["dt_s"] == pytest.approx(1.0)
    assert ev[0]["dt_s"] > 3.0 * ev[0]["ema_s"]
    assert rec.counters["ft.stragglers"] == 1
    assert rec.hists["serve.macro.step_s"].count == 4


def test_unnamed_step_timer_stays_silent(rec):
    t = StepTimer(warmup=1)
    for step in range(4):
        t.start()
        t.stop(step)
    assert rec.events("ft.straggler") == []
    assert "None.step_s" not in rec.hists and not rec.hists


# ---------------------------------------------------------------------------
# Telemetry must never change behavior: scheduler identity on vs off
# ---------------------------------------------------------------------------


def _run_traffic(enabled: bool):
    prev = telemetry.get()
    r = obs.install(obs.Recorder(enabled=enabled))
    try:
        specs = poisson_request_stream(
            40, 0.3, {"sink": 0.5, "random": 0.5}, prompt_len=(4, 60),
            new_tokens=(8, 40), seed=7)
        pools = SharedPagedPools.create(128, 16)
        mgr = TieringManager(128, TierConfig(page_size=16, hbm_pages=16,
                                             period_steps=4))
        tuner = OnlineTuner(128, default_period=4)
        sched = TrafficScheduler(specs, TrafficMonitor(pools, mgr, tuner),
                                 page_size=16, max_active=6)
        sched.run(400)
        return (sched.admitted, sched.completed, tuner.period, tuner.state,
                mgr.modeled_time, r)
    finally:
        obs.install(prev)


def test_scheduler_behavior_identical_with_telemetry_on_and_off():
    a_on = _run_traffic(True)
    a_off = _run_traffic(False)
    assert a_on[:5] == a_off[:5], \
        "recording must be a pure observer of the serving/tuning path"
    r_on, r_off = a_on[5], a_off[5]
    assert r_off.events() == [] and r_off.summary()["counters"] == {}
    # the enabled run captured the full decision path end to end
    types = {e["type"] for e in r_on.events()}
    assert {"serve.admit", "serve.retire", "tier.move",
            "tuner.transition"} <= types
    c = r_on.summary()["counters"]
    assert c["serve.admitted"] == a_on[0]
    assert c["serve.retired"] == a_on[1]
    assert c["tier.pages_moved"] >= 0
    g = r_on.summary()["gauges"]
    assert 0.0 <= g["pool.hbm_resident_frac"] <= 1.0
    assert 0.0 <= g["pool.allocated_frac"] <= 1.0
