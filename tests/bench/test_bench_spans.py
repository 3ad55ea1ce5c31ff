"""Idle gaps named by the program's spans (``bench.spans``), on a trace
recorded with them on one TPU v5e (1.6 s of the qwen3-14b-tp4.long cell,
cut by ``bench.spans.compact``) and on hand-made intervals, and the
readers of the program's admission and boundary timings on a synthetic
run."""
import pathlib

import numpy as np
import pytest

from bench import harness
from bench import spans as SP
from bench import trace as TR

DATA = pathlib.Path(__file__).parent / "data" / \
    "qwen3-14b-tp4.long.spans.trace.json.gz"

OPS = [[f"%op.{i} = ", s, e - s]
       for i, (s, e) in enumerate([(0, 5), (12, 20), (45, 50), (62, 98),
                                   (105, 120)])]
BENCH = [["bench.step", 0, 100], ["bench.wait", 100, 20]]
PROGRAM = [["serve.step", 5, 90], ["serve.prefill", 10, 30],
           ["serve.prefill.first_tokens", 25, 15],
           ["serve.macro.launch", 50, 10],
           # after the last bench.* span: outside the window
           ["serve.step", 130, 10]]


def _trace(host):
    return TR.from_compact({
        "/device:TPU:0": {"XLA Ops": OPS,
                          "XLA Modules": [["jit_f(1)", 0, 120]]},
        "/host:CPU": {"python3": sorted(host, key=lambda e: e[1])}})


def test_gap_is_named_by_the_innermost_open_span():
    red = SP.reduce(_trace(BENCH + PROGRAM))
    assert red.gap_list == [("serve.step #1", pytest.approx(7e-9)),
                            ("serve.prefill.first_tokens #1",
                             pytest.approx(25e-9)),
                            ("serve.macro.launch #1", pytest.approx(12e-9)),
                            ("bench.wait #1", pytest.approx(7e-9))]
    assert red.idle_by_span["serve.prefill.first_tokens"] == \
        pytest.approx(25e-9)
    # idle inside bench.step: 44 ns, of which 37 below serve.step
    assert red.named_share() == pytest.approx(37 / 44)


def test_window_comes_from_bench_spans_only():
    red = SP.reduce(_trace(BENCH + PROGRAM))
    assert red.window_s == pytest.approx(120e-9)
    assert red.idle_s == pytest.approx(51e-9)
    (step,) = red.steps                  # the later serve.step is outside
    assert step.idle_ms == pytest.approx(44e-6)
    assert step.launched
    assert red.boundary_idle_ms() == pytest.approx(44e-6)
    assert [n for n, _ in step.parts] == [
        "serve.prefill", "serve.prefill.first_tokens", "serve.macro.launch"]


def test_without_program_spans_the_names_are_bench_traces():
    plain = _trace(BENCH)
    red = SP.reduce(plain)
    assert red.gap_list == TR.reduce(plain).gap_list
    assert red.steps == [] and red.boundary_idle_ms() is None
    assert red.named_share() == 0.0
    # the program's spans leave bench.trace's reduction as it was
    with_spans = TR.reduce(_trace(BENCH + PROGRAM))
    assert with_spans == TR.reduce(plain)


def test_compact_keeps_program_spans():
    pd = _trace(BENCH + PROGRAM)
    back = TR.from_compact(SP.compact(pd))
    assert SP.reduce(back) == SP.reduce(pd)


def test_innermost_sweeps_nested_spans():
    spans = [(0, 10, "a"), (1, 3, "b"), (5, 9, "c"), (6, 7, "d"),
             (12, 14, "e")]
    assert SP.innermost(spans, [0.5, 2, 4, 6.5, 8, 11, 13]) == [
        ["a"], ["a", "b"], ["a"], ["a", "c", "d"], ["a", "c"], [], ["e"]]


@pytest.fixture(scope="module")
def recorded():
    pd = TR.load(DATA)
    return TR.reduce(pd), SP.reduce(pd)


def test_recorded_idle_by_span(recorded):
    plain, red = recorded
    assert red.window_s == pytest.approx(plain.window_s, rel=1e-12)
    assert red.window_s == pytest.approx(1.629147117, rel=1e-9)
    assert red.idle_s == pytest.approx(plain.window_s - plain.busy_s,
                                       rel=1e-9)
    by = red.idle_by_span
    assert by["bench.wait"] == pytest.approx(0.153204702, rel=1e-9)
    assert by["serve.macro.wait"] == pytest.approx(0.030456411, rel=1e-6)
    assert by["serve.prefill.first_tokens"] == \
        pytest.approx(0.024253077, rel=1e-6)
    assert by["serve.tables"] == pytest.approx(0.02057909, rel=1e-6)
    assert by["serve.emit"] == pytest.approx(0.01138954, rel=1e-6)
    assert by["serve.macro.launch"] == pytest.approx(0.009698678, rel=1e-6)
    assert sum(by.values()) == pytest.approx(red.idle_s, rel=1e-9)
    # every idle second inside a bench.step is named below serve.step
    assert red.idle_in_bench_steps_s == pytest.approx(0.096981764, rel=1e-6)
    assert red.named_share() == pytest.approx(1.0)
    assert len(red.gap_list) == len(plain.gap_list) == 501


def test_recorded_steps(recorded):
    _, red = recorded
    assert len(red.steps) == 6 and all(s.launched for s in red.steps)
    assert red.boundary_idle_ms() == pytest.approx(15.2457905, rel=1e-6)
    slow = red.slow_steps(1)[0]
    assert slow.ms == pytest.approx(289.124081, rel=1e-6)
    assert slow.parts[0] == ("serve.macro.wait",
                             pytest.approx(210.856426, rel=1e-6))


def _ctx(events):
    return harness.Context(conf={}, cfg=None, serving={}, log=[],
                           window_s=51.0, setup_s=0.0, events=events,
                           trace=None, device_kind="TPU v5 lite",
                           pool_itemsize=4)


def test_queue_wait_reads_each_admitted_request():
    waits = np.linspace(0.0, 400.0, 120)
    events = [{"type": "serve.admit", "rids": [2 * i, 2 * i + 1],
               "wait_ms": list(waits[2 * i: 2 * i + 2])}
              for i in range(60)]
    read = harness.reader("queue_wait_p90_ms")
    assert read(_ctx(events)) == pytest.approx(np.quantile(waits, 0.9))
    # a program whose admissions carry no waits gives nothing
    assert read(_ctx([{"type": "serve.admit", "joiners": 2}])) is None


def test_tier_boundary_is_the_mean_monitor_span():
    events = [{"type": "obs.span", "name": n, "parent": "serve.step",
               "ms": ms}
              for n, ms in (("serve.monitor", 1.0), ("serve.emit", 9.0),
                            ("serve.monitor", 3.0))]
    read = harness.reader("tier_boundary_ms")
    assert read(_ctx(events)) == pytest.approx(2.0)
    assert read(_ctx([{"type": "serve.macro", "n_steps": 16}])) is None
