"""The reduction from a device trace to busy time, kernel time and idle
gaps, on a trace recorded on one TPU v5e (1.5 s of the qwen3-14b-tp4.long
cell, cut by ``bench.trace.compact``) and on hand-made intervals."""
import pathlib

import pytest

from bench import trace as TR

DATA = pathlib.Path(__file__).parent / "data" / \
    "qwen3-14b-tp4.long.trace.json.gz"


@pytest.fixture(scope="module")
def red():
    return TR.reduce(TR.load(DATA))


def test_union_merges_overlaps_and_nesting():
    assert TR.union([(5, 7), (0, 2), (1, 3), (6, 6.5), (8, 9)]) == \
        [(0, 3), (5, 7), (8, 9)]


def test_gaps_between_busy_intervals():
    busy = TR.union([(1, 2), (4, 5)])
    assert TR.gaps(busy, 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert TR.gaps(busy, 1, 5) == [(2, 4)]


def test_exclusive_time_subtracts_nested_ops():
    ex = TR._exclusive([(0, 10, "loop"), (1, 3, "a"), (4, 8, "b"),
                        (5, 6, "c"), (12, 13, "d")])
    assert ex == {"loop": 4, "a": 2, "b": 3, "c": 1, "d": 1}


def test_names():
    assert TR.op_name("%paged_attention.5 = (f32[32,10,128]) custom-call(x)") \
        == "paged_attention.5"
    assert TR.base_name("paged_attention.5") == "paged_attention"
    assert TR.base_name("jit_prefill_batched(1234)") == "jit_prefill_batched"
    assert TR.base_name("copy-done") == "copy-done"


def test_recorded_trace_busy_union(red):
    assert red.window_s == pytest.approx(1.692673132, rel=1e-9)
    assert red.busy_s == pytest.approx(1.508967933, rel=1e-9)
    # the window is exactly busy time plus the idle gaps
    assert red.busy_s + sum(s for _, s in red.gap_list) == \
        pytest.approx(red.window_s, rel=1e-9)


def test_recorded_trace_kernel_and_program_time(red):
    assert red.op_time("paged_attention") == \
        pytest.approx(0.745442867, rel=1e-9)
    sec, n = red.program_time("decode_macro_step")
    assert (sec, n) == (pytest.approx(1.30144947, rel=1e-9), 2)
    sec, n = red.program_time("prefill_batched")
    assert (sec, n) == (pytest.approx(0.17728942, rel=1e-9), 2)
    assert red.top_ops(1)[0][0] == "jit_decode_macro_step/paged_attention.5"


def test_recorded_trace_idle_gaps(red):
    assert len(red.gap_list) == 303
    assert sum(s for _, s in red.gap_list) == \
        pytest.approx(0.183705199, rel=1e-9)
    top = red.top_gaps(2)
    assert top[0] == ["bench.wait #1", pytest.approx(0.140259443, rel=1e-9)]
    assert top[1][0] == "bench.step #274"
