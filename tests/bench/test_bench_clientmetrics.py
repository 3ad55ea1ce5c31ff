"""Client-side metric arithmetic on synthetic delivery logs."""
import pytest

from bench import clientmetrics as CM


def _log():
    # due, max_new, delivery times (s from the window's start)
    return [
        CM.Delivery(0.0, 3, times=[0.5, 0.6, 0.9]),        # done
        CM.Delivery(1.0, 4, times=[1.2, 1.2, 2.0, 2.1]),   # done, burst
        CM.Delivery(2.0, 3, times=[2.5, 3.5, 9.0]),        # last after end
        CM.Delivery(4.0, 2, times=[]),                     # never started
        CM.Delivery(6.0, 2, times=[9.5]),                  # first after end
    ]


def test_ttft_censors_at_the_window_end(monkeypatch):
    monkeypatch.setattr(CM, "min_samples", lambda q: 1)
    log = _log()
    # waits 500, 200, 500 ms; request 4 (due 4.0, nothing by 5.0) enters
    # with its 1000 ms up to the end; request 5 is due after the window
    assert CM.ttft_ms(log, 5.0, 0.0) == pytest.approx(200.0)
    assert CM.ttft_ms(log, 5.0, 0.5) == pytest.approx(500.0)
    assert CM.ttft_ms(log, 5.0, 1.0) == pytest.approx(1000.0)
    # a longer window lets request 5 in with its real first token (3500
    # ms) and request 4 with its longer wait (6000 ms)
    assert CM.ttft_ms(log, 10.0, 0.75) == pytest.approx(3500.0)
    assert CM.ttft_ms(log, 10.0, 1.0) == pytest.approx(6000.0)


def test_tpot_and_stall_per_request(monkeypatch):
    monkeypatch.setattr(CM, "min_samples", lambda q: 1)
    log = _log()
    # finished in the window: requests 1 and 2 only
    assert CM.tpot_ms(log, 5.0, 0.0) == pytest.approx(200.0)
    assert CM.tpot_ms(log, 5.0, 1.0) == pytest.approx(300.0)
    # longest gaps in the window: 0.3, 0.8, 1.0 (request 3: 2.5 -> 3.5)
    assert CM.stall_ms(log, 5.0, 0.0) == pytest.approx(300.0)
    assert CM.stall_ms(log, 5.0, 1.0) == pytest.approx(1000.0)


def test_tokens_per_second_over_the_whole_window():
    # 3 + 4 + 2 tokens reach the client by 5 s
    assert CM.output_tok_s(_log(), 5.0) == pytest.approx(9 / 5.0)


@pytest.mark.parametrize("q,need", [(0.95, 200), (0.9, 100), (0.5, 20)])
def test_a_tail_needs_ten_samples_beyond_it(q, need):
    assert CM.min_samples(q) == need
    CM.tail(list(range(need)), q)
    with pytest.raises(CM.TooFewSamples):
        CM.tail(list(range(need - 1)), q)


def test_tail_interpolates_between_order_statistics():
    vals = list(range(1, 201))
    assert CM.tail(vals, 0.95) == pytest.approx(190.05)
    assert CM.tail(vals, 0.9) == pytest.approx(180.1)
