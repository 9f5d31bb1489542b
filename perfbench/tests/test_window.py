"""The window's arithmetic: steps found from token arrivals, a window
that opens and closes on step boundaries, gaps and tails, and lateness
timed from the due time."""

import threading

import pytest

from perfbench import serve_cell
from perfbench.serve_cell import Stream


def arrivals(steps, streams, step_s=0.25, jitter=0.002):
    return [k * step_s + i * jitter / streams
            for k in range(steps) for i in range(streams)]


def test_steps_are_found_from_arrivals():
    clusters = serve_cell.step_clusters(arrivals(10, 8), 0.06)
    assert len(clusters) == 10
    assert all(c[2] == 8 for c in clusters)


def test_window_opens_and_closes_on_step_boundaries():
    times = arrivals(100, 8)
    t_ref = times[8 * 10 + 3]            # some token of step 10
    lo, hi = serve_cell.aligned_window(times, t_ref, 5.1, 0.06)
    assert lo == pytest.approx(max(times[80:88]))
    # 5.1 s at 0.25 s a step: the first step ending >= lo + 5.1 is 21 on.
    assert hi == pytest.approx(max(times[8 * 31:8 * 32]))
    inside = [t for t in times if lo < t <= hi]
    assert len(inside) == 21 * 8         # whole steps only
    with pytest.raises(RuntimeError):
        serve_cell.aligned_window(times, t_ref, 500.0, 0.06)


def test_one_more_or_less_second_of_traffic_does_not_move_the_rate():
    """What PR 22's cell lacked: the rate over an aligned window is the
    same wherever the nominal end falls inside a step."""
    times = arrivals(400, 8)
    rates = []
    for seconds in (30.0, 30.1, 30.2):
        lo, hi = serve_cell.aligned_window(times, times[85], seconds, 0.06)
        rates.append(sum(lo < t <= hi for t in times) / (hi - lo))
    assert max(rates) - min(rates) < 1e-6 * rates[0]


def test_gaps_count_every_gap_that_ends_in_the_window():
    a, b = Stream(0, [1], 4), Stream(1, [1], 4)
    a.times, b.times = [0.0, 1.0, 2.0, 4.0], [0.5, 1.5, 3.5]
    gaps = serve_cell.gaps_in([a, b], (1.0, 3.5))
    assert sorted(gaps) == [1.0, 1.0, 2.0]  # 1->2, 0.5->1.5, 1.5->3.5


class FakeGen:
    request_id = "r"

    def __init__(self, tokens):
        self.tokens, self.closed = tokens, False

    def __iter__(self):
        return iter(self.tokens)

    def close(self):
        self.closed = True


class FakeHandle:
    def __init__(self, tokens):
        self.generate = self
        self.tokens = tokens

    def remote_streaming(self, prompt, max_new_tokens):
        self.gen = FakeGen(self.tokens[:max_new_tokens])
        return self.gen


def test_a_stream_must_deliver_exactly_what_was_asked():
    never, now = threading.Event(), threading.Event()
    now.set()
    full = Stream(0, [1, 2], 3)
    full.consume(FakeHandle([5, 6, 7]), never)
    assert full.finished and full.ok(100) and len(full.times) == 3
    short = Stream(1, [1, 2], 3)
    short.consume(FakeHandle([5, 6]), never)
    assert short.finished and not short.ok(100)
    wild = Stream(2, [1, 2], 2)
    wild.consume(FakeHandle([5, 600]), never)
    assert not wild.ok(100)
    cut = Stream(3, [1, 2], 3)
    handle = FakeHandle([5, 6, 7])
    cut.consume(handle, now)             # the window has closed
    assert cut.cancelled and handle.gen.closed and cut.ok(100)


def test_lateness_and_ttft_are_timed_from_the_due_time():
    s = Stream(0, [1], 2, due=10.0)
    s.sent, s.times = 10.3, [11.0, 11.1]
    closed = Stream(1, [1], 2)           # a closed loop's: no due time
    closed.sent, closed.times = 5.0, [5.5]
    assert serve_cell.from_due([s, closed], "first_token") \
        == [pytest.approx(1.0)]
    assert serve_cell.from_due([s, closed], "sent") == [pytest.approx(0.3)]


def test_counters_are_differenced_against_the_step_before_the_window():
    """A program compiled in the window's first step is counted: the
    baseline is the last step before the window, not the first inside."""
    import types

    from perfbench.rundata import RunData

    def step(compiles, preemptions):
        return types.SimpleNamespace(compiles=compiles,
                                     preemptions=preemptions)

    run = RunData(cell={}, cfg={}, mix={}, family=None, chips=1, peaks=None,
                  window=(0, 1), end_to_end={}, memory_peak_bytes=0,
                  engine_steps=[step(7, 0), step(7, 2)],
                  step_before=step(6, 0))
    assert run.counted_in_window("compiles") == 1
    assert run.counted_in_window("preemptions") == 2
    run.step_before = None
    assert run.counted_in_window("compiles") is None
