"""The window's arithmetic: steps found from token arrivals, a window
that opens and closes on step boundaries, gaps and tails, and lateness
timed from the due time."""

import random
import statistics
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


# ---- a stream's time per output token: tpot_mean_ms over all of the window
# ---- (end to end), tpot_p50_ms as the median over runs of 64 (per layer) ----


def stream_of(times, index=0):
    s = Stream(index, [1], len(times))
    s.times = list(times)
    return s


def stepped_streams(steps, streams, step_s, tokens_a_step, stall=None):
    """Stamps of ``streams`` streams over ``steps`` engine steps of
    ``step_s``; ``tokens_a_step(stream, step)`` tokens reach a stream
    together at the step's end. ``stall`` = (step, seconds): that one
    step takes so much longer."""
    out = [[] for _ in range(streams)]
    now = 0.0
    for k in range(steps):
        now += step_s + (stall[1] if stall and stall[0] == k else 0.0)
        for i in range(streams):
            out[i] += [now + 1e-5 * i] * tokens_a_step(i, k)
    return [stream_of(t, i) for i, t in enumerate(out)]


def tpot_ms(streams, window=(0.0, 1e9)):
    runs = serve_cell.tpot_runs(streams, window)
    return 1e3 * statistics.median(runs), runs


def mean_ms(streams, clients, window=(0.0, 1e9)):
    return 1e3 * serve_cell.tpot_mean(streams, window, clients)


def kept(share):
    """Seeded drafts: ``share`` of the (stream, step) pairs give two
    tokens, as a self-drafting step's acceptance does."""
    def tokens(i, k):
        return 1 + (random.Random(f"{i}/{k}").random() < share)
    return tokens


@pytest.mark.parametrize("step_s", [0.0125, 0.0175, 0.030])
def test_tpot_of_a_one_token_program_is_its_step(step_s):
    streams = stepped_streams(400, 8, step_s, lambda i, k: 1)
    ms, runs = tpot_ms(streams)
    assert ms == pytest.approx(1e3 * step_s, rel=1e-6)
    # 399 tokens after a stream's first: six runs of 64, the rest dropped.
    assert len(runs) == 8 * 6


@pytest.mark.parametrize("share", [0.358, 0.8, 1.0])
def test_tpot_of_a_two_token_program_is_step_over_tokens_a_step(share):
    """The two tokens of a kept draft arrive together (a zero gap, and at
    full acceptance half of all gaps): the median gap reads 0 or a step
    and nothing between, ``tpot_p50_ms`` a step over what it gave."""
    step_s = 0.0125
    streams = stepped_streams(3000, 16, step_s, kept(share))
    ms, runs = tpot_ms(streams)
    assert len(runs) > 900 and min(runs) > 0.3 * step_s   # never zero
    # A run of 64 tokens is a whole number of steps, so under a step of
    # constant length the median has a grain of one step in 64 / (1 +
    # share); the chip's steps vary by more than that from one to the next.
    assert ms == pytest.approx(1e3 * step_s / (1 + share),
                               rel=(1 + share) / 64)
    gaps = serve_cell.gaps_in(streams, (0.0, 1e9))
    assert serve_cell.percentile(gaps, 50) in (
        0.0, pytest.approx(step_s))


def test_tpot_carries_what_drafting_returns():
    """Every draft refused: the same steps give a token each, and the
    metric reads the step, 1.36 times what it read at 35.8 % kept."""
    step_s = 0.0125
    drafting, _ = tpot_ms(stepped_streams(3000, 16, step_s, kept(0.358)))
    refused, _ = tpot_ms(stepped_streams(3000, 16, step_s, kept(0.0)))
    assert refused == pytest.approx(1e3 * step_s, rel=1e-6)
    assert refused / drafting == pytest.approx(1.358, rel=0.01)


@pytest.mark.parametrize("tokens_a_step", [lambda i, k: 1, kept(0.358)],
                         ids=["one-token", "drafting"])
def test_a_stall_of_seconds_leaves_the_median_and_shows_in_the_mean(
        tokens_a_step):
    """One step of 3 s among 3,000 of 12.5 ms: 7.4 % of the window's
    time, and of the rate. One run a stream of some sixty holds it, so
    the median of runs stays; the mean over the window carries all of
    it, which is why the mean and not the median is held to a bound."""
    calm = stepped_streams(3000, 16, 0.0125, tokens_a_step)
    stalled = stepped_streams(3000, 16, 0.0125, tokens_a_step,
                              stall=(1500, 3.0))
    window = (0.0, 3000 * 0.0125)
    assert len(tpot_ms(calm)[1]) > 700
    assert abs(tpot_ms(stalled)[0] / tpot_ms(calm)[0] - 1) < 0.01
    assert mean_ms(stalled, 16, (0.0, window[1] + 3.0)) \
        / mean_ms(calm, 16, window) == pytest.approx(1.08, abs=0.002)


@pytest.mark.parametrize("step_s", [0.0125, 0.0175, 0.030])
def test_the_mean_of_a_one_token_program_with_every_seat_taken_is_its_step(
        step_s):
    streams = stepped_streams(400, 8, step_s, lambda i, k: 1)
    assert mean_ms(streams, 8, (0.0, 400 * step_s + 1e-3)) \
        == pytest.approx(1e3 * step_s, rel=1e-3)
    # ... which the median of runs reads too: they differ by what the
    # window holds beside plain steps.
    assert tpot_ms(streams)[0] == pytest.approx(1e3 * step_s, rel=1e-6)


@pytest.mark.parametrize("share", [0.0, 0.358, 1.0])
def test_the_mean_carries_what_drafting_returns(share):
    step_s = 0.0125
    streams = stepped_streams(3000, 16, step_s, kept(share))
    assert mean_ms(streams, 16, (0.0, 3000 * step_s + 1e-3)) \
        == pytest.approx(1e3 * step_s / (1 + share), rel=0.01)


def test_the_mean_counts_an_empty_seat_and_a_turnover():
    """16 clients, one of them between two requests for a quarter of the
    window (queueing, a long prompt's prefill): its seat gives no token
    meanwhile, and the mean is that much longer; the median of runs is
    not. A window without a token has no mean."""
    step_s, steps = 0.0125, 3200
    full = stepped_streams(steps, 16, step_s, lambda i, k: 1)
    away = stepped_streams(steps, 16, step_s,
                           lambda i, k: int(i > 0 or k >= steps // 4))
    window = (0.0, steps * step_s + 1e-3)
    assert mean_ms(away, 16, window) / mean_ms(full, 16, window) \
        == pytest.approx(16 / (15 + 0.75), rel=1e-3)
    assert tpot_ms(away)[0] == pytest.approx(tpot_ms(full)[0], rel=1e-6)
    assert serve_cell.tpot_mean(full, (-2.0, -1.0), 16) is None
    # Only tokens inside the window count, and the window's own length.
    half = (0.0, steps * step_s / 2 + 1e-3)
    assert mean_ms(full, 16, half) == pytest.approx(1e3 * step_s, rel=1e-3)


def test_a_run_does_not_span_two_requests_and_skips_a_first_token():
    """Two requests of one client back to back, 100 tokens each, the
    second's first token 5 s after the first's last (queueing, prefill):
    a run each, 35 tokens of remainder each dropped, no run across."""
    first = stream_of([0.01 * k for k in range(100)])
    second = stream_of([6.0 + 0.02 * k for k in range(100)], 1)
    runs = serve_cell.tpot_runs([first, second], (-1.0, 100.0))
    assert runs == [pytest.approx(0.01), pytest.approx(0.02)]
    # The token before a run may lie before the window, as a gap's may:
    # the window opens after token 10, the run is tokens 11..74.
    runs = serve_cell.tpot_runs([first], (0.105, 100.0))
    assert runs == [pytest.approx(0.01)]
    # ... and nothing after the window's close counts.
    assert serve_cell.tpot_runs([first], (0.105, 0.70)) == []


@pytest.mark.parametrize("tokens,runs", [(1, 0), (64, 0), (65, 1),
                                         (128, 1), (129, 2)])
def test_a_stream_with_fewer_than_65_tokens_in_the_window_gives_no_run(
        tokens, runs):
    s = stream_of([0.01 * (k + 1) for k in range(tokens)])
    assert len(serve_cell.tpot_runs([s], (0.0, 100.0))) == runs


def test_the_run_length_is_the_modules_constant(monkeypatch):
    assert serve_cell.TPOT_RUN == 64
    s = stream_of([0.01 * k for k in range(17)])
    assert serve_cell.tpot_runs([s], (-1.0, 1.0)) == []
    monkeypatch.setattr(serve_cell, "TPOT_RUN", 8)   # the CPU rehearsals'
    assert serve_cell.tpot_runs([s], (-1.0, 1.0)) \
        == [pytest.approx(0.01)] * 2
