"""The readers of the program's step log (``steplog.py`` and the ten
``layer_metrics`` files that call it): on a CPU rehearsal of a closed
cell, where the six medians are numbers and the four idle shares are left
out for want of a device trace; and on ``recorded_trace.json`` with a
hand-made step log, where the four idle shares must sum to the trace's
idle share and a log that cannot be trusted gives nothing.
"""

import json
import os
import statistics
import types

import pytest

from perfbench import byname, probe, run, steplog
from perfbench import trace_reduce as tr
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import (CELLS, REHEARSAL, SEED,
                                            benchmark_with)

HERE = os.path.dirname(os.path.abspath(__file__))
MEDIANS = ("sched_ms_p50", "decode_launch_ms_p50", "decode_wait_ms_p50",
           "decode_sample_ms_p50", "prefill_ms_p50", "step_gap_ms_p50")
IDLE = ("idle_pct.launch", "idle_pct.wait", "idle_pct.sample",
        "idle_pct.between_steps")
CLOCK = 1000.0  # perf_counter reads this much more than the trace's clock


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


def test_rehearsal_gives_the_six_medians_and_no_idle_share(tmp_path, capsys):
    bench = benchmark_with(CELLS)
    result = run.run_cell(bench, [REHEARSAL, run.HERE], "tiny-closed", SEED,
                          2.0, True, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    got = result["metrics"]
    for name in MEDIANS:
        assert got[name]["unit"] == "ms" and got[name]["value"] > 0, name
    assert not set(IDLE) & set(got)
    # The log is the engine's own, whole, on the window's clock, and tells
    # the same story as the benchmark's span around ``step``: a step that
    # only decoded is its scheduler and the three parts of its decode.
    engine = probe.ProbedEngine.instances[-1]
    log = engine.step_log()
    assert log["oldest_start"] == log["steps"][0]["start"]
    assert len(log["steps"]) == len(engine.steps)
    for record, seen in zip(log["steps"], engine.steps):
        assert seen.start <= record["start"] <= record["end"] <= seen.end
        assert record["decodes"] == seen.decodes
        assert record["live_pages"] == seen.live_pages
        assert len(record.get("prefills", ())) == seen.prefills
    assert any(s.get("prefills") for s in log["steps"])
    assert all(s["compiled"] == 0 for s in log["steps"][-20:])
    only_decoded = [s for s in log["steps"][-100:]
                    if s["decodes"] and not s.get("prefills")]
    assert only_decoded
    # The four phases tile the step: in order, none overlapping the next,
    # all inside it. What they leave uncovered is a few microseconds of
    # the step's own; on a loaded machine a thread is descheduled between
    # two phases now and then, so the time is judged by the median.
    tiling = ("infer.schedule", "infer.decode.launch", "infer.decode.wait",
              "infer.decode.sample")
    uncovered = []
    for record in only_decoded:
        phases = [p for p in record["phases"] if p[0] in tiling]
        assert tuple(p[0] for p in phases) == tiling
        edges = [record["start"]] + [t for _, t0, t1 in phases
                                     for t in (t0, t1)] + [record["end"]]
        assert edges == sorted(edges), record
        uncovered.append(record["end"] - record["start"]
                         - steplog.phase_seconds(record, tiling))
    assert 0 <= statistics.median(uncovered) < 1e-3
    # The window line names the longest time between two steps and the
    # loop's phase over it.
    window = next(json.loads(line.split(" ", 2)[2])
                  for line in capsys.readouterr().out.splitlines()
                  if line.startswith("[perfbench] window "))
    in_window = log["steps"][window["first_step_index"]:
                             window["last_step_index"] + 1]
    assert window["engine_steps"] == len(in_window)
    assert window["largest_step_gap_ms"] == pytest.approx(1e3 * max(
        b["start"] - a["end"] for a, b in zip(in_window, in_window[1:])))
    assert window["largest_step_gap_phase"] in (
        "none", "serve.llm.lock_wait", "serve.llm.publish")
    assert window["longest_step_ms"] == pytest.approx(1e3 * max(
        s["end"] - s["start"] for s in in_window))
    assert window["longest_step_phase"].startswith("infer.")


def test_a_window_longer_than_the_programs_ring_is_still_read(
        tmp_path, monkeypatch):
    """The program's recorder is a ring; the probe takes each record as
    its step ends. With a ring of 48 records and a window of some
    hundreds of steps the readers still see every step of the window."""
    from raytpu.util import tracing

    plain = probe.ProbedEngine.__init__

    def with_a_small_ring(self, *args, **kwargs):
        plain(self, *args, **kwargs)
        self.recorder = tracing.StepRecorder(maxlen=48)

    monkeypatch.setattr(probe.ProbedEngine, "__init__", with_a_small_ring)
    result = run.run_cell(benchmark_with(CELLS), [REHEARSAL, run.HERE],
                          "tiny-closed", SEED, 2.0, True, require_tpu=False,
                          work_dir=str(tmp_path))
    engine = probe.ProbedEngine.instances[-1]
    assert len(engine.recorder) == 48 < len(engine.steps)
    log = engine.step_log()
    assert [s["start"] for s in log["steps"]] == sorted(
        s["start"] for s in log["steps"])
    assert len(log["steps"]) == len(engine.steps)
    for name in ("step_gap_ms_p50", "decode_launch_ms_p50"):
        assert result["metrics"][name]["value"] > 0, name


def test_the_longest_gap_between_two_steps_and_its_phase():
    steps = [step_record(0.01 * i, 0.01 * i + 0.009) for i in range(6)]
    # Ordinary gaps of 1 ms, of which the lock wait covers 0.27.
    assert steplog.largest_step_gap(steps) == (pytest.approx(1.0), "none")
    # A stall of 2 s before the fourth step, spent waiting for the lock.
    late = step_record(2.03, 2.039)
    late["phases"][0] = ["serve.llm.lock_wait", steps[2]["end"] + 1e-4,
                         late["start"] - 1e-5]
    stalled = steps[:3] + [late]
    gap, phase = steplog.largest_step_gap(stalled)
    assert gap == pytest.approx(1e3 * (late["start"] - steps[2]["end"]))
    assert phase == "serve.llm.lock_wait"
    # The same stall with nothing open over it: the thread did not run.
    late["phases"][0] = ["serve.llm.lock_wait", late["start"] - 1e-4,
                         late["start"] - 1e-5]
    assert steplog.largest_step_gap(stalled)[1] == "none"
    # No log, or a single step: nothing to read.
    assert steplog.largest_step_gap([]) == (None, None)
    assert steplog.largest_step_gap(steps[:1]) == (None, None)


def test_the_longest_step_and_the_phase_it_spent_most_in():
    """A pause inside a step is no gap between steps: here the wait for
    the device took 130 ms where it takes 5.4."""
    steps = [step_record(0.01 * i, 0.01 * i + 0.009) for i in range(4)]
    assert steplog.longest_step(steps) == (pytest.approx(9.0),
                                           "infer.decode.wait")
    paused = step_record(0.04, 0.049)
    end = paused["end"] + 0.125
    for phase in paused["phases"]:
        if phase[0] in ("infer.decode", "infer.decode.launch"):
            phase[2] = end if phase[0] == "infer.decode" else end - 0.001
        elif phase[0] == "infer.decode.wait":
            phase[1] = phase[2] = end  # the pause fell in the launch
    paused["phases"] = [p for p in paused["phases"]
                        if p[0] not in ("infer.decode.sample",
                                        "serve.llm.publish")]
    paused["end"] = end
    assert steplog.longest_step(steps + [paused]) == (
        pytest.approx(134.0), "infer.decode.launch")
    assert steplog.longest_step([]) == (None, None)


# ---- a hand-made log against a recorded trace ------------------------------


def step_record(lo, hi, decodes=8):
    """A decode step filling the trace's span ``(lo, hi)``, on the
    program's clock, with the replica loop's two phases around it."""
    at = lambda x: CLOCK + lo + x * (hi - lo)  # noqa: E731
    return {
        "start": at(0.0), "end": at(1.0), "decodes": decodes,
        "phases": [["serve.llm.lock_wait", at(-0.0004), at(-0.0001)],
                   ["infer.schedule", at(0.01), at(0.02)],
                   ["infer.decode", at(0.02), at(0.999)],
                   ["infer.decode.launch", at(0.02), at(0.30)],
                   ["infer.decode.wait", at(0.30), at(0.90)],
                   ["infer.decode.sample", at(0.90), at(0.999)],
                   ["serve.llm.publish", at(1.0002), at(1.0005)]]}


class Engine:
    def __init__(self, steps, oldest=CLOCK - 1.0):
        # By default the ring still holds steps from before the window.
        self.log = {"steps": steps, "oldest_start": oldest}

    def step_log(self, since=0.0):
        return self.log


def run_data(trace, engine, monkeypatch, offsets=(3e-6, 5e-6)):
    """What ``run_cell`` hands the readers, for a trace whose
    ``pb.engine.step`` spans opened ``offsets`` after their stamps."""
    monkeypatch.setattr(probe.ProbedEngine, "instances", [engine])
    marks = tr.spans(trace, "pb.engine.step")
    traced = [types.SimpleNamespace(start=CLOCK + m.start - late)
              for m, late in zip(marks, offsets)]
    return RunData(cell={}, cfg={}, mix={}, family=None, chips=1, peaks=None,
                   window=(CLOCK, CLOCK + 1.0), end_to_end={},
                   memory_peak_bytes=0, trace=trace, traced_steps=traced)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return tr.Trace.from_json(json.load(f))


def recorded_steps(trace):
    return [step_record(m.start, m.end)
            for m in tr.spans(trace, "pb.engine.step")]


def test_idle_shares_sum_to_the_traces_idle_share(recorded, monkeypatch):
    data = run_data(recorded, Engine(recorded_steps(recorded)), monkeypatch)
    shares = {name: read(name, data) for name in IDLE}
    assert all(v is not None and v > 0 for v in shares.values()), shares
    assert sum(shares.values()) == pytest.approx(data.device_idle_pct(),
                                                 abs=1e-6)
    # The device runs from 34 % to 75 % of each step: idle before it is
    # the launch's (and the scheduler's), after it the wait's until 90 %.
    assert shares["idle_pct.launch"] > shares["idle_pct.sample"]
    assert shares["idle_pct.wait"] > shares["idle_pct.sample"]
    # Between the steps: the 6.36 us between the two spans, and the 4 us
    # (the median offset of the pairs) by which the first record opens
    # after the window does.
    first, second = tr.spans(recorded, "pb.engine.step")
    lo, hi = tr.window_of(recorded)
    assert shares["idle_pct.between_steps"] == pytest.approx(
        100 * (second.start - first.end + 4e-6) / (hi - lo), rel=1e-3)


def test_each_piece_of_a_gap_goes_to_the_phase_open_over_it(monkeypatch):
    """One step of 10 ms whose device works from 3 to 7 ms: the gap
    that begins in the wait is cut where the wait ends."""
    trace = tr.Trace(
        device={0: {"XLA Ops": [tr.Event("%fusion.1 = f32[8] fusion()",
                                         0.003, 0.007)]}},
        host={"python": [tr.Event("pb.engine.step", 0.0, 0.010)]})
    data = run_data(trace, Engine([step_record(0.0, 0.010)]), monkeypatch,
                    offsets=(0.0,))
    # schedule 0.1-0.2, launch 0.2-3.0, wait 3.0-9.0, sample 9.0-9.99 ms;
    # 0.1 ms before the schedule and 0.01 ms after the decode are the
    # step's own and count with the launch.
    assert read("idle_pct.launch", data) == pytest.approx(30.0 + 0.1)
    assert read("idle_pct.wait", data) == pytest.approx(20.0)
    assert read("idle_pct.sample", data) == pytest.approx(9.9)
    assert read("idle_pct.between_steps", data) == pytest.approx(0.0)
    assert sum(read(n, data) for n in IDLE) == pytest.approx(60.0)


def test_offsets_that_disagree_give_nothing(recorded, monkeypatch):
    engine = Engine(recorded_steps(recorded))
    data = run_data(recorded, engine, monkeypatch, offsets=(3e-6, 303e-6))
    assert steplog.clock_offset(data) is None
    assert [read(name, data) for name in IDLE] == [None] * 4
    # Within 200 us they agree, and the median is the offset.
    data = run_data(recorded, engine, monkeypatch, offsets=(3e-6, 103e-6))
    assert steplog.clock_offset(data) == pytest.approx(-CLOCK + 53e-6)
    assert read("idle_pct.wait", data) > 0


def test_one_late_span_among_many_does_not_decide(monkeypatch):
    """A span that opened 4 ms after its stamp (another thread held the
    interpreter) is the benchmark's delay, not the clocks'."""
    spans = [tr.Event("pb.engine.step", 0.01 * i, 0.01 * i + 0.009)
             for i in range(9)]
    trace = tr.Trace(
        device={0: {"XLA Ops": [tr.Event("%fusion.1 = f32[8] fusion()",
                                         s.start + 0.003, s.start + 0.007)
                                for s in spans]}},
        host={"python": spans})
    engine = Engine([step_record(s.start, s.end) for s in spans])
    late = [3e-6] * 9
    late[4] = 4e-3
    data = run_data(trace, engine, monkeypatch, offsets=late)
    assert steplog.clock_offset(data) == pytest.approx(-CLOCK + 3e-6)
    assert sum(read(n, data) for n in IDLE) == pytest.approx(
        data.device_idle_pct())


def test_a_truncated_log_gives_nothing(recorded, monkeypatch):
    steps = recorded_steps(recorded)
    # The ring's oldest record starts after the window opened.
    data = run_data(recorded, Engine(steps, oldest=CLOCK + 0.001),
                    monkeypatch)
    assert [read(name, data) for name in MEDIANS + IDLE] == [None] * 10
    data = run_data(recorded, Engine(steps), monkeypatch)
    assert all(read(name, data) is not None
               for name in MEDIANS if name != "prefill_ms_p50")


def test_a_program_without_a_step_log_gives_nothing(recorded, monkeypatch):
    """The parent of the PR that brought ``step_log()``: the readers
    return None and do not raise, and the line leaves them out."""
    data = run_data(recorded, object(), monkeypatch)
    assert [read(name, data) for name in MEDIANS + IDLE] == [None] * 10
    monkeypatch.setattr(probe.ProbedEngine, "instances", [])
    assert [read(name, data) for name in MEDIANS + IDLE] == [None] * 10


def test_medians_of_a_hand_made_window(monkeypatch):
    steps = [step_record(0.01 * i, 0.01 * i + 0.009) for i in range(5)]
    steps[2]["phases"].insert(2, ["infer.prefill", steps[2]["start"] + 1e-3,
                                  steps[2]["start"] + 3e-3])
    steps[2]["phases"].insert(3, ["infer.prefill_chunk",
                                  steps[2]["start"] + 3e-3,
                                  steps[2]["start"] + 4e-3])
    trace = tr.Trace(device={}, host={})
    data = run_data(trace, Engine(steps), monkeypatch, offsets=())
    assert read("sched_ms_p50", data) == pytest.approx(0.09)
    assert read("decode_launch_ms_p50", data) == pytest.approx(0.28 * 9)
    assert read("decode_wait_ms_p50", data) == pytest.approx(0.60 * 9)
    assert read("decode_sample_ms_p50", data) == pytest.approx(0.099 * 9)
    assert read("prefill_ms_p50", data) == pytest.approx(3.0)  # one step
    assert read("step_gap_ms_p50", data) == pytest.approx(1.0)
    assert [read(name, data) for name in IDLE] == [None] * 4
    # Only steps that ended inside the window count.
    data.window = (steps[1]["end"], steps[3]["end"])
    assert len(steplog.window_steps(data)) == 2


# ---- what tpot_p50_ms (per layer) has to agree with --------------------------------------------


def plain_steps(n, period, **fields):
    return [dict(start=k * period, end=k * period + 0.8 * period,
                 decodes=16, phases=[], **fields) for k in range(n)]


@pytest.mark.parametrize("emitted,tokens", [(None, 1.0), (16, 1.0),
                                            (22, 1.375), (32, 2.0)])
def test_decode_period_is_a_plain_steps_period_over_what_it_gave(emitted,
                                                                 tokens):
    """A program that does not draft has no ``emitted`` in its records:
    a token a sequence a step."""
    fields = {} if emitted is None else {"emitted": emitted, "drafted": 16}
    got = steplog.decode_period(plain_steps(50, 0.0125, **fields))
    assert got["step_period_ms_p50"] == pytest.approx(12.5)
    assert got["tokens_per_seq_step"] == pytest.approx(tokens)
    assert got["prefill_share_ms"] == 0.0
    assert set(got) == {"step_period_ms_p50", "tokens_per_seq_step",
                        "prefill_share_ms"}


def test_decode_period_spreads_the_prefill_steps_over_all():
    """One step in ten holds a chunk and takes 100 ms more: 10 ms a step
    on the plain period, which the median of the plain steps leaves out."""
    steps, now = [], 0.0
    for k in range(101):
        chunk = k % 10 == 5
        steps.append(dict(start=now, end=now + 0.010, decodes=32,
                          phases=[], **({"prefills": [{}]} if chunk else {})))
        now += 0.0125 + (0.100 if chunk else 0.0)
    got = steplog.decode_period(steps)
    assert got["step_period_ms_p50"] == pytest.approx(12.5)
    assert got["prefill_share_ms"] == pytest.approx(10.0)
    assert got["tokens_per_seq_step"] == 1.0


def test_decode_period_of_a_window_without_a_plain_step_is_nothing():
    for steps in ([], plain_steps(1, 0.0125),
                  [dict(start=0.0, end=1.0, decodes=0, phases=[]),
                   dict(start=1.0, end=2.0, decodes=0, phases=[])]):
        assert set(steplog.decode_period(steps).values()) == {None}
