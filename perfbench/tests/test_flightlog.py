"""``perfbench/flightlog.py`` and the nine ``layer_metrics`` files that
call it, on hand-made step logs and a ``Trace.from_json`` trace: a chip
that sets the pace behind a host a few milliseconds ahead, with one pause
of the collector in it; the same with the log shifted by one step, with
a decode's event lost or one too many, with records that carry no
ordinals (the parent);
and an engine that drafts, which keeps nothing in flight.

The pure functions' cases are collected into tier 1 by
``tests/test_flightlog_cases.py``.
"""

import types

import pytest

from perfbench import byname, flightlog, probe, run
from perfbench import trace_reduce as tr
from perfbench.rundata import RunData

CLOCK = 5000.0     # the log's clock where the trace's reads 0
MS = 1e-3
PERIOD = 10 * MS
N0 = 41            # the first traced record's decode
STEPS = 24
# A stall before the launch of step 12: the collector held the thread.
PAUSED_STEP, PAUSE_S = 12, 0.140
NAMES = ("gc_pause_pct", "gc_full_collections", "step_offcpu_ms_p50",
         "idle_long_gaps_pct", "idle_in_gc_pct", "device_step_ms_p50",
         "host_lead_ms_p50", "fetch_lag_ms_p50", "decode_carried_pct")


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


def at(k):
    """When step ``k`` starts on the trace's clock: a period a step, and
    the pause before step ``PAUSED_STEP``'s launch moves what follows."""
    return k * PERIOD + (PAUSE_S if k > PAUSED_STEP else 0.0)


def plain_step(k, carried=0, prefills=None):
    """Step ``k`` of an engine with one decode in flight, on the log's
    clock: it dispatches decode ``N0 + k`` 1.5 ms in and then waits for
    decode ``N0 + k - 1``, which the chip ends 4.9 ms in."""
    t0 = CLOCK + at(k)
    late = PAUSE_S if k == PAUSED_STEP else 0.0  # inside the launch
    ms = lambda x, moved=late: t0 + x * MS + moved  # noqa: E731
    record = {
        "start": t0, "end": ms(5.7), "decodes": 8, "ahead": 1,
        "dispatched": N0 + k, "fetched": N0 + k - 1, "carried": carried,
        # The thread ran through the step but for 0.2 ms (and the pause).
        "cpu_s": 2.0 * MS, "wait_cpu_s": 0.1 * MS,
        "phases": [["infer.schedule", ms(0.1, 0.0), ms(0.2, 0.0)],
                   ["infer.decode", ms(0.2, 0.0), ms(5.6)],
                   ["infer.decode.launch", ms(0.2, 0.0), ms(1.5)],
                   ["infer.decode.wait", ms(1.5), ms(5.1)],
                   ["infer.decode.sample", ms(5.1), ms(5.6)]]}
    if prefills:
        record["prefills"] = prefills
    return record


def plain_modules(steps=STEPS):
    """Decode ``N0 + k`` runs from 5.0 to 14.5 ms after step ``k``'s
    start and its sampler to 14.9: the chip sets the pace, idle 0.1 ms a
    step, and the pause leaves it idle until the late launch arrives."""
    events = []
    for k in range(-1, steps):
        start = at(k) + 5.0 * MS
        if k == PAUSED_STEP:  # dispatched late: 0.1 ms after its launch
            start = at(k) + PAUSE_S + 1.6 * MS
        events.append(["jit__decode(77)", start, start + 9.5 * MS])
        events.append(["jit__sample(78)", start + 9.5 * MS,
                       start + 9.9 * MS])
    return events


def trace_of(modules, spans):
    return tr.Trace.from_json({
        "device": {"0": {"XLA Modules": modules}},
        "host": {"python": [["pb.engine.step", s, e] for s, e in spans]}})


class Engine:
    def __init__(self, steps):
        self.log = {"steps": steps, "oldest_start": CLOCK - 1.0}

    def step_log(self, since=0.0):
        return self.log


def run_data(monkeypatch, steps, modules, pauses, traced=None,
             clock=CLOCK):
    """What ``run_cell`` hands the readers: the records ``traced`` (all
    by default) under ``pb.engine.step`` spans that opened 4 us after
    their stamps, the log's clock ``clock`` over the trace's."""
    from raytpu.util import tracing

    monkeypatch.setattr(probe.ProbedEngine, "instances", [Engine(steps)])
    monkeypatch.setattr(tracing, "host_pauses",
                        lambda since=0.0: [p for p in pauses
                                           if p[2] > since], raising=False)
    traced = steps if traced is None else traced
    spans = [(s["start"] - clock, s["end"] - clock + 2e-6) for s in traced]
    return RunData(
        cell={}, cfg={}, mix={}, family=None, chips=1, peaks=None,
        window=(steps[0]["start"] - 1e-6, steps[-1]["end"]), end_to_end={},
        memory_peak_bytes=0, trace=trace_of(modules, spans),
        traced_steps=[types.SimpleNamespace(start=s["start"] - 4e-6)
                      for s in traced])


def the_pause():
    t0 = CLOCK + at(PAUSED_STEP) + 0.4 * MS
    return ["host.gc", t0, t0 + PAUSE_S - 0.5 * MS,
            {"generation": 2, "collected": 1234, "stepping": True}]


def steady(carried=()):
    steps = [plain_step(k, carried=int(k in carried)) for k in range(STEPS)]
    # The paused step's thread did not run while the collector did not
    # either: its CPU time holds the collection.
    steps[PAUSED_STEP]["cpu_s"] += PAUSE_S - 0.5 * MS
    return steps


# ---- the pure functions --------------------------------------------------------


def test_gc_seconds_are_clipped_to_the_window_and_full_ones_counted():
    pauses = [["host.gc", 1.0, 1.2, {"generation": 2}],
              ["host.gc", 2.0, 2.004, {"generation": 1}],
              ["host.other", 2.5, 2.9, {}],
              ["host.gc", 3.9, 4.1, {"generation": 2}]]
    assert flightlog.gc_seconds(pauses, (1.1, 4.0)) \
        == pytest.approx(0.1 + 0.004 + 0.1)
    # Counted where it ended: the last one ended past the window.
    assert flightlog.gc_full_collections(pauses, (1.1, 4.0)) == 1
    assert flightlog.gc_full_collections(pauses, (0.0, 5.0)) == 2
    assert flightlog.gc_seconds([], (0.0, 1.0)) == 0.0


def test_off_cpu_time_is_what_the_wall_holds_beyond_the_cpu_outside_the_wait():
    step = plain_step(3)
    # 5.7 ms of wall, 3.6 of them the wait; 2.0 ms of CPU, 0.1 in the wait.
    assert flightlog.offcpu_seconds(step) == pytest.approx(0.2 * MS)
    # A thread that burned CPU in the wait (it span) is not off the CPU
    # outside it; and a record without the field says nothing.
    step["wait_cpu_s"] = 3.6 * MS
    step["cpu_s"] = 5.7 * MS
    assert flightlog.offcpu_seconds(step) == pytest.approx(0.0)
    del step["cpu_s"]
    assert flightlog.offcpu_seconds(step) is None
    steps = steady()
    steps[5]["prefills"] = [{"tokens": 9}]
    steps[5]["cpu_s"] = 0.0  # not a plain step: left out
    # Fewer steps than a block are one block, the paused step's 0.7 ms
    # in its mean.
    assert flightlog.step_offcpu_ms_p50(steps) \
        == pytest.approx((22 * 0.2 + 0.7) / 23)
    assert flightlog.step_offcpu_ms_p50(
        [{k: v for k, v in s.items() if k != "cpu_s"}
         for s in steps]) is None


def test_off_cpu_time_is_read_over_blocks_so_a_ticking_clock_says_the_same():
    """On the chip's host the thread's CPU clock ticks every 10 ms: a
    step of 2 ms of CPU reads 0 four times in five and a whole tick the
    fifth. Over blocks the reading is the fine clock's; over single
    steps it would be the wall time (2.1 ms), and cut at 0 a step it
    would be biased."""
    fine = [plain_step(k % PAUSED_STEP) for k in range(5 * 64)]
    ticking = [dict(s, cpu_s=(10 * MS if k % 5 == 4 else 0.0),
                    wait_cpu_s=0.0) for k, s in enumerate(fine)]
    # 2.1 ms of wall outside the wait, 2.0 ms of CPU a step on average
    # (blocks of 60 hold twelve ticks each; of 64, twelve or thirteen,
    # which is the reading's resolution there: 10 ms / 64).
    assert flightlog.step_offcpu_ms_p50(ticking, block=60) \
        == pytest.approx(0.1)
    assert flightlog.step_offcpu_ms_p50(ticking) \
        == pytest.approx(0.1, abs=10.0 / 64)
    assert flightlog.step_offcpu_ms_p50(fine) == pytest.approx(0.2)
    # A pause in one block moves that block's mean and not the median.
    paused = [dict(s) for s in fine]
    paused[70]["end"] += 0.14
    assert flightlog.step_offcpu_ms_p50(paused) == pytest.approx(0.2)
    # A short last block is left out; fewer steps than a block are one.
    assert flightlog.step_offcpu_ms_p50(fine[:64 + 9]) == pytest.approx(0.2)
    assert flightlog.step_offcpu_ms_p50(fine[:9]) == pytest.approx(0.2)


def test_carried_is_a_share_of_the_steps_that_went_out_ahead():
    steps = steady(carried=(2, 3, 9))
    steps[0]["ahead"] = 0  # built from the host's tokens: not counted
    assert flightlog.decode_carried_pct(steps) \
        == pytest.approx(100.0 * 3 / (STEPS - 1))
    assert flightlog.decode_carried_pct(
        [dict(s, ahead=0) for s in steps]) is None  # a drafting engine
    parent = [{k: v for k, v in s.items() if k != "carried"} for s in steps]
    assert flightlog.decode_carried_pct(parent) is None


def test_long_gaps_and_what_covers_them():
    gaps = [(0.0, 0.004), (1.0, 1.150), (2.0, 2.0101), (3.0, 3.01)]
    assert flightlog.long_gaps(gaps) == [(1.0, 1.150), (2.0, 2.0101)]
    cover = [(0.9, 1.1), (1.12, 1.2), (2.005, 2.006)]
    assert flightlog.covered_seconds(gaps, cover) \
        == pytest.approx(0.1 + 0.03 + 0.001)
    assert flightlog.covered_seconds(gaps, []) == 0.0


def test_a_module_events_name():
    assert flightlog.module_name("jit__decode(6103778470494593253)") \
        == "_decode"
    assert flightlog.module_name("jit__sample(12)") == "_sample"
    assert flightlog.module_name("jit__prefill") == "_prefill"
    assert flightlog.module_name("jit_convert_element_type(3)") is None


def events(modules):
    return [tr.Event(*m) for m in modules]


def test_pairing_by_ordinals_reads_the_chips_step_the_lead_and_the_lag():
    steps = steady()
    decodes = flightlog.pair(steps, events(plain_modules()), -CLOCK,
                             steps[0]["start"])
    assert [d.n for d in decodes] == list(range(N0, N0 + STEPS))
    assert flightlog.device_step_ms_p50(decodes) == pytest.approx(10.0)
    # Dispatched 1.5 ms into its step, begun 5.0 ms in.
    assert flightlog.host_lead_ms_p50(decodes) == pytest.approx(3.5)
    # The sampler ends 4.9 ms into the next step, whose wait ends at 5.1.
    assert flightlog.fetch_lag_ms_p50(decodes) == pytest.approx(0.2)
    # The decode dispatched late began 0.1 ms after its launch's end.
    late = decodes[PAUSED_STEP]
    assert late.start - late.launch_ended == pytest.approx(0.1 * MS)
    # The last traced decode has no next one and was fetched by no record.
    assert decodes[-1].next_start is None
    assert decodes[-1].wait_ended is None


def test_only_the_traced_records_decodes_are_paired():
    steps = steady()
    decodes = flightlog.pair(steps, events(plain_modules()), -CLOCK,
                             steps[4]["start"], traced_steps=6)
    # The decode in flight when tracing began (N0 + 3) started before the
    # first traced launch and is no pair; the six that follow are.
    assert [d.n for d in decodes] == list(range(N0 + 4, N0 + 10))
    assert flightlog.device_step_ms_p50(decodes) == pytest.approx(10.0)


def test_a_step_that_prefilled_is_left_out_of_its_medians():
    steps = steady()
    steps[6]["prefills"] = [{"tokens": 300}]
    decodes = flightlog.pair(steps, events(plain_modules()), -CLOCK,
                             steps[0]["start"])
    by_n = {d.n: d for d in decodes}
    # Its own decode is no plain step's; the period of the decode before
    # it holds its prefill.
    assert not by_n[N0 + 6].plain and by_n[N0 + 6].next_plain
    assert by_n[N0 + 5].plain and not by_n[N0 + 5].next_plain


@pytest.mark.parametrize("how", [
    "fetched_a_step_early", "an_event_too_many", "an_event_lost",
    "no_ordinals", "no_decode_traced"])
def test_a_pairing_that_does_not_hold_reads_nothing(how):
    steps, modules = steady(), plain_modules()
    if how == "fetched_a_step_early":
        # A log shifted by one step, as a reader that pairs by position
        # takes it: every record is to have fetched the decode it had
        # just dispatched, which the chip ends a period later.
        steps = [dict(s, fetched=s["dispatched"]) for s in steps]
    elif how == "an_event_too_many":
        # Shifted the other way: a decode program the records do not
        # know ran first, so each event is the decode before its
        # record's and began before the launch that is to have
        # dispatched it.
        modules = modules + [["jit__decode(77)", at(0) + 0.3 * MS,
                              at(0) + 0.4 * MS]]
    elif how == "an_event_lost":
        # From the fifth on, an event is the decode after its record's:
        # it ends a period after the wait that is to have fetched it.
        modules = [m for m in modules
                   if not (m[0].startswith("jit__decode")
                           and abs(m[1] - (at(4) + 5.0 * MS)) < 1e-9)]
    elif how == "no_ordinals":
        steps = [{k: v for k, v in s.items()
                  if k not in ("dispatched", "fetched", "carried")}
                 for s in steps]
    else:
        modules = [m for m in modules if not m[0].startswith("jit__decode")]
    assert flightlog.pair(steps, events(modules), -CLOCK,
                          steps[0]["start"]) is None


def test_a_few_broken_pairs_do_not_take_the_rest_away():
    steps = steady()
    # One record's wait stamped before its decode's sampler ended.
    steps[7]["phases"][3][2] -= 1.0 * MS
    decodes = flightlog.pair(steps, events(plain_modules()), -CLOCK,
                             steps[0]["start"])
    assert decodes is not None and len(decodes) == STEPS


def drafting_step(k):
    """A step of an engine that drafts: the three programs go out inside
    the launch, start as they are enqueued, and the wait blocks on them."""
    t0 = CLOCK + k * PERIOD
    ms = lambda x: t0 + x * MS  # noqa: E731
    return {"start": t0, "end": ms(9.6), "decodes": 8, "ahead": 0,
            "dispatched": N0 + k, "fetched": N0 + k, "carried": 0,
            "drafted": 8, "accepted": 5, "emitted": 13,
            "cpu_s": 3.0 * MS, "wait_cpu_s": 0.1 * MS,
            "phases": [["infer.schedule", ms(0.1), ms(0.2)],
                       ["infer.decode", ms(0.2), ms(9.5)],
                       ["infer.decode.launch", ms(0.2), ms(2.4)],
                       ["infer.decode.verify", ms(1.0), ms(1.6)],
                       ["infer.decode.accept", ms(1.6), ms(1.9)],
                       ["infer.decode.draft", ms(1.9), ms(2.3)],
                       ["infer.decode.wait", ms(2.4), ms(8.9)],
                       ["infer.decode.sample", ms(8.9), ms(9.5)]]}


def drafting_modules(steps=STEPS):
    out = []
    for k in range(steps):
        t0 = k * PERIOD
        out += [["jit__decode(5)", t0 + 1.2 * MS, t0 + 6.2 * MS],
                ["jit__accept(6)", t0 + 6.2 * MS, t0 + 6.5 * MS],
                ["jit__draft(7)", t0 + 6.5 * MS, t0 + 8.6 * MS]]
    return out


def test_an_engine_that_drafts_has_no_lead_and_fetches_its_own_decode():
    steps = [drafting_step(k) for k in range(STEPS)]
    decodes = flightlog.pair(steps, events(drafting_modules()), -CLOCK,
                             steps[0]["start"])
    assert [d.n for d in decodes] == list(range(N0, N0 + STEPS))
    # The verify program begins inside the launch that enqueued it.
    assert flightlog.host_lead_ms_p50(decodes) == 0.0
    assert flightlog.device_step_ms_p50(decodes) == pytest.approx(10.0)
    # The draft program ends 8.6 ms in, the wait 8.9.
    assert flightlog.fetch_lag_ms_p50(decodes) == pytest.approx(0.3)
    assert flightlog.decode_carried_pct(steps) is None


# ---- through the readers' files ------------------------------------------------


def test_the_nine_readers_on_a_run_with_one_pause(monkeypatch):
    steps = steady(carried=(3, 4))
    data = run_data(monkeypatch, steps, plain_modules(), [the_pause()])
    got = {name: read(name, data) for name in NAMES}
    assert all(v is not None for v in got.values()), got
    window = data.window[1] - data.window[0]
    assert got["gc_pause_pct"] \
        == pytest.approx(100.0 * (PAUSE_S - 0.5 * MS) / window)
    assert got["gc_full_collections"] == 1
    assert got["step_offcpu_ms_p50"] == pytest.approx(
        (23 * 0.2 + 0.7) / 24, abs=1e-6)
    assert got["decode_carried_pct"] == pytest.approx(100.0 * 2 / STEPS)
    assert got["device_step_ms_p50"] == pytest.approx(10.0, abs=1e-6)
    assert got["host_lead_ms_p50"] == pytest.approx(3.5, abs=1e-2)
    assert got["fetch_lag_ms_p50"] == pytest.approx(0.2, abs=1e-2)
    # One long gap: the chip ended decode N0 + 11 and its sampler 4.9 ms
    # into the paused step and got the next 1.6 ms after the pause. The
    # collector ran 0.4 ms into the step to 0.1 ms before the pause's
    # end, and the chip was busy over its first 4.5 ms.
    idle = data.device_idle_pct()
    traced = tr.window_of(data.trace)
    gap = PAUSE_S + 1.6 * MS - 4.9 * MS
    assert got["idle_long_gaps_pct"] \
        == pytest.approx(100.0 * gap / (traced[1] - traced[0]))
    assert got["idle_in_gc_pct"] == pytest.approx(
        100.0 * (PAUSE_S - 0.1 * MS - 4.9 * MS) / (traced[1] - traced[0]),
        rel=1e-3)
    assert 0 < got["idle_in_gc_pct"] < got["idle_long_gaps_pct"] < idle
    assert flightlog.gc_by_generation(data) == {"2": {
        "count": 1, "seconds": pytest.approx(PAUSE_S - 0.5 * MS),
        "longest_s": pytest.approx(PAUSE_S - 0.5 * MS), "stepping": 1}}
    # And the list for PERF.md names the gap's cover.
    (named,) = flightlog.named_gaps(data)
    assert named["seconds"] == pytest.approx(gap, rel=1e-3)
    assert named["gc"][0][0] == 2 and named["gc"][0][2] is True
    assert [s["dispatched"] for s in named["steps"]] == [N0 + PAUSED_STEP]
    assert named["steps"][0]["phase"] == "infer.decode.launch"


def test_the_parents_records_and_module_give_nothing(monkeypatch):
    """A program without the fields and without ``host_pauses``: every
    reader leaves its metric out, and none raises."""
    from raytpu.util import tracing

    new = ("dispatched", "fetched", "carried", "cpu_s", "wait_cpu_s")
    steps = [{k: v for k, v in s.items() if k not in new} for s in steady()]
    data = run_data(monkeypatch, steps, plain_modules(), [])
    monkeypatch.delattr(tracing, "host_pauses")
    got = {name: read(name, data) for name in NAMES}
    # The trace alone still says how much of the idle time is long gaps.
    assert got.pop("idle_long_gaps_pct") > 0
    assert all(v is None for v in got.values()), got
    assert flightlog.named_gaps(data) is None


def test_without_a_trace_the_hosts_four_are_read(monkeypatch):
    data = run_data(monkeypatch, steady(carried=(5,)), plain_modules(),
                    [the_pause()])
    data.trace, data.traced_steps = None, []
    got = {name: read(name, data) for name in NAMES}
    assert {n for n, v in got.items() if v is not None} \
        == set(flightlog.HOST_ONLY)
    assert flightlog.host_metrics(data) == {
        name: float(got[name]) for name in flightlog.HOST_ONLY}


def test_a_ring_that_dropped_the_windows_first_pauses_reads_nothing(
        monkeypatch):
    from raytpu.util import tracing

    steps = steady()
    full = [["host.gc", steps[2]["start"] + i * 1e-3,
             steps[2]["start"] + i * 1e-3 + 2e-3, {"generation": 0}]
            for i in range(tracing.PAUSE_RING)]
    data = run_data(monkeypatch, steps, plain_modules(), full)
    assert read("gc_pause_pct", data) is None
    assert read("gc_full_collections", data) is None
    # The same ring with an entry from before the window is whole.
    full[0] = ["host.gc", steps[0]["start"] - 2.0, steps[0]["start"] - 1.9,
               {"generation": 2}]
    assert read("gc_pause_pct", data) > 0
    assert read("gc_full_collections", data) == 0


def test_benchmark_json_enters_each_reader_twice():
    import json
    import os

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    serving = {w["name"] for w in bench["workloads"]} \
        - {"medium-train", "xl-train-fsdp4"}
    for name in NAMES:
        short, held = by_name[name], by_name[name + ".long"]
        assert short["moves"] == "itl_p95_ms"
        assert held["moves"] == "tpot_mean_ms"
        # A model that drafts keeps nothing in flight: no record of it
        # has ``ahead`` 1, the reader finds nothing to read, and a cell
        # is listed only where the reader reads.
        silent = {"kexaone-selfdraft-decode"} \
            if name == "decode_carried_pct" else set()
        assert set(short["workloads"]) | set(held["workloads"]) \
            == serving - silent
        assert not set(short["workloads"]) & set(held["workloads"])
        assert {k: short[k] for k in ("unit", "better", "source", "layer")} \
            == {k: held[k] for k in ("unit", "better", "source", "layer")}
        assert byname.load_reader([run.HERE], name + ".long") \
            is byname.load_reader([run.HERE], name)
    assert by_name["host_lead_ms_p50"]["better"] == "higher"
