"""The reduction from trace to numbers, on a small recorded trace and on
hand-made intervals.

``recorded_trace.json`` is two decode steps of a two-layer GPT-2 at XL's
widths (batch 8, four pages a sequence) cut from a trace taken on the
v5e in PR 23, instruction names shortened to 140 characters. The expected
busy time, window and paged-kernel time below were computed once from its
raw rows by a sweep over sorted end points, not by the code under test.
The window is that of its two ``pb.engine.step`` spans, 0.0005 to
0.018156709 s: the device is idle for 3 ms before the first step's first
operation and for 2.3 ms after the second's last, and both count.
"""

import json
import os

import pytest

from perfbench import trace_reduce as tr
from perfbench.trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return Trace.from_json(json.load(f))


def test_recorded_trace_busy_window_and_kernel_time(recorded):
    lo, hi = tr.window_of(recorded)
    assert (lo, hi) == (0.0005, 0.018156709)
    assert tr.busy_seconds(recorded) == pytest.approx(0.007227865, rel=1e-6)

    def is_paged(e):
        return tr.op_head(e.name).startswith("_paged_pallas")

    paged = tr.kernel_events(recorded, is_paged)
    assert len(paged[0]) == 4            # two layers, two steps
    assert sum(e.seconds for e in paged[0]) == pytest.approx(
        9.0223e-05, rel=1e-4)
    assert all(tr.is_pallas(e) for e in paged[0])
    # Only the kernels of the first step's span.
    first = tr.spans(recorded, "pb.engine.step")[0]
    inside = tr.kernel_events(recorded, is_paged,
                              within=[(first.start, first.end)])
    assert [round(e.start, 6) for e in inside[0]] == [0.00508, 0.006172]


def test_paged_reader_counts_bytes_and_time_of_the_same_steps(recorded):
    """Three steps opened a span, the profiler stopped during the third
    and its span is not in the trace: kernels and bytes are those of the
    first two. Two layers, 8 sequences of 4 live pages a step."""
    import types

    from perfbench import byname, peaks
    from perfbench.rundata import RunData

    bench = os.path.dirname(HERE)
    cfg = dict(byname.load_json([bench], "configs", "gpt2-xl"), n_layer=2)
    step = types.SimpleNamespace(traced=True, decodes=8, live_pages=32)
    run = RunData(
        cell={}, cfg=cfg, mix={"engine_options": {"page_size": 16}},
        family=byname.load_family([bench], cfg), chips=1,
        peaks=peaks.peaks_for("TPU v5 lite"), window=(0, 1), end_to_end={},
        memory_peak_bytes=0, trace=recorded, traced_steps=[step] * 3)
    # K and V of 32 pages x 16 tokens x 25 heads x 64 x 2 bytes, 2 layers,
    # 2 steps = 13,107,200 bytes = 16.004 us at 819 GB/s, of 90.223 us.
    share = byname.load_reader([bench], "paged_attn_roofline").read(run)
    assert share == pytest.approx(100 * 13_107_200 / 819e9 / 9.0223e-05,
                                  rel=1e-4)
    assert share == pytest.approx(17.74, abs=0.01)
    assert run.device_idle_pct() == pytest.approx(
        100 * (1 - 0.007227865 / 0.017656709), rel=1e-6)


def test_a_device_idle_at_the_windows_edges_counts_as_idle():
    """What the first-to-last-device-event window hid: 1 s of work in a
    10 s step is 90 % idle, not 0 %."""
    t = Trace(device={0: {tr.OPS_LINE: [Event("%fusion.1 = f32[2] "
                                              "fusion(...)", 4.0, 5.0)]}},
              host={"main": [Event("pb.train.step", 0.0, 10.0)]})
    assert tr.window_of(t) == (0.0, 10.0)
    assert tr.busy_seconds(t) == pytest.approx(1.0)
    gaps = dict(tr.idle_gaps(t))
    assert gaps["total: inside pb.train.step"] == pytest.approx(9.0)
    # Without any span there is only the device's own extent to go by.
    assert tr.window_of(Trace(device=t.device, host={})) == (4.0, 5.0)


def test_recorded_trace_breakdown(recorded):
    ops = tr.heaviest_ops(recorded)
    assert 1 <= len(ops) <= 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    assert ops[0][0].startswith("copy bf16[513,16,25,64]")  # the pool copy
    # Self times partition the busy time: no instruction is counted twice.
    total = sum(t for _, t in tr.self_times(recorded.device[0][tr.OPS_LINE]))
    assert total == pytest.approx(tr.busy_seconds(recorded), rel=1e-6)
    gaps = tr.idle_gaps(recorded)
    assert len(gaps) <= 10
    idle = sum(sec for name, sec in gaps if name.startswith("total: "))
    lo, hi = tr.window_of(recorded)
    assert idle == pytest.approx((hi - lo) - tr.busy_seconds(recorded),
                                 rel=1e-6)
    assert gaps[0][0] == "total: inside pb.engine.step"


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert tr.measure([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) \
        == [(0, 1), (2, 4), (6, 9)]
    assert tr.clip([(0, 5), (8, 9)], (3, 8.5)) == [(3, 5), (8, 8.5)]


def synthetic():
    """One chip: a loop of 10 s enclosing a matmul and a kernel; an
    all-gather in flight from 2 to 6 s of which 3 to 4 s is hidden by the
    matmul and 5 to 6 s by the kernel; idle from 10 to 12 s, which
    begins as one benchmark span ends; the next opens at 11 s."""
    ops = [Event("%while.1 = (s32[]) while(...)", 0.0, 10.0),
           Event("%fusion.3 = bf16[8,16]{1,0} fusion(...)", 3.0, 4.0),
           Event("%attn.prefill.2 = (bf16[4,128,64]{2,1,0}, f32[4,128,128]"
                 "{2,1,0}) custom-call(...)", 5.0, 6.0),
           Event("%custom-call.7 = f32[2]{0} custom-call(...)", 12.0, 13.0)]
    asyncs = [Event("%all-gather-start.1 = (f32[8]) all-gather-start(...)",
                    2.0, 6.0)]
    host = [Event("pb.train.step", 0.0, 10.0), Event("pb.train.step", 11.0,
                                                     13.0)]
    return Trace(device={0: {tr.OPS_LINE: ops, tr.ASYNC_LINE: asyncs}},
                 host={"main": host})


def test_synthetic_busy_idle_self_time_and_collectives():
    t = synthetic()
    assert tr.window_of(t) == (0.0, 13.0)
    assert tr.busy_seconds(t) == pytest.approx(11.0)
    selfs = {tr.op_label(e.name): s for e, s in
             tr.self_times(t.device[0][tr.OPS_LINE])}
    assert selfs["while s32[]"] == pytest.approx(8.0)
    assert selfs["fusion bf16[8,16]"] == pytest.approx(1.0)
    assert [e.name[:6] for e in tr.leaves(t.device[0][tr.OPS_LINE])] \
        == ["%fusio", "%attn.", "%custo"]
    # The gather is exposed from 2 to 3 and from 4 to 5.
    assert tr.collective_exposed_seconds(t) == pytest.approx(2.0)
    kernels = tr.kernel_events(t, tr.is_pallas)[0]
    assert [tr.op_head(e.name) for e in kernels] == ["attn.prefill.2"]
    gaps = dict(tr.idle_gaps(t))
    assert gaps["total: no benchmark span open"] == pytest.approx(2.0)
    assert tr.op_kind(t.device[0][tr.OPS_LINE][0].name) == "while"
    assert tr.op_label("%convert.383.remat24 = bf16[48,1600,4800]{2,1,0} "
                       "convert(f32[48,1600,4800]{2,1,0} %p)") \
        == "convert bf16[48,1600,4800]"


def test_flash_reader_tells_its_three_kernels_apart():
    from perfbench import run as harness

    flash = harness.load_reader([harness.HERE], "flash_attn_roofline")
    lay = "{2,1,0:T(8,128)(2,1)}"
    fwd = f"%attn.prefill.33 = (bf16[64,1024,64]{lay}, f32[64,1024,128]" \
          f"{{2,1,0:T(8,128)}}) custom-call(bf16[64,1024,64]{lay} %x)"
    dkv = f"%attn.prefill.35 = (bf16[64,1024,64]{lay}, bf16[64,1024,64]" \
          f"{lay}) custom-call(bf16[64,1024,64]{lay} %x)"
    dq = f"%attn.prefill.36 = bf16[64,1024,64]{lay} custom-call(bf16[64," \
         f"1024,64]{lay} %x)"
    assert flash.classify(fwd) == ("fwd", 64, 1024, 64)
    assert flash.classify(dkv) == ("dkv", 64, 1024, 64)
    assert flash.classify(dq) == ("dq", 64, 1024, 64)
