"""``flash_fwd_per_bwd`` on hand-made traces of a train step, the way
``test_trace_reduce.py`` holds the other readers: instruction names as
the v5e's traces of the two training cells carry them (``attn.prefill``
on one chip, ``shard_map`` a chip under the mesh; the log-sum-exp as
``f32[bh, 1, T]``)."""

import os

import pytest

from perfbench import byname, trace_reduce as tr
from perfbench.rundata import RunData
from perfbench.trace_reduce import Event, Trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FWD = ("%{name}.{n} = (bf16[{bh},1024,64]{{2,1,0}}, f32[{bh},1,1024]"
       "{{2,1,0}}) custom-call(...)")
DQ = "%{name}.{n} = bf16[{bh},1024,64]{{2,1,0}} custom-call(...)"
DKV = ("%{name}.{n} = (bf16[{bh},1024,64]{{2,1,0}}, bf16[{bh},1024,64]"
       "{{2,1,0}}) custom-call(...)")


def train_trace(forwards, *, layers=3, steps=2, chips=1, name="attn.prefill",
                bh=256):
    """``steps`` train steps of ``layers`` layers on each of ``chips``:
    a forward a layer, then per layer ``forwards - 1`` more forwards
    (the remat re-run), dq and dk/dv, among other instructions."""
    device = {}
    for chip in range(chips):
        ops, now, n = [], 0.0, 0

        def emit(fmt):
            nonlocal now, n
            n += 1
            ops.append(Event(fmt.format(name=name, n=n, bh=bh), now,
                             now + 1e-3))
            now += 2e-3

        for _ in range(steps):
            for _ in range(layers):
                emit("%fusion.{n} = bf16[16,1024,3072]{{2,1,0}} fusion(...)")
                emit(FWD)
            for _ in range(layers):
                for _ in range(forwards - 1):
                    emit(FWD)
                emit(DQ)
                emit(DKV)
                emit("%custom-call.{n} = f32[2]{{0}} custom-call(...)")
        device[chip] = {tr.OPS_LINE: ops}
    return Trace(device=device,
                 host={"main": [Event("pb.train.step", 0.0, 1.0)]})


def read(trace):
    run = RunData(cell={}, cfg={}, mix={}, family=None, chips=1, peaks=None,
                  window=(0, 1), end_to_end={}, memory_peak_bytes=0,
                  trace=trace)
    return byname.load_reader([BENCH], "flash_fwd_per_bwd").read(run)


@pytest.mark.parametrize("forwards, kw, want", [
    (2, {}, 2.0),                                   # save nothing
    (1, {}, 1.0),                                   # the residuals kept
    (2, {"chips": 4, "name": "shard_map", "bh": 100}, 2.0),
    (1, {"chips": 4, "name": "shard_map", "bh": 100}, 1.0),
])
def test_forward_calls_per_backward(forwards, kw, want):
    assert read(train_trace(forwards, **kw)) == want


def test_nothing_to_read_without_a_backward_or_a_trace():
    serving = Trace(
        device={0: {tr.OPS_LINE: [Event(FWD.format(
            name="attn.prefill", n=1, bh=25), 0.0, 1e-3)]}},
        host={"main": [Event("pb.engine.step", 0.0, 1.0)]})
    assert read(serving) is None
    assert read(None) is None


def test_the_entry_lists_the_training_cells():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": "flash_fwd_per_bwd", "unit": "ratio", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s_chip",
        "workloads": ["medium-train", "xl-train-fsdp4"]}
