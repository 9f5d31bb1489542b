"""The ``olmoe`` family and the routed-expert layer's four readers: a
tiny OLMoE served end to end on the CPU through ``run.run_cell`` (the
check holds the engine's prefill and decode through the paged cache to
the family's plain float32 reference at 1e-4 of the logit range), the
readers on a hand-made step log and trace, and the family's count
functions against numbers worked out by hand."""

import json
import os
import types

import pytest

from perfbench import byname, moe, probe, run
from perfbench import trace_reduce as tr
from perfbench.peaks import PEAKS
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import SEED, benchmark_with, names

HERE = os.path.dirname(os.path.abspath(__file__))
OLMOE = os.path.join(HERE, "olmoe")
COUNTERS = ("moe_experts_touched_pct", "moe_load_max_over_mean")
DEVICE = ("moe_ffn_roofline", "moe_ffn_busy_pct")


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


@pytest.fixture(scope="module")
def family():
    return run.load_family([run.HERE], {"family": "olmoe"})


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(run.HERE, "configs", "olmoe-1b-7b.json")) as f:
        return json.load(f)


# ---- a tiny OLMoE through the command path -----------------------------------


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_olmoe_cell_end_to_end(traced, tmp_path):
    bench = benchmark_with({"tiny-moe-closed": ("olmoe-batch-decode", 1)},
                           config="tiny-olmoe")
    result = run.run_cell(bench, [OLMOE, run.HERE], "tiny-moe-closed", SEED,
                          2.0, traced, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    got = result["metrics"]
    if not traced:
        assert set(got) == names(bench, "end_to_end", "olmoe-batch-decode")
        return
    # No TPU plane in a CPU trace: the two device metrics are left out,
    # the two counters are numbers.
    assert not set(DEVICE) & set(got)
    touched = got["moe_experts_touched_pct"]["value"]
    uneven = got["moe_load_max_over_mean"]["value"]
    # 4 rows x 2 experts of 8 a layer: between 2 and 8 experts touched,
    # the fullest holding 1 to 4 of a mean of 1.
    assert 25.0 <= touched <= 100.0 and 1.0 <= uneven <= 4.0
    assert got["compiles_in_window"]["value"] == 0
    engine = probe.ProbedEngine.instances[-1]
    total = sum(map(sum, engine.stats()["expert_tokens"]))
    assert total == 2 * 2 * (engine.stats()["prefill_tokens"]
                             + engine.stats()["decode_tokens"])


# ---- the readers on a hand-made log and trace --------------------------------


class Engine:
    def __init__(self, steps):
        self.log = {"steps": steps, "oldest_start": 0.0}

    def step_log(self, since=0.0):
        return self.log


def record(start, end, assignments, touched, most, prefills=None):
    out = {"start": start, "end": end, "decodes": 16, "phases": [],
           "moe_assignments": assignments, "moe_experts_touched": touched,
           "moe_expert_max": most}
    if prefills:
        out["prefills"] = prefills
    return out


def run_data(family, cfg, monkeypatch, steps, trace=None, peaks=None):
    monkeypatch.setattr(probe.ProbedEngine, "instances", [Engine(steps)])
    traced = []
    if trace is not None:
        # The probe's stamps enclose the engine's: 1 ms either side.
        traced = [types.SimpleNamespace(start=s["start"] - 1e-3,
                                        end=s["end"] + 1e-3)
                  for s in steps[:len(tr.spans(trace, "pb.engine.step"))]]
    return RunData(cell={}, cfg=cfg, mix={}, family=family, chips=1,
                   peaks=peaks, window=(0.5, 100.0), end_to_end={},
                   memory_peak_bytes=0, trace=trace, traced_steps=traced)


def test_counters_are_medians_over_plain_decode_steps(family, published,
                                                      monkeypatch):
    # 10 layers x 64 experts = 640; three plain steps and one that also
    # prefilled, which is left out.
    steps = [record(1, 2, 1280, 560, 6), record(2, 3, 1280, 576, 8),
             record(3, 4, 1280, 544, 5),
             record(4, 5, 3000, 1100, 40, prefills=[{"tokens": 215}])]
    data = run_data(family, published, monkeypatch, steps)
    assert read("moe_experts_touched_pct", data) == pytest.approx(87.5)
    assert read("moe_load_max_over_mean", data) == pytest.approx(6 / 2.0)
    assert [read(n, data) for n in DEVICE] == [None, None]


def test_a_dense_program_or_family_gives_nothing(family, published,
                                                 monkeypatch):
    steps = [{"start": 1, "end": 2, "decodes": 8, "phases": []}]
    data = run_data(family, published, monkeypatch, steps)
    assert [read(n, data) for n in COUNTERS + DEVICE] == [None] * 4
    gpt2 = run.load_family([run.HERE], {"family": "gpt2"})
    data = run_data(gpt2, {}, monkeypatch, [record(1, 2, 1280, 560, 6)])
    assert [read(n, data) for n in COUNTERS + DEVICE] == [None] * 4
    # A program without a step log at all (the parent's).
    monkeypatch.setattr(probe.ProbedEngine, "instances", [object()])
    assert [read(n, data) for n in COUNTERS + DEVICE] == [None] * 4


def test_roofline_and_busy_share_from_a_trace(family, published,
                                              monkeypatch):
    """Two traced steps of 20 ms; in each the chip works for 10 ms, 4 of
    them in three ragged-dot kernels. A third ragged-dot outside any
    span, and the events of the untraced third step, are not counted."""
    def step_events(t0):
        return [tr.Event("%fusion.1 = bf16[16,2048] fusion()", t0 + 0.002,
                         t0 + 0.008),
                tr.Event("%ragged-dot-none = bf16[128,1024]{1,0} "
                         "custom-call(s32[1] %a)", t0 + 0.008, t0 + 0.010),
                tr.Event("%ragged-dot-none.1 = bf16[128,1024]{1,0} "
                         "custom-call(s32[1] %a)", t0 + 0.010, t0 + 0.011),
                tr.Event("%ragged-dot-metadata = (s32[65]) custom-call()",
                         t0 + 0.011, t0 + 0.012)]
    trace = tr.Trace(
        device={0: {"XLA Ops": step_events(10.0) + step_events(10.02) + [
            tr.Event("%ragged-dot-none = bf16[128,1024] custom-call()",
                     10.045, 10.046)]}},
        host={"python": [tr.Event("pb.engine.step", 10.0, 10.02),
                         tr.Event("pb.engine.step", 10.02, 10.04)]})
    steps = [record(1.0, 1.018, 1280, 560, 6),
             record(1.02, 1.038, 1280, 560, 6),
             record(1.04, 1.058, 1280, 560, 6)]
    peaks = PEAKS["TPU v5 lite"]
    data = run_data(family, published, monkeypatch, steps, trace, peaks)
    assert moe.traced_seconds(data) == pytest.approx((0.008, 0.020))
    assert moe.traced_counts(data) == {"assignments": 2560,
                                       "experts_touched": 1120}
    assert read("moe_ffn_busy_pct", data) == pytest.approx(40.0)
    # 1120 experts x 3 x 2048 x 1024 x 2 B = 14.09 GB -> 17.2 ms at
    # 819 GB/s; the FLOPs (2560 pairs x 12.6 MFLOP) need 0.16 ms.
    least = 1120 * 3 * 2048 * 1024 * 2 / 819e9
    assert read("moe_ffn_roofline", data) == pytest.approx(
        100 * least / 0.008)
    # Off a TPU there are no peaks and no roofline share.
    data = run_data(family, published, monkeypatch, steps, trace)
    assert read("moe_ffn_roofline", data) is None
    assert read("moe_ffn_busy_pct", data) == pytest.approx(40.0)


# ---- the family's counts, by hand -------------------------------------------


def test_counts_of_the_published_model(family, published):
    whole = dict(published, num_hidden_layers=16)
    # Per layer: 4 x 2048^2 attention, 2 x 2048 q/k norms, 2 x 2048 block
    # norms, 2048 x 64 router, 64 x 3 x 2048 x 1024 experts.
    layer = 4 * 2048 ** 2 + 4 * 2048 + 2048 * 64 + 64 * 3 * 2048 * 1024
    outside = 2 * 50304 * 2048 + 2048
    assert family.param_count(whole) == outside + 16 * layer == 6919161856
    active = layer - 56 * 3 * 2048 * 1024
    assert family.active_param_count(whole) == outside + 16 * active \
        == 1282017280
    assert family.param_count(published) == outside + 10 * layer
    assert family.moe_shape(published) == (10, 64, 8, 2048, 1024, 2)
    assert family.kv_shape(published) == (10, 16, 128, 2)
    assert family.vocab_rows_held(published) == 50304


@pytest.mark.parametrize("assignments,touched,flops,bytes_", [
    (1, 1, 12582912.0, 12582912.0),          # one pair, one expert
    (1280, 560, 1280 * 12582912.0, 560 * 12582912.0),  # a decode step
    (2048 * 10, 640, 20480 * 12582912.0, 640 * 12582912.0)])
def test_expert_layer_flops_and_bytes(family, published, assignments,
                                      touched, flops, bytes_):
    # 3 x 2048 x 1024 = 6,291,456 weights an expert: 2 FLOPs each a
    # pair, 2 bytes each an expert touched.
    assert family.expert_ffn_flops(published, assignments) == flops
    assert family.expert_ffn_bytes(published, touched) == bytes_
