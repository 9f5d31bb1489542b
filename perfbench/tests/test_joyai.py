"""The ``joyai`` family and the readers of a latent cache: a tiny
JoyAI-LLM-Flash (a dense layer and two routed ones, a quarter of the
experts held) served end to end on the CPU through ``run.run_cell`` (the
check's prompts through the whole-prompt expanded program, the traffic's
through chunks and the absorbed kernel, interpreted, one pool a layer);
the three new readers on a hand-made step log and trace; the
configuration against the catalog's row; the family's counts against
numbers worked out by hand. (The reference against a literal
transcription of the equations, and the share test, are tier-1:
``tests/test_joyai.py``.)"""

import json
import os
import types

import pytest

from perfbench import byname, latent, probe, run
from perfbench import trace_reduce as tr
from perfbench.peaks import PEAKS
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import SEED, names

HERE = os.path.dirname(os.path.abspath(__file__))
JOYAI = os.path.join(HERE, "joyai")
CELL = "joyai-latent-decode"
NEW = ("mla_attn_roofline", "mla_attn_busy_pct", "kv_bytes_per_token")


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


def benchmark(tiny=None):
    """``BENCHMARK.json`` and, as ``test_rehearsal.benchmark_with`` does
    it, a cell ``tiny`` of the tiny configuration that reports what
    ``joyai-latent-decode`` reports."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if tiny:
        bench["configs"].append({"name": "tiny-joyai", "source": "rehearsal",
                                 "file": "-", "reduced": [], "why": "-"})
        bench["workloads"].append({"name": tiny, "config": "tiny-joyai",
                                   "traffic": tiny, "chips": 1,
                                   "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(tiny)
    return bench


@pytest.fixture(scope="module")
def family():
    return run.load_family([run.HERE], {"family": "joyai"})


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(run.HERE, "configs",
                           "joyai-llm-flash.json")) as f:
        return json.load(f)


# ---- a tiny model through the command path ------------------------------------


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_joyai_cell_end_to_end(traced, tmp_path):
    bench = benchmark(tiny="tiny-latent-decode")
    result = run.run_cell(bench, [JOYAI, run.HERE], "tiny-latent-decode",
                          SEED, 2.0, traced, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    got = result["metrics"]
    engine = probe.ProbedEngine.instances[-1]
    stats = engine.stats()
    # The check went through the whole-prompt program, the traffic
    # through chunks; one pool a layer, nothing preempted.
    assert stats["prefill_compiles"] == {"16": 1}
    assert set(stats["chunk_prefill_compiles"]) >= {"16x4", "16x8"}
    assert engine.cache.v == [] and len(engine.cache.k) == 3
    assert stats["kv_pool_bytes"] == 3 * 49 * 8 * 256 * 4
    assert stats["num_preemptions"] == 0
    # 4 of 16 experts held: about a quarter of a token's 4 pairs in each
    # of the two routed layers are computed here, the others nowhere.
    here = sum(s.get("moe_assignments", 0)
               for s in engine.step_log()["steps"])
    tokens = stats["prefill_tokens"] + stats["decode_tokens"]
    assert 0.1 < here / (tokens * 2 * 4) < 0.45
    if not traced:
        assert set(got) == names(bench, "end_to_end", CELL)
        return
    assert not {"paged_attn_roofline", "paged_attn_kinds_roofline",
                "paged_attn_busy_pct", "kv_resident_vs_flat_pct"} & set(got)
    # No TPU plane in a CPU trace: the two device metrics are left out,
    # the counter is a number.
    assert not {"mla_attn_roofline", "mla_attn_busy_pct"} & set(got)
    assert got["kv_bytes_per_token"]["value"] == 3 * 256 * 4
    # Held to the mean time per output token since PR 46: what it shares
    # with the other serving cells it reads under the ``.long`` names.
    assert got["compiles_in_window.long"]["value"] == 0
    assert got["preemptions.long"]["value"] == 0
    assert 0.0 < got["moe_experts_touched_pct.long"]["value"] <= 100.0
    assert got["out_tokens_per_s.long"]["value"] > 0


# ---- the readers on a hand-made log and trace --------------------------------


def step(start, end, pages, prefills=0, **fields):
    return types.SimpleNamespace(
        start=start, end=end, decodes=32, prefills=prefills,
        live_pages=pages, program=types.SimpleNamespace(
            fields=dict(fields, live_pages=pages)))


def run_data(family, cfg, steps, trace=None, peaks=None):
    return RunData(cell={}, cfg=cfg, mix={"engine_options":
                                          {"page_size": 128}},
                   family=family, chips=1, peaks=peaks,
                   window=(0.5, 100.0), end_to_end={}, memory_peak_bytes=0,
                   engine_steps=steps, trace=trace,
                   traced_steps=steps if trace is not None else [])


def test_roofline_and_busy_share_from_a_trace(family, published):
    """Three traced steps of 20 ms; in each the chip works for 12 ms, 6
    of them in twelve latent kernels. The second also prefilled a chunk
    and is left out, events and pages; a kernel outside any span, and
    another family's paged kernel, are not counted."""
    def step_events(t0):
        return [tr.Event("%fusion.1 = bf16[32,2048] fusion()", t0 + 0.002,
                         t0 + 0.008)] + [
            tr.Event(f"%_mla_paged_pallas.{i} = bf16[32,32,512]{{2,1,0}} "
                     f"custom-call(s32[32,168] %a)",
                     t0 + 0.008 + i * 0.0005, t0 + 0.008 + (i + 1) * 0.0005)
            for i in range(12)]
    trace = tr.Trace(
        device={0: {"XLA Ops": step_events(10.0) + step_events(10.02)
                    + step_events(10.04) + [
            tr.Event("%_mla_paged_pallas.13 = bf16[32,32,512] custom-call()",
                     10.065, 10.066)]}},
        host={"python": [tr.Event("pb.engine.step", 10.0, 10.02),
                         tr.Event("pb.engine.step", 10.02, 10.04),
                         tr.Event("pb.engine.step", 10.04, 10.06)]})
    steps = [step(1.0, 1.018, 2500), step(1.02, 1.038, 2500, prefills=1),
             step(1.04, 1.058, 2532)]
    peaks = PEAKS["TPU v5 lite"]
    data = run_data(family, published, steps, trace, peaks)
    assert latent.traced(data) == pytest.approx((0.012, 0.024, 5032))
    assert read("mla_attn_busy_pct", data) == pytest.approx(50.0)
    # A page of one layer is 128 rows of 576 values in bf16: 147,456 B;
    # a slot costs 32 heads x 2 x (576 + 512) = 69,632 FLOPs a layer.
    least = max(12 * 5032 * 147456 / 819e9,
                12 * 5032 * 128 * 69632 / 197e12)
    assert least == pytest.approx(12 * 5032 * 147456 / 819e9)
    assert read("mla_attn_roofline", data) == pytest.approx(
        100 * least / 0.012)
    # Off a TPU there are no peaks and no roofline share.
    data = run_data(family, published, steps, trace)
    assert read("mla_attn_roofline", data) is None
    assert read("mla_attn_busy_pct", data) == pytest.approx(50.0)
    # Only chunk steps traced: nothing to read.
    data = run_data(family, published, [steps[1]], tr.Trace(
        device=trace.device, host={"python": trace.host["python"][:1]}),
        peaks)
    assert [read(n, data) for n in NEW[:2]] == [None, None]


def test_a_program_without_the_kernel_gives_nothing(family, published):
    """The parent's program, or another family's: no ``_mla_paged_pallas``
    event, no ``kv_bytes_per_token`` in a record; no reader raises."""
    trace = tr.Trace(
        device={0: {"XLA Ops": [tr.Event(
            "%_paged_pallas.1 = bf16[32,1,32,512] custom-call()", 10.001,
            10.004)]}},
        host={"python": [tr.Event("pb.engine.step", 10.0, 10.02)]})
    peaks = PEAKS["TPU v5 lite"]
    data = run_data(family, published, [step(1.0, 1.018, 90)], trace, peaks)
    assert [read(n, data) for n in NEW] == [None] * 3
    olmoe = run.load_family([run.HERE], {"family": "olmoe"})
    data = run_data(olmoe, {}, [step(1.0, 1.018, 90)], trace, peaks)
    assert [read(n, data) for n in NEW] == [None] * 3
    # No trace, no steps at all.
    assert [read(n, run_data(family, published, [])) for n in NEW] \
        == [None] * 3
    # A record without fields (a program with no step log).
    bare = types.SimpleNamespace(start=1, end=2, decodes=1, prefills=0,
                                 live_pages=1, program=None)
    assert read("kv_bytes_per_token",
                run_data(family, published, [bare])) is None


def test_bytes_a_token_are_the_last_records(family, published):
    steps = [step(1, 2, 90, kv_bytes_per_token=15360),
             step(2, 3, 90, kv_bytes_per_token=15360)]
    assert read("kv_bytes_per_token",
                run_data(family, published, steps)) == 15360.0


def test_readers_constants_are_the_benchmarks_entries():
    """``BENCHMARK.json``'s entries for the cell and its three metrics
    are consistent with the readers, and the cell is in no list whose
    reader reads another kernel."""
    bench = benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        mod, entry = byname.load_reader([run.HERE], name), entries[name]
        assert callable(mod.read)
        assert (entry["moves"], entry["workloads"]) \
            == ("tpot_mean_ms", [CELL])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("joyai-llm-flash", "latent-decode", 1)
    left_out = {"paged_attn_roofline", "paged_attn_kinds_roofline",
                "paged_attn_busy_pct", "kv_resident_vs_flat_pct"}
    assert not [m["name"] for m in bench["per_layer"]
                if m["name"] in left_out and CELL in m["workloads"]]
    # Held to the mean time per output token since PR 46 (PERF.md
    # section 2): its rate and tail are read per layer, with no bound.
    assert names(bench, "end_to_end", CELL) == {"tpot_mean_ms", "setup_s"}
    assert {"out_tokens_per_s.long", "itl_p95_ms.long", "tpot_p50_ms",
            "moe_ffn_roofline.long"} <= {
        m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}


# ---- the configuration and the family's counts, by hand ----------------------


# The ``config`` of the model's row in the driver's catalog of
# architectures (JoyAI-LLM-Flash), copied: the catalog lies outside the
# checkout.
CATALOG_SOURCE = ("https://huggingface.co/jdopensource/JoyAI-LLM-Flash/"
                  "blob/main/config.json")
CATALOG_CONFIG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


def test_configuration_holds_the_published_numbers(published):
    assert published["source"] == CATALOG_SOURCE
    for key, value in CATALOG_CONFIG.items():
        if key not in published["reduced"]:
            assert published[key] == value, key
    assert published["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert (published["num_hidden_layers"],
            published["published_num_hidden_layers"]) == (12, 40)
    assert (published["n_routed_experts"],
            published["published_n_routed_experts"],
            published["experts_held"]) == (32, 256, [0, 32])
    assert {"assumed", "deployment", "source"} <= set(published)
    assert "8 chips share each layer" in published["deployment"]


def test_counts_of_the_configuration(family, published):
    # Attention: q_a 2048 x 1536, q_b 1536 x 6144, kv_a 2048 x 576, kv_b
    # 512 x 8192, o 4096 x 2048, and the two inner norms.
    matrices = 3145728 + 9437184 + 1179648 + 4194304 + 8388608
    assert matrices == 26345472
    attention = matrices + 1536 + 512
    expert = 3 * 2048 * 768
    assert expert == 4718592
    routed = attention + 2 * 2048 + 2048 * 256 + 256 + expert + 32 * expert
    dense = attention + 2 * 2048 + 3 * 2048 * 7168
    outside = 2 * 129280 * 2048 + 2048
    assert family.param_count(published) == outside + dense + 11 * routed \
        == 2608411392
    # 5.22 GB in bf16, as the issue's arithmetic has it (2,608 M).
    assert round(family.param_count(published) * 2 / 1e9, 2) == 5.22
    whole = dict(published, num_hidden_layers=40, n_routed_experts=256,
                 experts_held=[0, 256])
    assert family.param_count(whole) == outside + dense + 39 * (
        routed + 224 * expert) == 48942542592  # "48B"
    # A token uses 8 x 32 / 256 = 1 routed expert a layer here.
    assert family.active_param_count(published) == outside + dense + 11 * (
        routed - 31 * expert)
    assert family.moe_shape(published) == (11, 32, 8, 2048, 768, 2)
    assert family.kv_shape(published) == (12, 1, 576, 2)
    assert family.vocab_rows_held(published) == 129280
    pcfg = family.program_config(published)
    assert (pcfg.n_expert, pcfg.experts_held, pcfg.n_expert_held) \
        == (256, (0, 32), 32)
    s = pcfg.serving
    assert (s.kv_row, s.expert_counts) == (640, (11, 32))
    assert [pcfg.ffn_width(i) for i in (0, 1, 11)] == [7168, None, None]


@pytest.mark.parametrize("pages,bytes_,flops", [
    # One page of 128 rows of 576 bf16 values in each of 12 layers.
    (1, 12 * 147456.0, 12 * 128 * 32 * 2.0 * 1088),
    # 32 sequences of 11,400 positions: 90 pages each, 5.10 GB a step as
    # published (5.66 as held on 640 lanes).
    (32 * 90, 12 * 2880 * 147456.0, 12 * 2880 * 128 * 69632.0)])
def test_latent_bytes_and_flops(family, published, pages, bytes_, flops):
    assert family.latent_attn_bytes(published, 128, pages) == bytes_
    assert family.latent_attn_flops(published, pages * 128) == flops


def test_expert_layer_flops_and_bytes(family, published):
    # 3 x 2048 x 768 = 4,718,592 weights an expert.
    assert family.expert_ffn_flops(published, 32) == 32 * 9437184.0
    assert family.expert_ffn_bytes(published, 220) == 220 * 9437184.0
