"""The ``lfm2_moe`` family, its cell and the readers of a cache whose
layers do not all keep keys and values: a tiny LFM2 (both dense layers and
one period: conv conv | full conv conv conv) served end to end on the CPU
through ``run.run_cell`` under the mix's own sampling, its prompts through
whole-prompt and chunk programs that leave a state at a seat, its decode
rows through their seats; the readers ``cache_resident_vs_all_kv_pct``,
``state_seats_peak_pct`` and ``paged_attn_plain_roofline``; the
configuration against the catalog's row; the family's counts against
numbers worked out by hand and against the program's own bytes; the mix
file's page arithmetic. (The reference against the program row by row,
chunks of every length, seats reused and preemption are tier-1:
``tests/test_lfm2_moe.py``.)"""

import json
import math
import os

import pytest

from perfbench import byname, probe, run, traffic
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import SEED, names
from raytpu.inference.engine import InferenceEngine

HERE = os.path.dirname(os.path.abspath(__file__))
LFM2 = os.path.join(HERE, "lfm2")
CELL = "lfm2-hybrid-decode"
NEW = ("cache_resident_vs_all_kv_pct", "state_seats_peak_pct",
       "paged_attn_plain_roofline")


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


def benchmark(tiny=None):
    """``BENCHMARK.json`` and a cell ``tiny`` of the tiny configuration
    that reports what ``lfm2-hybrid-decode`` reports."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if tiny:
        bench["configs"].append({"name": "tiny-lfm2", "source": "rehearsal",
                                 "file": "-", "reduced": [], "why": "-"})
        bench["workloads"].append({"name": tiny, "config": "tiny-lfm2",
                                   "traffic": tiny, "chips": 1,
                                   "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(tiny)
    return bench


@pytest.fixture(scope="module")
def family():
    return run.load_family([run.HERE], {"family": "lfm2_moe"})


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(run.HERE, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(run.HERE, "traffic", "hybrid-decode.json")) as f:
        return json.load(f)


# ---- a tiny model through the command path ----------


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_lfm2_cell_end_to_end(traced, tmp_path, short_runs):
    bench = benchmark(tiny="tiny-hybrid-decode")
    result = run.run_cell(bench, [LFM2, run.HERE], "tiny-hybrid-decode",
                          SEED, 2.0, traced, require_tpu=False,
                          work_dir=str(tmp_path))
    engine = probe.ProbedEngine.instances[-1]
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    assert compared["check_decode_rows_min"][0] \
        <= compared["check_decode_rows_min"][1] == 1e-4
    assert compared["compiles_in_window"] == [0, 0]
    stats = engine.stats()
    # One attention layer's pool, five state arrays, a seat a slot;
    # prompts went whole and through chunks; nothing preempted.
    assert len(engine.cache.k) == 1 and len(engine.cache.state) == 5
    assert stats["state_seats_total"] == 4 and stats["num_preemptions"] == 0
    assert stats["prefill_compiles"] and stats["chunk_prefill_compiles"]
    steps = engine.step_log()["steps"]
    assert max(s["state_seats"] for s in steps) == 4
    assert all(s["state_bytes"] == s["state_seats"] * 5 * 2 * 64 * 4
               for s in steps)
    got = result["metrics"]
    if not traced:
        assert set(got) == names(bench, "end_to_end", CELL) \
            == {"tpot_mean_ms", "setup_s"}
        assert math.isfinite(got["tpot_mean_ms"]["value"]) \
            and got["tpot_mean_ms"]["value"] > 0
        return
    for name in ("cache_resident_vs_all_kv_pct", "state_seats_peak_pct",
                 "moe_experts_touched_pct.long", "tpot_p50_ms",
                 "decode_batch_mean.long", "out_tokens_per_s.long"):
        assert math.isfinite(got[name]["value"]), name
    assert got["state_seats_peak_pct"]["value"] == 100.0
    # One layer in six keeps keys and values: a sixth, and the seats'
    # states on top (2.5 KB a sequence against 512 B a page of 8 tokens).
    assert 100 / 6 < got["cache_resident_vs_all_kv_pct"]["value"] < 50
    assert not {"paged_attn_roofline", "paged_attn_kinds_roofline",
                "kv_resident_vs_flat_pct"} & set(got)


# ---- the readers ----------


class Step:
    """A probe's record of a traced step, with the program's fields."""

    def __init__(self, decodes, live_pages, seats, prefills=0):
        self.decodes, self.prefills = decodes, prefills
        self.live_pages = live_pages
        self.program = type("Program", (), {"fields": {
            "live_pages": live_pages, "state_seats": seats}})()


def run_data(family, cfg, mix, traced, logged=()):
    data = RunData(cell={}, cfg=cfg, mix=mix, family=family, chips=1,
                   peaks=None, window=(0.0, 100.0), end_to_end={},
                   memory_peak_bytes=0, traced_steps=list(traced))

    class Engine:
        def step_log(self, since=0.0):
            return {"oldest_start": 0.0, "steps": list(logged)}

    probe.ProbedEngine.instances[:] = [Engine()]
    return data


def test_readers_on_hand_made_steps(family, published, mix):
    saved = list(probe.ProbedEngine.instances)
    try:
        steps = [Step(64, 2000, 64), Step(64, 2100, 64),
                 Step(0, 0, 64, prefills=1)]   # a prompt's step: left out
        logged = [{"start": t, "end": t + 0.5, "state_seats": n}
                  for t, n in ((1.0, 60), (2.0, 64), (3.0, 63))]
        data = run_data(family, published, mix, steps, logged)
        # A page: 128 rows of K and of V, 8 heads of 64 in bf16 = 262,144
        # B a layer; two layers hold it, ten would; 65,536 B a seat.
        held = 2 * 4100 * 262144 + 128 * 65536
        assert read("cache_resident_vs_all_kv_pct", data) \
            == pytest.approx(100.0 * held / (10 * 4100 * 262144))
        assert 20.0 < read("cache_resident_vs_all_kv_pct", data) < 20.1
        assert read("state_seats_peak_pct", data) == 100.0
        # Off a chip there are no peaks: the roofline reader gives none.
        assert read("paged_attn_plain_roofline", data) is None
        # A program without seats in its records (the parent's): nothing.
        for s in steps:
            del s.program.fields["state_seats"]
        plain = run_data(family, published, mix, steps,
                         [{"start": 1.0, "end": 1.5}])
        assert [read(n, plain) for n in NEW] == [None, None, None]
        # A family without states: nothing.
        other = run.load_family([run.HERE], {"family": "olmoe"})
        assert read("cache_resident_vs_all_kv_pct",
                    run_data(other, published, mix, steps)) is None
    finally:
        probe.ProbedEngine.instances[:] = saved


def test_plain_roofline_counts_the_attention_layers_pages(family, published,
                                                          mix, monkeypatch):
    from perfbench import paged_kinds, peaks

    steps = [Step(64, 2000, 64), Step(64, 2100, 64)]
    data = run_data(family, published, mix, steps)
    data.peaks = peaks.PEAKS["TPU v5 lite"]
    monkeypatch.setattr(paged_kinds, "traced_seconds",
                        lambda run_: (0.004, 0.04))
    monkeypatch.setattr(paged_kinds, "_plain_traced",
                        lambda run_: [(None, s) for s in steps])
    bytes_ = 2 * 4100 * 262144
    assert read("paged_attn_plain_roofline", data) == pytest.approx(
        100.0 * bytes_ / data.peaks.hbm_bytes_per_s / 0.004)


def test_readers_constants_are_the_benchmarks_entries():
    bench = benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {"cache_resident_vs_all_kv_pct": "KV cache",
              "state_seats_peak_pct": "scheduler",
              "paged_attn_plain_roofline": "kernels"}
    for name in NEW:
        mod, entry = byname.load_reader([run.HERE], name), entries[name]
        assert callable(mod.read)
        assert (entry["layer"], entry["moves"], entry["workloads"]) \
            == (layers[name], "tpot_mean_ms", [CELL])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2-24b-a2b", "hybrid-decode", 1)
    assert bench["workloads"][-1] == cell and len(cell["why"]) <= 200
    config = bench["configs"][-1]
    assert (config["name"], config["reduced"]) \
        == ("lfm2-24b-a2b", ["num_hidden_layers"])
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    twins = {n for n in entries if n.endswith(".long")}
    assert {"moe_ffn_roofline.long", "moe_ffn_busy_pct.long",
            "moe_experts_touched_pct.long",
            "moe_load_max_over_mean.long"} <= twins
    assert listed == twins | set(NEW) | {"tpot_p50_ms"}
    assert names(bench, "end_to_end", CELL) == {"tpot_mean_ms", "setup_s"}


# ---- the configuration and the family's counts, by hand ----------


# The ``config`` of the model's row in the driver's catalog of
# architectures (LFM2-24B-A2B), copied: the catalog lies outside the
# checkout.
CATALOG_SOURCE = ("https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/"
                  "config.json")
CATALOG_CONFIG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention",
                                                      "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def test_configuration_holds_the_published_numbers(published):
    assert published["source"] == CATALOG_SOURCE
    for key, value in CATALOG_CONFIG.items():
        if key not in published["reduced"]:
            assert published[key] == value, key
    assert published["reduced"] == ["num_hidden_layers"]
    assert (published["num_hidden_layers"],
            published["published_num_hidden_layers"]) == (10, 40)
    assert len(published["layer_types"]) == 40 \
        and published["layer_types"].count("full_attention") == 10
    assert {"assumed", "deployment", "source", "reduced_why"} \
        <= set(published)
    assert "four pipeline stages" in published["deployment"]
    assert {"tie_word_embeddings", "expert_bias_std", "norm_topk_sum_eps",
            "conv", "qk_norm", "rope", "compute", "weights"} \
        <= set(published["assumed"])


def test_counts_of_the_configuration(family, published):
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert conv == 16783360                      # "16.8 M"
    # q 2048 x 2048, k and v 2048 x 512 each, o 2048 x 2048, head norms.
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert attention == 10485888                 # "10.5 M"
    expert = 3 * 2048 * 1536
    assert expert == 9437184                     # "9.44 M"
    routed = 64 * expert + 2048 * 64 + 64 + 2 * 2048
    dense = 3 * 2048 * 11776 + 2 * 2048
    embedding = 65536 * 2048                     # and the head, tied
    assert family.param_count(published) == (
        embedding + 2048 + 2 * (conv + dense) + 2 * (attention + routed)
        + 6 * (conv + routed)) == 5267090176
    assert round(family.param_count(published) * 2 / 1e9, 1) == 10.5
    whole, a_token = family.published_param_counts(published)
    assert whole == embedding + 2048 + 2 * (conv + dense) \
        + 10 * (attention + routed) + 28 * (conv + routed)
    assert round(a_token / 1e9, 2) == 2.33
    assert family.active_param_count(published) \
        == family.param_count(published) - 8 * 60 * expert
    assert family.moe_shape(published) == (8, 64, 4, 2048, 1536, 2)
    assert family.kv_shape(published) == (2, 8, 64, 2)
    assert family.state_bytes_per_seq(published) == 8 * 2 * 2048 * 2 == 65536
    assert family.vocab_rows_held(published) == 65536
    pcfg = family.program_config(published)
    assert pcfg.layer_types == ("conv", "conv", "full_attention", "conv",
                                "conv", "conv", "full_attention", "conv",
                                "conv", "conv")
    served = pcfg.serving
    assert served.expert_counts == (8, 64) and served.drafting is None
    assert [s is None for s in served.layer_states] \
        == [k == "full_attention" for k in pcfg.layer_types]
    assert {s for s in served.layer_states if s} == {(2, 2048)}
    assert [pcfg.ffn_width(i) for i in (0, 1, 2)] == [11776, 11776, None]
    assert (pcfg.qk_head_norm, pcfg.tie_embeddings, pcfg.conv_taps,
            pcfg.choice_bias, pcfg.topk_sum_eps, pcfg.scoring) \
        == (True, True, 3, 0.01, 1e-6, "sigmoid")


def test_counts_are_the_programs_bytes_at_a_scaled_down_copy(family):
    """The same count functions over the tiny configuration against the
    bytes of the tree the engine serves from (float32)."""
    import jax

    with open(os.path.join(LFM2, "configs", "tiny-lfm2.json")) as f:
        tiny = json.load(f)
    pcfg = family.program_config(tiny, {"attn_impl": "reference",
                                        "paged_attn": "reference"})
    params = family.train_parts(pcfg)[0](jax.random.PRNGKey(0))
    eng = InferenceEngine(pcfg, params, page_size=8, max_num_seqs=2,
                          max_model_len=64)
    assert sum(eng.stats()["param_bytes"].values()) \
        == 4 * family.param_count(tiny)
    layers, kv, d, itemsize = family.kv_shape(tiny)
    assert (layers, itemsize) == (1, 4) and len(eng.cache.k) == 1
    assert eng.cache.token_bytes == 2 * layers * kv * d * itemsize
    assert eng.cache.state_bytes == family.state_bytes_per_seq(tiny)
    assert eng.stats()["state_bytes"] == 3 * family.state_bytes_per_seq(tiny)
    assert eng._expert_tokens.shape == family.moe_shape(tiny)[:2]


def test_expert_bytes(family, published):
    # 3 x 2048 x 1536 = 9,437,184 weights an expert, 18.9 MB in bf16.
    assert family.expert_ffn_flops(published, 256) == 256 * 18874368.0
    assert family.expert_ffn_bytes(published, 500) == 500 * 18874368.0


# ---- the mix ----------


def test_the_mix_holds_the_issues_traffic(mix):
    assert (mix["kind"], mix["clients"], mix["order_seed"],
            mix["window_opens_after_client"]) == ("closed", 64, 1, 0)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 1024,
                                    "hi": 8192}
    assert mix["new_tokens"] == {"dist": "const", "value": 2048}
    assert mix["first_wave_new_tokens"] == [32 * (i + 1)
                                            for i in range(64)]
    assert mix["sampling"] == {"temperature": 1.0}
    assert mix["check"]["decode_positions"] == 16
    bucket = mix["engine_options"]["prefill_buckets"]
    assert len(bucket) == 1 and all(
        512 < n < bucket[0] for n in mix["check"]["prompt_tokens"])
    assert traffic.request_sampling(mix, SEED, 5)["temperature"] == 1.0
    # Client 0's requests outlast set-up, window and trace at any step a
    # v5e can make (over 8 ms): 32 + 2 x 2,048 tokens, over 4,000 steps.
    assert mix["requests_per_client"] >= 3


def test_page_arithmetic_of_the_mix(mix, published):
    opts = mix["engine_options"]
    page, seqs = opts["page_size"], opts["max_num_seqs"]
    assert (page, seqs, opts["decode_buckets"]) == (128, 64, [64])
    longest = mix["prompt_tokens"]["hi"] + mix["new_tokens"]["value"]
    assert -(-longest // page) == 80
    assert opts["max_model_len"] == 80 * page == 10240
    assert opts["num_pages"] == seqs * 80 + 1 == 5121
    sizes = traffic.quantile_sizes(mix["prompt_tokens"], mix["clients"])
    chunk = opts["prefill_chunk"]
    assert (chunk, opts["chunk_buckets"], opts["prefill_buckets"]) \
        == (2048, [2048], [2048])
    # The shortest third of the prompts fits a chunk and goes whole; the
    # others are cut two to four times and carry a state across each cut.
    cuts = [-(-int(n) // chunk) for n in sizes]
    assert (cuts.count(1), cuts.count(2), cuts.count(3), cuts.count(4)) \
        == (21, 22, 12, 9) and max(sizes) + 2048 < opts["max_model_len"]
    # Two attention layers' K and V pools: 2.68 GB in bf16; 64 seats of
    # 64 KB and the scratch row: 4.3 MB.
    pool = opts["num_pages"] * page * 8 * 64 * 2
    assert round(2 * 2 * pool / 1e9, 2) == 2.68
    assert (seqs + 1) * 65536 == 4259840
    # Every program the traffic and the check can reach is warmed: the
    # whole-prompt bucket, chunks behind tables of 32 and 64 columns,
    # decodes behind 8 ... 64 and 80.
    widths = (1, 2, 4, 8, 16, 32, 64, 80)

    def width(tokens):
        return min(w for w in widths if w >= -(-tokens // page))

    decodes, chunks, whole = set(), set(), False
    for prompt, new in mix["warmup"]:
        decodes |= {width(prompt + 1), width(prompt + new)}
        if prompt <= chunk:
            whole = True
        else:
            chunks.add(width(prompt))
    assert whole and chunks == {32, 64} and decodes == {8, 16, 32, 64, 80}
    assert {width(int(n)) for n in sizes if n > chunk} <= chunks
    assert {width(n + 1) for n in mix["check"]["prompt_tokens"]} \
        | {width(n + 17) for n in mix["check"]["prompt_tokens"]} <= decodes
