"""The ``longcat_flash`` family and the reader it brings: a tiny
LongCat-Flash-Chat (two published layers, an eighth of the routed experts
held) served end to end on the CPU through ``run.run_cell`` (whole prompts
through the expanded program, decodes through the absorbed kernel,
interpreted, two latent pools a layer, tokens sampled);
``moe_zero_pairs_pct`` on a recorded step log; the configuration against
the catalog's row; the family's counts against numbers worked out by hand
and against the built tree. (The reference against a literal transcription
of the equations, and the share test, are tier-1:
``tests/test_longcat_flash.py``.)"""

import json
import os

import pytest

from perfbench import byname, probe, run
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import SEED, names

HERE = os.path.dirname(os.path.abspath(__file__))
LONGCAT = os.path.join(HERE, "longcat")
CELL = "longcat-shortcut-decode"
NEW = "moe_zero_pairs_pct"


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


def benchmark(tiny=None):
    """``BENCHMARK.json`` and, as ``test_rehearsal.benchmark_with`` does
    it, a cell ``tiny`` of the tiny configuration that reports what
    ``longcat-shortcut-decode`` reports."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if tiny:
        bench["configs"].append({"name": "tiny-longcat",
                                 "source": "rehearsal", "file": "-",
                                 "reduced": [], "why": "-"})
        bench["workloads"].append({"name": tiny, "config": "tiny-longcat",
                                   "traffic": tiny, "chips": 1,
                                   "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(tiny)
    return bench


@pytest.fixture(scope="module")
def family():
    return run.load_family([run.HERE], {"family": "longcat_flash"})


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(run.HERE, "configs",
                           "longcat-flash-chat.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(run.HERE, "traffic",
                           "shortcut-decode.json")) as f:
        return json.load(f)


# ---- a tiny model through the command path ------------------------------------


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_longcat_cell_end_to_end(traced, tmp_path):
    bench = benchmark(tiny="tiny-shortcut-decode")
    result = run.run_cell(bench, [LONGCAT, run.HERE], "tiny-shortcut-decode",
                          SEED, 2.0, traced, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    got = result["metrics"]
    engine = probe.ProbedEngine.instances[-1]
    stats = engine.stats()
    # Prompts whole, no chunk program; two pools a layer, nothing
    # preempted.
    assert stats["prefill_compiles"] == {"32": 1}
    assert not stats["chunk_prefill_compiles"]
    assert engine.cache.v == [] and len(engine.cache.k) == 4
    assert stats["kv_pool_bytes"] == 4 * 33 * 8 * 256 * 4
    assert stats["num_preemptions"] == 0
    # 4 of 32 routed experts held beside 16 identities: a twelfth of a
    # token's 6 choices in each of the two routed layers is computed
    # here, a third costs nothing anywhere, the rest is another chip's.
    tokens = stats["prefill_tokens"] + stats["decode_tokens"]
    assert stats["moe_pairs"] == tokens * 2 * 6
    here = sum(map(sum, stats["expert_tokens"]))
    assert 0.02 < here / stats["moe_pairs"] < 0.25
    assert 0.2 < stats["moe_zero_pairs"] / stats["moe_pairs"] < 0.5
    if not traced:
        assert set(got) == names(bench, "end_to_end", CELL)
        return
    # No TPU plane in a CPU trace: the device metrics are left out, the
    # counters are numbers.
    assert not {"mla_attn_roofline", "mla_attn_busy_pct",
                "moe_ffn_roofline.long"} & set(got)
    assert got["kv_bytes_per_token"]["value"] == 4 * 256 * 4
    assert 20.0 < got[NEW]["value"] < 50.0
    assert got["compiles_in_window.long"]["value"] == 0
    assert got["preemptions.long"]["value"] == 0
    assert 0.0 < got["moe_experts_touched_pct.long"]["value"] <= 100.0
    assert got["out_tokens_per_s.long"]["value"] > 0


# ---- the reader on a recorded step log ----------------------------------------


class Engine:
    def __init__(self, steps):
        self.log = {"steps": steps, "oldest_start": 0.0}

    def step_log(self, since=0.0):
        return self.log


def record(start, end, zero, pairs, prefills=None, decodes=64):
    out = {"start": start, "end": end, "decodes": decodes, "phases": [],
           "moe_assignments": 70, "moe_experts_touched": 40,
           "moe_expert_max": 5}
    if pairs is not None:
        out.update(moe_zero_pairs=zero, moe_pairs=pairs)
    if prefills:
        out["prefills"] = prefills
    return out


def run_data(family, cfg, monkeypatch, steps, window=(0.5, 100.0)):
    monkeypatch.setattr(probe.ProbedEngine, "instances", [Engine(steps)])
    return RunData(cell={}, cfg=cfg, mix={}, family=family, chips=1,
                   peaks=None, window=window, end_to_end={},
                   memory_peak_bytes=0)


def test_zero_pairs_share_is_a_median_over_plain_decode_steps(
        family, published, monkeypatch):
    """64 sequences x 12 choices x 4 routed layers = 3,072 pairs a step.
    Three plain steps, one that also prefilled a prompt (left out: its
    pairs are the prompt's), one outside the window, one that decoded
    nothing."""
    steps = [record(1, 2, 1000, 3072), record(2, 3, 1024, 3072),
             record(3, 4, 1100, 3072),
             record(4, 5, 9000, 3072 + 48 * 512,
                    prefills=[{"tokens": 512}]),
             record(200, 201, 3072, 3072),
             record(5, 6, 600, 1200, decodes=0)]
    data = run_data(family, published, monkeypatch, steps)
    assert read(NEW, data) == pytest.approx(100 * 1024 / 3072)


def test_a_program_without_the_fields_gives_nothing(family, published,
                                                    monkeypatch):
    """The parent's records (a routed model's, without the two fields),
    a dense program's, a program with no step log: ``None``, no raise."""
    data = run_data(family, published, monkeypatch,
                    [record(1, 2, None, None), record(2, 3, None, None)])
    assert read(NEW, data) is None
    data = run_data(family, published, monkeypatch,
                    [{"start": 1, "end": 2, "decodes": 8, "phases": []}])
    assert read(NEW, data) is None
    monkeypatch.setattr(probe.ProbedEngine, "instances", [object()])
    assert read(NEW, data) is None
    monkeypatch.setattr(probe.ProbedEngine, "instances", [])
    assert read(NEW, data) is None


def test_benchmark_entries_of_the_cell():
    bench = benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    entry = entries[NEW]
    assert callable(byname.load_reader([run.HERE], NEW).read)
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"],
            entry["workloads"]) == ("expert layer", "%", "program_counter",
                                    "tpot_mean_ms", [CELL])
    # Found by name, not by place: the next PR appends after these.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = next(c for c in bench["configs"]
                  if c["name"] == "longcat-flash-chat")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("longcat-flash-chat", "shortcut-decode", 1)
    assert len(cell["why"]) <= 200
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert names(bench, "end_to_end", CELL) == {"tpot_mean_ms", "setup_s"}
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert {NEW, "mla_attn_roofline", "mla_attn_busy_pct",
            "kv_bytes_per_token", "tpot_p50_ms"} <= listed
    # Every quantity a held cell shares with the others, under ``.long``.
    assert {m["name"] for m in bench["per_layer"]
            if m["name"].endswith(".long")} <= listed
    assert not {"paged_attn_roofline", "paged_attn_kinds_roofline",
                "paged_attn_busy_pct", "kv_resident_vs_flat_pct"} & listed
    # Appended only: the cells the benchmark had stand where they stood.
    assert [w["name"] for w in bench["workloads"]][:8] == [
        "medium-train", "xl-batch-decode", "xl-train-fsdp4",
        "olmoe-batch-decode", "mellum2-long-decode", "joyai-latent-decode",
        "kexaone-selfdraft-decode", "lfm2-hybrid-decode"]


# ---- the configuration and the family's counts, by hand ----------------------


# The ``config`` of the model's row in the driver's catalog of
# architectures (LongCat-Flash-Chat), copied: the catalog lies outside the
# checkout.
CATALOG_SOURCE = ("https://huggingface.co/meituan-longcat/"
                  "LongCat-Flash-Chat/blob/main/config.json")
CATALOG_CONFIG = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def test_configuration_holds_the_published_numbers(published):
    assert published["source"] == CATALOG_SOURCE
    for key, value in CATALOG_CONFIG.items():
        if key not in published["reduced"]:
            assert published[key] == value, key
    assert published["reduced"] == ["num_layers", "n_routed_experts",
                                    "vocab_size"]
    assert (published["num_layers"], published["published_num_layers"]) \
        == (4, 28)
    assert (published["n_routed_experts"],
            published["published_n_routed_experts"],
            published["experts_held"]) == (16, 512, [0, 16])
    assert (published["vocab_size"], published["published_vocab_size"]) \
        == (16384, 131072)
    # The guide's floors: four layers, eight experts, an eighth of the
    # vocabulary; no width among the cuts.
    assert published["vocab_size"] * 8 >= published["published_vocab_size"]
    assert {"assumed", "deployment", "source", "reduced_why"} \
        <= set(published)
    assert "32 chips share each layer" in published["deployment"]
    assert {"rope_interleave", "softmax_scale", "rope_scaling",
            "hidden_act", "norm_topk_prob", "tie_word_embeddings",
            "mla_scales", "e_score_correction_bias",
            "e_score_correction_bias_std", "compute", "param_dtype",
            "weights"} <= set(published["assumed"])


def test_counts_of_the_configuration(family, published):
    # One sublayer's attention: q_a 6144 x 1536, q_b 1536 x 12288, kv_a
    # 6144 x 576, kv_b 512 x 16384, o 8192 x 6144, the two inner norms.
    matrices = 9437184 + 18874368 + 3538944 + 8388608 + 50331648
    assert matrices == 90570752
    attention = matrices + 1536 + 512
    dense = 3 * 6144 * 12288
    assert dense == 226492416
    expert = 3 * 6144 * 2048
    assert expert == 37748736
    outside_experts = 2 * (attention + 2 * 6144 + dense) \
        + 6144 * 768 + 768
    assert outside_experts == 638874368
    layer = outside_experts + 16 * expert
    assert layer == 1242854144
    outside = 2 * 16384 * 6144 + 6144
    assert family.param_count(published) == outside + 4 * layer \
        == 5172749312
    # 10.35 GB in bf16, as the issue's arithmetic has it (5,172.7 M).
    assert round(family.param_count(published) * 2 / 1e9, 2) == 10.35
    whole = dict(published, num_layers=28, n_routed_experts=512,
                 experts_held=[0, 512], vocab_size=131072)
    assert family.param_count(whole) == 2 * 131072 * 6144 + 6144 + 28 * (
        outside_experts + 512 * expert) == 560664980480  # "560B"
    # Of a token's 12 choices 12 x 16 / 768 = a quarter of one falls on
    # an expert held here, on average.
    assert family.active_param_count(published) == outside + 4 * (
        outside_experts + 0.25 * expert)
    assert family.moe_shape(published) == (4, 16, 12, 6144, 2048, 2)
    assert family.kv_shape(published) == (8, 1, 576, 2)
    assert family.vocab_rows_held(published) == 16384
    assert family.router_width(published) == 768
    pcfg = family.program_config(published)
    assert (pcfg.n_layer, pcfg.n_expert, pcfg.n_zero_expert,
            pcfg.experts_held, pcfg.n_expert_held) \
        == (8, 512, 256, (0, 16), 16)
    s = pcfg.serving
    assert (s.kv_row, s.expert_counts, s.expert_pairs) \
        == (640, (4, 16), True)
    assert [pcfg.ffn_width(i) for i in range(8)] == [12288] * 8
    assert [pcfg.shortcut_to(i) for i in range(4)] == [1, None, 3, None]


def test_the_built_tree_has_the_counted_parameters(family, published):
    """The program's own tree, as shapes: its leaves add up to the
    family's count, and its bytes as held to the deployment's 10.35 GB."""
    import jax

    pcfg = family.program_config(published)
    tree = jax.eval_shape(family.train_parts(pcfg)[0],
                          jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(a.size for a in leaves) == family.param_count(published)
    held = sum(a.size * a.dtype.itemsize for a in leaves)
    assert round(held / 1e9, 2) == 10.38  # norms, router, bias in float32
    assert {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}


def test_counts_at_the_tiny_size(family):
    with open(os.path.join(LONGCAT, "configs", "tiny-longcat.json")) as f:
        tiny = json.load(f)
    attention = 64 * 48 + 48 + 48 * 4 * 24 + 64 * 136 + 128 \
        + 128 * 4 * 32 + 4 * 16 * 64
    sublayer = attention + 2 * 64 + 3 * 64 * 96
    layer = 2 * sublayer + 64 * 48 + 48 + 4 * (3 * 64 * 32)
    assert family.param_count(tiny) == 2 * 512 * 64 + 64 + 2 * layer
    assert family.moe_shape(tiny) == (2, 4, 6, 64, 32, 4)
    assert family.kv_shape(tiny) == (4, 1, 136, 4)
    assert family.router_width(tiny) == 48


@pytest.mark.parametrize("pages,bytes_,flops", [
    # One page of 128 rows of 576 bf16 values in each of 8 pools; a slot
    # costs 64 heads x 2 x (576 + 512) FLOPs a pool.
    (1, 8 * 147456.0, 8 * 128 * 64 * 2.0 * 1088),
    # 64 sequences of 2,560 positions: 20 pages each, 1.51 GB a step as
    # published (1.68 as held on 640 lanes).
    (64 * 20, 8 * 1280 * 147456.0, 8 * 1280 * 128 * 139264.0)])
def test_latent_bytes_and_flops(family, published, pages, bytes_, flops):
    assert family.latent_attn_bytes(published, 128, pages) == bytes_
    assert family.latent_attn_flops(published, pages * 128) == flops


def test_expert_layer_flops_and_bytes(family, published):
    # 3 x 6144 x 2048 = 37,748,736 weights an expert: K-EXAONE's shape.
    assert family.expert_ffn_flops(published, 16) == 16 * 75497472.0
    assert family.expert_ffn_bytes(published, 40) == 40 * 75497472.0


# ---- the mix's arithmetic ------------------------------------------------------


def test_mix_is_the_traffic_the_issue_names(mix):
    from perfbench import traffic

    assert (mix["kind"], mix["clients"], mix["requests_per_client"],
            mix["order_seed"]) == ("closed", 64, 3, 1)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 512,
                                    "hi": 2048}
    assert mix["new_tokens"] == {"dist": "const", "value": 4096}
    assert mix["first_wave_new_tokens"] == [64 * (i + 1) for i in range(64)]
    assert mix["window_opens_after_client"] == 0
    assert mix["sampling"] == {"temperature": 1.0}
    assert mix["trace_seconds"] == 2.0
    options = mix["engine_options"]
    assert (options["page_size"], options["max_num_seqs"],
            options["max_model_len"], options["decode_buckets"]) \
        == (128, 64, 6144, [64])
    # Every client's prompt + 4,096 tokens at once, in whole pages, + 36
    # spare + the scratch page: nothing can be preempted.
    sizes = traffic.quantile_sizes(mix["prompt_tokens"], 64)
    at_once = sum(-(-(int(n) + 4096) // 128) for n in sizes)
    assert at_once == 2635 and options["num_pages"] == at_once + 36 + 1
    assert max(sizes) + 4096 <= options["max_model_len"]
    assert max(sizes) <= options["prefill_buckets"][-1]
    assert max(mix["check"]["prompt_tokens"]) <= options[
        "prefill_buckets"][-1]
    # 8 pools x 1,280 B a token as held.
    assert options["num_pages"] * 128 * 8 * 1280 == 3502243840
    # One slot turns over every 64 decode steps from the window's first.
    plan = traffic.closed_schedule(mix, 0)
    turns = traffic.simulate_closed_turnovers(plan, 64 * 64)
    assert turns[:64] == [64 * (i + 1) - 1 for i in range(64)]
