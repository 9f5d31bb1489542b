"""The ``glm_moe_dsa`` family and the readers it brings: a tiny GLM-5
(a dense layer and two routed ones, a quarter of the routed experts held,
an indexer that keeps 16 positions) served end to end on the CPU through
``run.run_cell`` (whole prompts over ``index_topk`` through the absorbed
form over their own rows, chunks and decodes through both pools a layer,
kernels interpreted, tokens sampled); the four ``dsa_*`` readers on a
recorded step log and a recorded trace; the configuration against the
catalog's row; the family's counts against numbers worked out by hand and
against the built tree; the mix's arithmetic. (The reference against a
literal transcription of the equations, the controls and the share test
are tier-1: ``tests/test_glm_dsa.py``.)"""

import json
import os

import pytest

from perfbench import byname, probe, run, trace_reduce
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import SEED, names

HERE = os.path.dirname(os.path.abspath(__file__))
GLM = os.path.join(HERE, "glm_dsa")
CELL = "glm5-sparse-decode"
NEW = ("dsa_index_roofline", "dsa_attn_roofline", "dsa_busy_pct",
       "dsa_selected_pct")


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


def benchmark(tiny=None):
    """``BENCHMARK.json`` and, as ``test_rehearsal.benchmark_with`` does
    it, a cell ``tiny`` of the tiny configuration that reports what
    ``glm5-sparse-decode`` reports."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if tiny:
        bench["configs"].append({"name": "tiny-glm", "source": "rehearsal",
                                 "file": "-", "reduced": [], "why": "-"})
        bench["workloads"].append({"name": tiny, "config": "tiny-glm",
                                   "traffic": tiny, "chips": 1,
                                   "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(tiny)
    return bench


@pytest.fixture(scope="module")
def family():
    return run.load_family([run.HERE], {"family": "glm_moe_dsa"})


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(run.HERE, "configs", "glm-5.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(run.HERE, "traffic", "sparse-decode.json")) as f:
        return json.load(f)


# ---- a tiny model through the command path ------------------------------------


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_glm_cell_end_to_end(traced, tmp_path, short_runs):
    bench = benchmark(tiny="tiny-sparse-decode")
    result = run.run_cell(bench, [GLM, run.HERE], "tiny-sparse-decode",
                          SEED, 2.0, traced, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    got = result["metrics"]
    engine = probe.ProbedEngine.instances[-1]
    stats = engine.stats()
    # The check's prompts whole (27 and 31 tokens against a top-k of 16),
    # the traffic's in chunks of 32; two pools a layer, nothing preempted.
    assert stats["prefill_compiles"] == {"32": 1}
    assert stats["chunk_prefill_compiles"]
    assert len(engine.cache.k) == len(engine.cache.v) == 3
    assert stats["kv_pool_bytes"] == 3 * 65 * 8 * (256 + 16) * 4
    assert stats["num_preemptions"] == 0
    if not traced:
        assert set(got) == names(bench, "end_to_end", CELL)
        return
    # No TPU plane in a CPU trace: the device metrics are left out, the
    # counters are numbers.
    assert not {"dsa_index_roofline", "dsa_attn_roofline", "dsa_busy_pct",
                "moe_ffn_roofline.long"} & set(got)
    assert got["kv_bytes_per_token"]["value"] == 3 * (256 + 16) * 4
    # Contexts of 40 to 114 positions against 16 kept.
    assert 100 * 16 / 114 < got["dsa_selected_pct"]["value"] < 100 * 16 / 40
    assert got["compiles_in_window.long"]["value"] == 0
    assert got["preemptions.long"]["value"] == 0
    assert got["out_tokens_per_s.long"]["value"] > 0


# ---- the readers on a recorded step log and trace -----------------------------


class Engine:
    def __init__(self, steps):
        self.log = {"steps": steps, "oldest_start": 0.0}

    def step_log(self, since=0.0):
        return self.log


def record(start, end, scored, selected, prefills=None, decodes=24):
    out = {"start": start, "end": end, "decodes": decodes, "phases": []}
    if scored is not None:
        out.update(dsa_rows_scored=scored, dsa_rows_selected=selected)
    if prefills:
        out["prefills"] = prefills
    return out


def run_data(family, cfg, monkeypatch, steps, window=(0.5, 100.0)):
    monkeypatch.setattr(probe.ProbedEngine, "instances", [Engine(steps)])
    return RunData(cell={}, cfg=cfg, mix={}, family=family, chips=1,
                   peaks=None, window=window, end_to_end={},
                   memory_peak_bytes=0)


def test_selected_share_is_a_median_over_plain_decode_steps(
        family, published, monkeypatch):
    """24 sequences at 34k of context keep 2,048 rows each. Three plain
    steps, one that also held a chunk (left out: its queries are the
    prompt's), one outside the window, one that decoded nothing."""
    kept = 24 * 2048
    steps = [record(1, 2, 800000, kept), record(2, 3, 819200, kept),
             record(3, 4, 900000, kept),
             record(4, 5, 9000000, kept + 4096 * 2048,
                    prefills=[{"tokens": 4096}]),
             record(200, 201, kept, kept),
             record(5, 6, 1000, 1000, decodes=0)]
    data = run_data(family, published, monkeypatch, steps)
    assert read("dsa_selected_pct", data) == pytest.approx(
        100 * kept / 819200)


def test_a_program_without_the_fields_gives_nothing(family, published,
                                                    monkeypatch):
    """The parent's records (no indexer, so neither field), a program with
    no step log, a run with no trace: ``None`` from all four, no raise."""
    data = run_data(family, published, monkeypatch,
                    [record(1, 2, None, None), record(2, 3, None, None)])
    assert [read(name, data) for name in NEW] == [None] * 4
    monkeypatch.setattr(probe.ProbedEngine, "instances", [object()])
    assert read("dsa_selected_pct", data) is None
    monkeypatch.setattr(probe.ProbedEngine, "instances", [])
    assert [read(name, data) for name in NEW] == [None] * 4


class Step:
    """A probe's step with the program's record of it."""

    def __init__(self, fields, prefills=0):
        self.decodes, self.prefills = 24, prefills
        self.program = type("Record", (), {"fields": fields})()


def traced_run(family, published, fields, events):
    from perfbench import peaks

    trace = trace_reduce.Trace.from_json({
        "device": {"0": {"XLA Ops": events}},
        "host": {"main": [["pb.engine.step", 0.0, 1.0],
                          ["pb.engine.step", 1.0, 2.0],
                          ["pb.engine.step", 2.0, 3.0]]}})
    data = RunData(cell={}, cfg=published, mix={}, family=family, chips=1,
                   peaks=peaks.PEAKS["TPU v5 lite"], window=(0.0, 3.0),
                   end_to_end={}, memory_peak_bytes=0)
    data.trace = trace
    data.traced_steps = [Step(fields[0]), Step(fields[1], prefills=1),
                         Step(fields[2])]
    return data


def call(head, kind="custom-call"):
    return f"%{head} = bf16[24,64,512]{{2,1,0}} {kind}(%x)"


def test_the_device_readers_on_a_recorded_trace(family, published):
    """Two plain steps and one that held a chunk (left out, with every
    event inside it). A step: 5 layers' index kernels 2 ms, the choice's
    fusions 3 ms (no name of their own: left out), the gather of 24 x
    2,048 rows of 640 lanes 1 ms, attention kernels 2 ms, something else
    12 ms."""
    scored, kept = 24 * 34000, 24 * 2048
    fields = [{"dsa_rows_scored": scored, "dsa_rows_selected": kept}] * 3
    events = []
    for base in (0.0, 1.0, 2.0):
        events += [
            [call("_dsa_index_pallas.3"), base + 0.100, base + 0.102],
            [call("fusion.7", "fusion"), base + 0.110, base + 0.113],
            [f"%fusion.2 = bf16[49152,640]{{1,0:T(8,128)(2,1)}} fusion(%p)",
             base + 0.120, base + 0.121],
            [call("_dsa_attend_pallas.4"), base + 0.130, base + 0.132],
            [call("fusion.9", "fusion"), base + 0.200, base + 0.212]]
    data = traced_run(family, published, fields, events)
    assert read("dsa_busy_pct", data) == pytest.approx(100 * 5 / 20)
    # 5 layers x 816,000 keys x 256 B = 1.04 GB in 2 x 2 ms at 819 GB/s.
    peaks_ = data.peaks
    index_bytes = 2 * 5 * scored * 128 * 2
    assert read("dsa_index_roofline", data) == pytest.approx(
        100 * (index_bytes / peaks_.hbm_bytes_per_s) / 0.004)
    # 5 x 49,152 rows x 1,152 B = 283 MB in 2 x (1 + 2) ms.
    attn_bytes = 2 * 5 * kept * 576 * 2
    assert read("dsa_attn_roofline", data) == pytest.approx(
        100 * (attn_bytes / peaks_.hbm_bytes_per_s) / 0.006)
    # The parent's trace has neither kernel.
    bare = traced_run(family, published, fields,
                      [e for e in events if "_dsa_" not in e[0]])
    assert [read(n, bare) for n in NEW[:3]] == [None] * 3


def test_benchmark_entries_of_the_cell():
    bench = benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert callable(byname.load_reader([run.HERE], name).read)
        assert (entries[name]["unit"], entries[name]["moves"],
                entries[name]["workloads"]) == ("%", "tpot_mean_ms", [CELL])
    assert [entries[n]["source"] for n in NEW] \
        == ["device_trace"] * 3 + ["program_counter"]
    assert [entries[n]["layer"] for n in NEW] \
        == ["kernels"] * 3 + ["KV cache"]
    # Found by name, not by place: the next PR appends after these.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = next(c for c in bench["configs"] if c["name"] == "glm-5")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("glm-5", "sparse-decode", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert names(bench, "end_to_end", CELL) == {"tpot_mean_ms", "setup_s"}
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert {*NEW, "kv_bytes_per_token", "tpot_p50_ms"} <= listed
    # Every quantity a held cell shares with the others, under ``.long``.
    assert {m["name"] for m in bench["per_layer"]
            if m["name"].endswith(".long")} <= listed
    # Their count is every live row, and their kernel is not on the path.
    assert not {"mla_attn_roofline", "mla_attn_busy_pct",
                "paged_attn_roofline", "moe_zero_pairs_pct"} & listed
    # Appended only: ten cells, still one on four chips.
    assert [w["name"] for w in bench["workloads"]] == [
        "medium-train", "xl-batch-decode", "xl-train-fsdp4",
        "olmoe-batch-decode", "mellum2-long-decode", "joyai-latent-decode",
        "kexaone-selfdraft-decode", "lfm2-hybrid-decode",
        "longcat-shortcut-decode", CELL]
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1


# ---- the configuration and the family's counts, by hand ----------------------


# The ``config`` of the model's row in the driver's catalog of
# architectures (GLM-5), copied: the catalog lies outside the checkout.
CATALOG_SOURCE = "https://huggingface.co/zai-org/GLM-5/blob/main/config.json"
CATALOG_CONFIG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "head_dim": 64, "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_rope_interleave": True, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 202752,
    "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880}


def test_configuration_holds_the_published_numbers(published):
    assert published["source"] == CATALOG_SOURCE
    for key, value in CATALOG_CONFIG.items():
        if key not in published["reduced"]:
            assert published[key] == value, key
    assert published["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                    "vocab_size"]
    assert (published["num_hidden_layers"],
            published["published_num_hidden_layers"],
            published["leading_dense_layers_held"]) == (5, 78, 1)
    assert (published["n_routed_experts"],
            published["published_n_routed_experts"],
            published["experts_held"]) == (8, 256, [0, 8])
    assert (published["vocab_size"], published["published_vocab_size"]) \
        == (19360, 154880)
    # The guide's floors: four layers of the repeated kind, eight experts,
    # an eighth of the vocabulary; no width among the cuts.
    assert published["vocab_size"] * 8 == published["published_vocab_size"]
    assert {"assumed", "deployment", "source", "reduced_why"} \
        <= set(published)
    assert "32 chips share each layer" in published["deployment"]
    assert {"index_keys", "index_k_norm_eps", "no_mtp_module",
            "no_indexer_loss", "cache_rows_as_held",
            "e_score_correction_bias", "e_score_correction_bias_std",
            "compute", "param_dtype", "weights"} <= set(published["assumed"])


def test_counts_of_the_configuration(family, published):
    # q_a 6144 x 2048, q_b 2048 x 16384, kv_a 6144 x 576, kv_b 512 x
    # 28672, o 16384 x 6144.
    matrices = 12582912 + 33554432 + 3538944 + 14680064 + 100663296
    assert matrices == 165019648
    # W_Iqb 2048 x 4096, W_Ik 6144 x 128, W_Iw 6144 x 32.
    indexer = 8388608 + 786432 + 196608
    assert indexer == 9371648
    attention = matrices + 2048 + 512 + indexer + 2 * 128
    dense = 3 * 6144 * 12288
    expert = 3 * 6144 * 2048
    assert (dense, expert) == (226492416, 37748736)
    outside_experts = attention + 2 * 6144 + 6144 * 256 + 256 + expert
    assert round(outside_experts / 1e6, 1) == 213.7
    assert round((attention + 2 * 6144 + dense) / 1e6, 1) == 400.9
    outside = 2 * 19360 * 6144 + 6144
    assert family.param_count(published) \
        == outside + (attention + 2 * 6144 + dense) \
        + 4 * (outside_experts + 8 * expert) == 2701673216
    # 5.40 GB in bf16, as the issue's arithmetic has it (2,701.6 M).
    assert round(family.param_count(published) * 2 / 1e9, 2) == 5.40
    whole = dict(published, num_hidden_layers=78, n_routed_experts=256,
                 experts_held=[0, 256], vocab_size=154880)
    whole.pop("leading_dense_layers_held")
    assert round(family.param_count(whole) / 1e9) == 744  # "744B"
    # Of a token's 8 choices 8 x 8 / 256 = a quarter of one falls on an
    # expert held here, on average.
    assert family.active_param_count(published) \
        == outside + (attention + 2 * 6144 + dense) \
        + 4 * (outside_experts + 0.25 * expert)
    assert family.moe_shape(published) == (4, 8, 8, 6144, 2048, 2)
    assert family.kv_shape(published) == (5, 1, 576 + 128, 2)
    assert family.latent_row_held(published) == 640
    assert family.vocab_rows_held(published) == 19360
    assert family.router_width(published) == 256
    assert family.dense_layers(published) == 1
    pcfg = family.program_config(published)
    assert (pcfg.n_layer, pcfg.n_expert, pcfg.experts_held,
            pcfg.n_expert_held, pcfg.first_dense) == (5, 256, (0, 8), 8, 1)
    s = pcfg.serving
    assert (s.kv_row, s.indexer, s.expert_counts) \
        == (640, (128, 2048), (4, 8))


def test_the_built_tree_has_the_counted_parameters(family, published):
    """The program's own tree, as shapes: its leaves add up to the
    family's count, 2,701.6 M, and its bytes as held to 5.4 GB."""
    import jax

    pcfg = family.program_config(published)
    tree = jax.eval_shape(family.train_parts(pcfg)[0],
                          jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(a.size for a in leaves) == family.param_count(published)
    assert round(sum(a.size for a in leaves) / 1e6, 1) == 2701.7
    held = sum(a.size * a.dtype.itemsize for a in leaves)
    assert round(held / 1e9, 2) == 5.42  # norms, router, bias in float32
    assert {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}


def test_counts_at_the_tiny_size(family):
    with open(os.path.join(GLM, "configs", "tiny-glm.json")) as f:
        tiny = json.load(f)
    indexer = 48 * 4 * 16 + 64 * 16 + 64 * 4 + 2 * 16
    attention = 64 * 48 + 48 + 48 * 4 * 24 + 64 * 136 + 128 \
        + 128 * 4 * 32 + 4 * 16 * 64 + indexer
    expert = 3 * 64 * 32
    routed = attention + 2 * 64 + 64 * 16 + 16 + expert + 4 * expert
    assert family.param_count(tiny) == 2 * 512 * 64 + 64 \
        + attention + 2 * 64 + 3 * 64 * 96 + 2 * routed
    assert family.moe_shape(tiny) == (2, 4, 4, 64, 32, 4)
    assert family.kv_shape(tiny) == (3, 1, 136 + 16, 4)


@pytest.mark.parametrize("scored,selected", [(1, 1), (24 * 34000, 24 * 2048)])
def test_dsa_bytes_and_flops(family, published, scored, selected):
    # A cached position: one key of 128 bf16 values, 32 heads x 128 x 2.
    assert family.dsa_index_bytes(published, scored) == 5 * scored * 256.0
    assert family.dsa_index_flops(published, scored) == 5 * scored * 8192.0
    # A chosen row: 576 bf16 values, 64 heads x 2 x (576 + 512).
    assert family.dsa_attn_bytes(published, selected) \
        == 5 * selected * 1152.0
    assert family.dsa_attn_flops(published, selected) \
        == 5 * selected * 139264.0


def test_expert_layer_flops_and_bytes(family, published):
    # 3 x 6144 x 2048 = 37,748,736 weights an expert: LongCat's shape.
    assert family.expert_ffn_flops(published, 16) == 16 * 75497472.0
    assert family.expert_ffn_bytes(published, 40) == 40 * 75497472.0


# ---- the mix's arithmetic ------------------------------------------------------


def test_mix_is_the_traffic_the_issue_names(mix, published):
    from perfbench import traffic

    assert (mix["kind"], mix["clients"], mix["requests_per_client"],
            mix["order_seed"]) == ("closed", 24, 3, 1)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 16384,
                                    "hi": 49152}
    assert mix["new_tokens"] == {"dist": "const", "value": 8192}
    assert mix["first_wave_new_tokens"] == [341 * (i + 1) for i in range(24)]
    assert mix["window_opens_after_client"] == 0
    assert mix["sampling"] == {"temperature": 1.0}
    assert mix["trace_seconds"] == 2.0
    options = mix["engine_options"]
    assert (options["page_size"], options["max_num_seqs"],
            options["max_model_len"], options["prefill_chunk"],
            options["chunk_buckets"], options["prefill_buckets"],
            options["decode_buckets"]) \
        == (128, 24, 57344, 4096, [4096], [4096], [24])
    # Every client's prompt + 8,192 tokens at once, in whole pages, + 36
    # spare + the scratch page: nothing can be preempted.
    sizes = traffic.quantile_sizes(mix["prompt_tokens"], 24)
    assert round(float(sizes.mean()), -2) == 29800
    at_once = sum(-(-(int(n) + 8192) // 128) for n in sizes)
    assert at_once == 7140 and options["num_pages"] == at_once + 36 + 1
    assert sum(int(n) + 8192 for n in sizes) == 912388  # tokens held
    assert max(sizes) + 8192 <= options["max_model_len"]
    # The check's prompts are served whole and are longer than what a
    # query keeps: the whole-prompt program selects.
    check = mix["check"]
    assert check["prompt_tokens"] == [3300, 4000]
    assert published["index_topk"] < min(check["prompt_tokens"])
    assert max(check["prompt_tokens"]) <= options["prefill_buckets"][-1] \
        == options["prefill_chunk"]
    assert check["decode_positions"] == 8
    # 5 layers x (1,280 + 256) B a token as held: 7.06 GB of pools.
    assert options["num_pages"] * 128 * 5 * (1280 + 256) == 7055278080
    # One seat turns over every 341 decode steps from the window's first.
    plan = traffic.closed_schedule(mix, 0)
    turns = traffic.simulate_closed_turnovers(plan, 24 * 341)
    assert turns[:24] == [341 * (i + 1) - 1 for i in range(24)]
