"""The ``ling_hybrid`` family, its cell and the readers of the delta-rule
linear-attention layers: a tiny Ling (a dense KDA layer and one whole
period: five KDA layers and a latent one, routed by groups) served end to
end on the CPU through ``run.run_cell`` under the mix's own sampling, the
decode kernel interpreted, its prompts through whole-prompt and chunk
programs that leave two state arrays a layer at a seat, its decode rows
through their seats and one latent pool; the readers
``kda_state_roofline``, ``kda_busy_pct`` and ``state_bytes_per_seq``; the
configuration against the catalog's row; the family's counts against
numbers worked out by hand and against the program's own bytes; the mix
file's page arithmetic. (The reference against the program row by row, the
chunked form against the recurrence, seats reused and the shares of a
layer routed by groups are tier-1: ``tests/test_ling_hybrid.py``.)"""

import json
import math
import os

import jax.numpy as jnp
import pytest

from perfbench import byname, probe, run, trace_reduce, traffic
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import SEED, names
from raytpu.inference.engine import InferenceEngine

HERE = os.path.dirname(os.path.abspath(__file__))
LING = os.path.join(HERE, "ling")
CELL = "ling-kda-decode"
NEW = ("kda_state_roofline", "kda_busy_pct", "state_bytes_per_seq")


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


def benchmark(tiny=None):
    """``BENCHMARK.json`` and a cell ``tiny`` of the tiny configuration
    that reports what ``ling-kda-decode`` reports."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if tiny:
        bench["configs"].append({"name": "tiny-ling", "source": "rehearsal",
                                 "file": "-", "reduced": [], "why": "-"})
        bench["workloads"].append({"name": tiny, "config": "tiny-ling",
                                   "traffic": tiny, "chips": 1,
                                   "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(tiny)
    return bench


@pytest.fixture(scope="module")
def family():
    return run.load_family([run.HERE], {"family": "ling_hybrid"})


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(run.HERE, "configs",
                           "ling-3.0-flash-vl.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(run.HERE, "traffic", "kda-decode.json")) as f:
        return json.load(f)


# ---- a tiny model through the command path ----------


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_ling_cell_end_to_end(traced, tmp_path, short_runs):
    bench = benchmark(tiny="tiny-kda-decode")
    result = run.run_cell(bench, [LING, run.HERE], "tiny-kda-decode",
                          SEED, 2.0, traced, require_tpu=False,
                          work_dir=str(tmp_path))
    engine = probe.ProbedEngine.instances[-1]
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    assert compared["check_decode_rows_min"][0] \
        <= compared["check_decode_rows_min"][1] == 2e-4
    assert compared["compiles_in_window"] == [0, 0]
    stats = engine.stats()
    # One latent pool, two state arrays for each of six KDA layers, a seat
    # a slot; prompts went whole and through chunks; nothing preempted.
    assert len(engine.cache.k) == 1 and engine.cache.v == [] \
        and len(engine.cache.state) == 12
    assert stats["state_seats_total"] == 4 and stats["num_preemptions"] == 0
    assert stats["prefill_compiles"] and stats["chunk_prefill_compiles"]
    a_seat = 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    steps = engine.step_log()["steps"]
    assert max(s["state_seats"] for s in steps) == 4
    assert all(s["state_bytes"] == s["state_seats"] * a_seat for s in steps)
    got = result["metrics"]
    if not traced:
        assert set(got) == names(bench, "end_to_end", CELL) \
            == {"tpot_mean_ms", "setup_s"}
        assert math.isfinite(got["tpot_mean_ms"]["value"]) \
            and got["tpot_mean_ms"]["value"] > 0
        return
    for name in ("state_bytes_per_seq", "state_seats_peak_pct",
                 "kv_bytes_per_token", "moe_experts_touched_pct.long",
                 "tpot_p50_ms", "decode_batch_mean.long",
                 "out_tokens_per_s.long"):
        assert math.isfinite(got[name]["value"]), name
    assert got["state_seats_peak_pct"]["value"] == 100.0
    assert got["state_bytes_per_seq"]["value"] == a_seat
    # One latent row a token: 128 + 8 values on 256 lanes, float32.
    assert got["kv_bytes_per_token"]["value"] == 256 * 4
    # The CPU's trace has no device plane: the kernel's readers are silent.
    assert not {"kda_state_roofline", "kda_busy_pct", "paged_attn_roofline",
                "paged_attn_plain_roofline"} & set(got)


# ---- the readers ----------


class Step:
    """A probe's record of a traced step, with the program's fields."""

    def __init__(self, decodes, live_pages, seats, prefills=0, bytes_=0):
        self.decodes, self.prefills = decodes, prefills
        self.live_pages = live_pages
        self.program = type("Program", (), {"fields": {
            "live_pages": live_pages, "state_seats": seats,
            "state_bytes": bytes_}})()


def test_state_bytes_per_seq_reads_the_last_step_that_held_a_seat(
        family, published, mix):
    steps = [Step(128, 9000, 128, bytes_=128 * 13025280),
             Step(0, 0, 0)]
    data = RunData(cell={}, cfg=published, mix=mix, family=family, chips=1,
                   peaks=None, window=(0.0, 1.0), end_to_end={},
                   memory_peak_bytes=0, engine_steps=steps)
    assert read("state_bytes_per_seq", data) == 13025280.0 \
        == family.state_bytes_per_seq(published)
    for s in steps:
        s.program.fields.clear()
    assert read("state_bytes_per_seq", data) is None


def hlo(head, text="f32[8]{0} fusion(f32[8]{0} %x)"):
    return f"%{head} = {text}"


def test_kernel_readers_on_a_hand_made_trace(family, published, mix):
    """Two traced plain decode steps of 128 rows and a step that holds a
    prompt, which is left out: in each plain step the state kernel runs
    six times 1 ms, two products that name a KDA parameter 0.5 ms each,
    and 4 ms of other work."""
    from perfbench import peaks

    def a_step(t0):
        events, t = [], t0
        for i in range(6):
            events.append(trace_reduce.Event(hlo(
                f"_kda_state_pallas.{i}",
                "(f32[128,32,128]{2,1,0}, f32[129,32,128,128]{3,2,1,0}) "
                "custom-call(...)"), t, t + 0.001))
            t += 0.001
        for name in ("q_proj", "f_proj"):
            events.append(trace_reduce.Event(hlo(
                "fusion.7", "bf16[128,4096]{1,0} fusion(bf16[128,2560]{1,0} "
                f"%x, bf16[2560,4096]{{1,0}} %params__layers_1____kda____"
                f"{name}____kernel__.1)"), t, t + 0.0005))
            t += 0.0005
        events.append(trace_reduce.Event(hlo("fusion.9"), t, t + 0.004))
        return events

    spans = [trace_reduce.Event("pb.engine.step", t, t + 0.02)
             for t in (1.0, 2.0, 3.0)]
    trace = trace_reduce.Trace(
        device={0: {"XLA Ops": a_step(1.001) + a_step(2.001)
                    + a_step(3.001)}},
        host={"main": spans})
    steps = [Step(128, 9000, 128), Step(128, 9100, 128),
             Step(128, 9100, 128, prefills=1)]
    data = RunData(cell={}, cfg=published, mix=mix, family=family, chips=1,
                   peaks=peaks.PEAKS["TPU v5 lite"], window=(0.0, 4.0),
                   end_to_end={}, memory_peak_bytes=0, trace=trace,
                   traced_steps=steps)
    # A sequence a layer: 2 x 2,097,152 B of matrices and 5 x 4,096 + 32
    # float32 of vectors; the tails are the convolution's, whose events
    # the metric does not time.
    a_row = 2 * 2097152 + 4 * (5 * 4096 + 32)
    assert family.kda_state_bytes(published, 256) == 256 * 6 * a_row
    assert family.kda_state_flops(published, 256) \
        == 256 * 6 * 32 * 7 * 128 * 128
    least = 256 * 6 * a_row / data.peaks.hbm_bytes_per_s
    assert read("kda_state_roofline", data) == pytest.approx(
        100.0 * least / 0.012)
    assert read("kda_busy_pct", data) == pytest.approx(
        100.0 * (0.006 + 0.001) / 0.011)
    # No kernel in the trace (another family, the parent, the CPU): none.
    data.trace = trace_reduce.Trace(
        device={0: {"XLA Ops": [trace_reduce.Event(hlo("fusion.9"), 1.001,
                                                   1.005)]}},
        host={"main": spans})
    assert read("kda_state_roofline", data) is None \
        and read("kda_busy_pct", data) is None
    data.trace = None
    assert read("kda_state_roofline", data) is None


def test_readers_constants_are_the_benchmarks_entries():
    bench = benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {"kda_state_roofline": "kernels", "kda_busy_pct": "kernels",
              "state_bytes_per_seq": "KV cache"}
    for name in NEW:
        mod, entry = byname.load_reader([run.HERE], name), entries[name]
        assert callable(mod.read)
        assert (entry["layer"], entry["moves"], entry["workloads"]) \
            == (layers[name], "tpot_mean_ms", [CELL])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("ling-3.0-flash-vl", "kda-decode", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in bench["configs"]
                  if c["name"] == "ling-3.0-flash-vl")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert len(bench["workloads"]) >= 11 \
        and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    twins = {n for n in entries if n.endswith(".long")}
    assert listed >= twins | set(NEW) | {"tpot_p50_ms"}
    assert names(bench, "end_to_end", CELL) == {"tpot_mean_ms", "setup_s"}


# ---- the configuration and the family's counts, by hand ----------


# The ``config`` of the model's row in the driver's catalog of
# architectures (Ling-3.0-flash-VL), copied: the catalog lies outside the
# checkout.
CATALOG_SOURCE = ("https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/"
                  "blob/main/config.json")
CATALOG_CONFIG = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}


def test_configuration_holds_the_published_numbers(published):
    assert published["source"] == CATALOG_SOURCE
    for key, value in CATALOG_CONFIG.items():
        if key not in published["reduced"]:
            assert published[key] == value, key
    assert published["reduced"] == ["num_hidden_layers", "num_experts",
                                    "vocab_size"]
    assert (published["num_hidden_layers"],
            published["published_num_hidden_layers"]) == (7, 42)
    assert (published["num_experts"], published["published_num_experts"],
            published["experts_held"]) == (64, 512, [0, 64])
    assert (published["vocab_size"], published["published_vocab_size"]) \
        == (19648, 157184) and 19648 * 8 == 157184
    assert published["layers_held"] == [1, 6, 7, 8, 9, 10, 11]
    assert {"assumed", "deployment", "source", "reduced_why"} \
        <= set(published)
    assert "eight chips" in published["deployment"] \
        and "one routing group" in published["deployment"]
    assert {"kda_equations", "kda_gate", "kda_gate_init", "no_rope_in_kda",
            "use_qk_norm", "head_wise_gate", "linear_silu",
            "group_norm_size", "state_dtype", "swiglu_clamp_not_built",
            "no_mtp_module", "text_path_only", "group_choice",
            "expert_bias_std", "compute", "weights"} \
        <= set(published["assumed"])


def test_counts_of_the_configuration(family, published):
    e = 2560
    kda = 5 * e * 4096 + 2 * e * 32 + 4 * 12288 + 32 + 4096 + 128
    assert kda == 52646048                       # "52.65 M"
    latent = e * 32 * 192 + e * 576 + 512 + 512 * 32 * 256 + 4096 * e \
        + e * 32
    assert latent == 31965696                    # "31.97 M"
    expert = 3 * e * 768
    assert expert == 5898240
    routed = 64 * expert + expert + e * 512 + 512 + 2 * e
    dense = 3 * e * 6144 + 2 * e
    outside = 2 * 19648 * e + e
    assert family.param_count(published) == (
        outside + (kda + dense) + 5 * (kda + routed) + (latent + routed)) \
        == 2803845056
    assert round(family.param_count(published) * 2 / 1e9, 2) == 5.61
    assert family.active_param_count(published) \
        == family.param_count(published) - 6 * 63 * expert
    assert family.moe_shape(published) == (6, 64, 8, 2560, 768, 2)
    assert family.kv_shape(published) == (1, 1, 576, 2)
    assert family.state_bytes_per_seq(published) \
        == 6 * (2097152 + 73728) == 13025280
    assert family.vocab_rows_held(published) == 19648
    pcfg = family.program_config(published)
    assert pcfg.layer_types == ("kda",) * 6 + ("full_attention",)
    served = pcfg.serving
    assert served.expert_counts == (6, 64) and served.drafting is None
    assert [s is None for s in served.layer_states] \
        == [False] * 6 + [True]
    assert [(a.shape, a.dtype) for a in served.layer_states[0]] == [
        ((32, 128, 128), jnp.float32), ((3, 12288), None)]
    assert served.kv_row == 640
    assert [pcfg.ffn_width(i) for i in (0, 1)] == [6144, None]
    assert (pcfg.n_expert, pcfg.experts_held, pcfg.n_group, pcfg.topk_group,
            pcfg.q_lora_rank, pcfg.attn_head_gate, pcfg.conv_taps,
            pcfg.choice_bias, pcfg.scoring, pcfg.kda_lower_bound) \
        == (512, (0, 64), 8, 4, None, True, 4, 0.01, "sigmoid", -5.0)


def test_counts_are_the_programs_bytes_at_a_scaled_down_copy(family):
    """The same count functions over the tiny configuration against the
    bytes of the tree the engine serves from (float32)."""
    import jax

    with open(os.path.join(LING, "configs", "tiny-ling.json")) as f:
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
    assert eng.cache.state_bytes == family.state_bytes_per_seq(tiny)
    assert eng.stats()["state_bytes"] == 3 * family.state_bytes_per_seq(tiny)
    assert eng._expert_tokens.shape == family.moe_shape(tiny)[:2]


# ---- the mix ----------


def test_the_mix_holds_the_issues_traffic(mix):
    assert (mix["kind"], mix["clients"], mix["order_seed"],
            mix["window_opens_after_client"]) == ("closed", 128, 1, 0)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 1024,
                                    "hi": 4096}
    assert mix["new_tokens"] == {"dist": "const", "value": 6144}
    assert mix["first_wave_new_tokens"] == [48 * (i + 1)
                                            for i in range(128)]
    assert mix["sampling"] == {"temperature": 1.0}
    assert mix["trace_seconds"] == 2.0
    assert mix["check"]["decode_positions"] == 16
    bucket = mix["engine_options"]["prefill_buckets"]
    assert len(bucket) == 1 and all(
        512 < n < bucket[0] for n in mix["check"]["prompt_tokens"])
    assert traffic.request_sampling(mix, SEED, 5)["temperature"] == 1.0
    assert mix["requests_per_client"] >= 3


def test_the_mix_declares_its_serve_options(mix, family, published):
    """``serve_cell.deploy`` binds the deployment with the mix's
    ``engine_options`` and nothing else: what the 128-stream deployment
    needs of the serve layer rides there, and reaches the deployment's
    config and not the engine."""
    from raytpu import serve

    assert mix["engine_options"]["serve_options"] \
        == {"health_check_timeout_s": 600.0}
    cfg = serve.LLMDeployment.bind(
        model=family.SERVE_MODEL, model_config=None,
        engine_options=dict(mix["engine_options"])
    )._ingress.deployment.config
    assert cfg.health_check_timeout_s == 600.0
    assert cfg.max_ongoing_requests == mix["clients"] == 128
    assert serve.LLMDeployment.config.health_check_timeout_s == 30.0


def test_page_arithmetic_of_the_mix(mix, published, family):
    opts = mix["engine_options"]
    page, seqs = opts["page_size"], opts["max_num_seqs"]
    assert (page, seqs, opts["decode_buckets"]) == (128, 128, [128])
    longest = mix["prompt_tokens"]["hi"] + mix["new_tokens"]["value"]
    assert -(-longest // page) == 80
    assert opts["max_model_len"] == 80 * page == 10240
    assert opts["num_pages"] == seqs * 80 + 1 == 10241
    sizes = traffic.quantile_sizes(mix["prompt_tokens"], mix["clients"])
    chunk = opts["prefill_chunk"]
    assert (chunk, opts["chunk_buckets"], opts["prefill_buckets"]) \
        == (2048, [2048], [2048])
    # Half of the prompts fit a chunk and go whole; the others are cut
    # once and carry the state across the cut.
    cuts = [-(-int(n) // chunk) for n in sizes]
    assert (cuts.count(1), cuts.count(2)) == (64, 64)
    assert (int(sizes[0]), int(sizes[-1]), int(sizes.sum())) \
        == (1030, 4074, 283646)
    # One latent pool: 1.68 GB in bf16; 129 rows of state: 1.68 GB; with
    # 5.61 GB of weights 8.97 GB resident, 53 % of the chip.
    pool = opts["num_pages"] * page * 640 * 2
    state = (seqs + 1) * family.state_bytes_per_seq(published)
    assert (round(pool / 1e9, 2), round(state / 1e9, 2)) == (1.68, 1.68)
    assert round((2 * family.param_count(published) + pool + state) / 1e9,
                 2) == 8.97
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
    assert whole and {width(int(n)) for n in sizes if n > chunk} <= chunks
    assert {width(int(n) + 1) for n in sizes} \
        | {width(int(n) + mix["new_tokens"]["value"]) for n in sizes} \
        <= decodes
    assert {width(n + 1) for n in mix["check"]["prompt_tokens"]} \
        | {width(n + 17) for n in mix["check"]["prompt_tokens"]} <= decodes
