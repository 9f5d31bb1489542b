"""The ``exaone_moe`` family, its cell and the readers of a step that
drafts: a tiny K-EXAONE (the dense layer, S S F S and the module; half of
the experts held) served end to end on the CPU through ``run.run_cell``
under the mix's own sampling, its decode step verifying two positions a
sequence (the probe's contract: ``correct`` true, and false for an engine
that keeps a rejected draft's row); the three ``spec_*`` readers; the
configuration against the catalog's row; the family's counts against
numbers worked out by hand and against the program's own bytes; the mix
file's page arithmetic. (The reference against the program row by row,
the module's drafts and the sampling's distribution are tier-1:
``tests/test_exaone_moe.py``.)"""

import json
import math
import os

import pytest

from perfbench import byname, probe, run, traffic
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import SEED, names
from raytpu.inference.engine import InferenceEngine

HERE = os.path.dirname(os.path.abspath(__file__))
KEXAONE = os.path.join(HERE, "kexaone")
CELL = "kexaone-selfdraft-decode"
NEW = ("spec_accept_pct", "spec_tokens_per_step", "spec_draft_ms_p50")


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


def benchmark(tiny=None):
    """``BENCHMARK.json`` and a cell ``tiny`` of the tiny configuration
    that reports what ``kexaone-selfdraft-decode`` reports."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if tiny:
        bench["configs"].append({"name": "tiny-kexaone",
                                 "source": "rehearsal", "file": "-",
                                 "reduced": [], "why": "-"})
        bench["workloads"].append({"name": tiny, "config": "tiny-kexaone",
                                   "traffic": tiny, "chips": 1,
                                   "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(tiny)
    return bench


@pytest.fixture(scope="module")
def family():
    return run.load_family([run.HERE], {"family": "exaone_moe"})


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(run.HERE, "configs",
                           "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(run.HERE, "traffic",
                           "selfdraft-decode.json")) as f:
        return json.load(f)


# ---- a tiny model through the command path ------------------------------------


def tiny_cell(tmp_path, traced):
    """(With ``short_runs``: the tiny mix's requests are 24 tokens.)"""
    bench = benchmark(tiny="tiny-selfdraft-decode")
    result = run.run_cell(bench, [KEXAONE, run.HERE],
                          "tiny-selfdraft-decode", SEED, 2.0, traced,
                          require_tpu=False, work_dir=str(tmp_path))
    return bench, result, probe.ProbedEngine.instances[-1]


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_kexaone_cell_end_to_end(traced, tmp_path, short_runs):
    bench, result, engine = tiny_cell(tmp_path, traced)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    assert compared["check_decode_rows_min"][0] \
        <= compared["check_decode_rows_min"][1] == 1e-4
    assert compared["compiles_in_window"] == [0, 0]
    stats = engine.stats()
    # Five layers' pools and the module's; nothing preempted; the
    # requests were stochastic and every decode step drafted.
    assert len(engine.cache.k) == 6 and len(engine.cache.kinds) == 2
    assert stats["num_preemptions"] == 0
    assert stats["drafted_tokens"] > 50
    steps = [s for s in engine.step_log()["steps"] if s["decodes"]]
    assert all(s["drafted"] == s["decodes"] <= s["emitted"]
               <= 2 * s["decodes"] for s in steps)
    assert any(s["sampled_stochastic"] for s in steps)
    assert all({"infer.decode.verify", "infer.decode.accept",
                "infer.decode.draft"} <= {p[0] for p in s["phases"]}
               for s in steps)
    got = result["metrics"]
    if not traced:
        # Held to the mean time per output token alone (PERF.md section
        # 2): the window over what its steps gave a stream, never zero.
        assert set(got) == names(bench, "end_to_end", CELL) \
            == {"tpot_mean_ms", "setup_s"}
        assert math.isfinite(got["tpot_mean_ms"]["value"]) \
            and got["tpot_mean_ms"]["value"] > 0
        return
    for name in NEW + ("moe_experts_touched_pct.long", "tpot_p50_ms",
                       "decode_batch_mean.long", "decode_launch_ms_p50.long",
                       "out_tokens_per_s.long", "itl_p95_ms.long"):
        assert math.isfinite(got[name]["value"]), name
    assert 0.0 <= got["spec_accept_pct"]["value"] <= 100.0
    assert 1.0 <= got["spec_tokens_per_step"]["value"] <= 2.0
    assert got["spec_tokens_per_step"]["value"] == pytest.approx(
        1 + got["spec_accept_pct"]["value"] / 100, abs=0.05)
    assert not {"paged_attn_roofline", "mla_attn_roofline"} & set(got)


class KeepsEveryDraft(InferenceEngine):
    """An engine at fault: it keeps a rejected draft's row too, and hands
    the stream a token for it."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        accept = self._accept_fn

        def kept(*a):
            ids, n = accept(*a)
            return ids.at[:, 1].set(abs(ids[:, 1])), n * 0 + 2

        self._accept_fn = kept


def test_an_engine_that_keeps_a_rejected_row_reads_not_correct(tmp_path):
    bases = probe.ProbedEngine.__bases__
    probe.ProbedEngine.__bases__ = (KeepsEveryDraft,)
    try:
        _, result, _ = tiny_cell(tmp_path, False)
    finally:
        probe.ProbedEngine.__bases__ = bases
    assert result["correct"] is False
    read_, limit = result["compared"]["check_rel_err"]
    assert read_ > limit
    assert result["failed"] == 0   # the streams themselves were well


# ---- the readers -----------------------------------------------------------------


def run_data(family, cfg, steps):
    data = RunData(cell={}, cfg=cfg, mix={"engine_options": {}},
                   family=family, chips=1, peaks=None, window=(0.0, 100.0),
                   end_to_end={}, memory_peak_bytes=0)

    class Engine:
        def step_log(self, since=0.0):
            return {"oldest_start": 0.0, "steps": steps}

    probe.ProbedEngine.instances[:] = [Engine()]
    return data


def step(t, decodes, accepted=None, emitted=None, draft_ms=None):
    fields = {} if accepted is None else {
        "drafted": decodes, "accepted": accepted, "emitted": emitted}
    phases = [] if draft_ms is None else [
        ["infer.decode.draft", t, t + draft_ms / 1e3]]
    return {"start": t, "end": t + 0.5, "decodes": decodes,
            "phases": phases, **fields}


def test_spec_readers_on_a_hand_made_log(family, published):
    saved = list(probe.ProbedEngine.instances)
    try:
        steps = [step(1.0, 16, 4, 20, 0.5), step(2.0, 16, 6, 22, 0.7),
                 step(3.0, 8, 0, 8, 0.9), step(4.0, 0)]   # a prefill's step
        data = run_data(family, published, steps)
        assert read("spec_accept_pct", data) == 100.0 * 10 / 40
        assert read("spec_tokens_per_step", data) == 50 / 40
        assert read("spec_draft_ms_p50", data) == pytest.approx(0.7)
        # A program that does not draft (the parent's): nothing is read.
        plain = run_data(family, published, [step(1.0, 16), step(2.0, 16)])
        assert [read(n, plain) for n in NEW] == [None, None, None]
    finally:
        probe.ProbedEngine.instances[:] = saved


def test_readers_constants_are_the_benchmarks_entries():
    bench = benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        mod, entry = byname.load_reader([run.HERE], name), entries[name]
        assert callable(mod.read)
        assert (entry["layer"], entry["moves"], entry["workloads"]) \
            == ("speculation", "tpot_mean_ms", [CELL])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("k-exaone-236b-a23b", "selfdraft-decode", 1)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"moe_ffn_roofline.long", "moe_ffn_busy_pct.long",
            "moe_experts_touched_pct.long", "moe_load_max_over_mean.long",
            "decode_batch_mean.long", "device_idle_pct.serve.long",
            "hbm_peak_gb.serve.long", "idle_pct.wait.long",
            "out_tokens_per_s.long", "itl_p95_ms.long"} <= listed
    # A window layer's whole context is not what its kernel reads. The
    # cell is held to the mean time per output token (PR 46): what it
    # shares with the other serving cells it reads under the ``.long``
    # names, and under no name that has such a twin; the median over runs
    # of 64 tokens stands beside them, per layer.
    assert "paged_attn_roofline" not in listed
    twins = {n for n in entries if n.endswith(".long")}
    assert twins <= listed
    assert not {n[:-5] for n in twins} & listed
    assert listed == twins | set(NEW) | {"tpot_p50_ms"}
    assert names(bench, "end_to_end", CELL) == {"tpot_mean_ms", "setup_s"}


# ---- the configuration and the family's counts, by hand ----------------------


# The ``config`` of the model's row in the driver's catalog of
# architectures (K-EXAONE-236B-A23B), its numbers and flags copied (the
# lists a layer are checked by their pattern): the catalog lies outside
# the checkout.
CATALOG_SOURCE = ("https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/"
                  "blob/main/config.json")
CATALOG_CONFIG = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "max_position_embeddings": 262144, "model_type": "exaone_moe",
    "moe_intermediate_size": 2048, "mtp_layer_types": ["full_attention"],
    "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}


def test_configuration_holds_the_published_numbers(published):
    assert published["source"] == CATALOG_SOURCE
    for key, value in CATALOG_CONFIG.items():
        if key not in published["reduced"]:
            assert published[key] == value, key
    assert published["reduced"] == ["num_hidden_layers", "num_experts",
                                    "vocab_size"]
    assert (published["num_hidden_layers"],
            published["published_num_hidden_layers"]) == (5, 48)
    assert (published["num_experts"], published["published_num_experts"],
            published["experts_held"]) == (16, 128, [0, 16])
    assert (published["vocab_size"], published["published_vocab_size"]) \
        == (19200, 153600) and 19200 == 150 * 128 == 153600 // 8
    # The lists a layer, whole as published: S S S F twelve times.
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert published["layer_types"] == period * 12
    assert published["sliding_windows"] == [128, 128, 128, 0] * 12
    assert published["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert {"assumed", "deployment", "source"} <= set(published)
    assert "8 chips share each layer" in published["deployment"]
    assert {"block_form", "qk_norm", "rope", "mtp_module", "compute",
            "e_score_correction_bias", "weights"} <= set(published["assumed"])


def test_counts_of_the_configuration(family, published):
    # Attention: q 6144 x 8192, k and v 6144 x 1024 each, o 8192 x 6144.
    attention = 50331648 + 2 * 6291456 + 50331648
    assert attention == 113246208
    expert = 3 * 6144 * 2048
    assert expert == 37748736
    outside = attention + expert + 6144 * 128   # + shared expert + router
    assert outside == 151781376                 # "151.8 M"
    small = 2 * 128 + 2 * 6144 + 128            # head norms, block norms, bias
    routed = outside + 16 * expert + small
    dense = attention + 3 * 6144 * 18432 + 2 * 128 + 2 * 6144
    vocabulary = 2 * 19200 * 6144 + 6144
    module = routed + 2 * 6144 * 6144 + 3 * 6144
    assert family.param_count(published) \
        == vocabulary + dense + 4 * routed + module == 4543318144
    # 9.1 GB in bf16, as the issue's arithmetic has it.
    assert round(family.param_count(published) * 2 / 1e9, 1) == 9.1
    assert family.moe_shape(published) == (5, 16, 8, 6144, 2048, 2)
    assert family.kv_shape(published) == (6, 8, 128, 2)
    assert family.layers_by_kind(published) == (2, 4)
    assert family.vocab_rows_held(published) == 19200
    # A token uses 8 x 16 / 128 = 1 routed expert a layer here.
    assert family.active_param_count(published) \
        == family.param_count(published) - 5 * 15 * expert
    pcfg = family.program_config(published)
    assert (pcfg.n_expert, pcfg.experts_held, pcfg.n_expert_held) \
        == (128, (0, 16), 16)
    assert pcfg.layer_types == ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")
    served = pcfg.serving
    assert served.expert_counts == (4, 16) and served.drafting.pools == 1
    assert [pcfg.ffn_width(i) for i in (0, 1, 4)] == [18432, None, None]
    assert (pcfg.qk_head_norm, pcfg.rope_kinds, pcfg.window) \
        == (True, ("sliding_attention",), 128)


def test_counts_are_the_programs_bytes_at_a_scaled_down_copy(family):
    """The same count functions over the tiny configuration against the
    bytes of the tree the engine serves from (float32)."""
    import jax

    with open(os.path.join(KEXAONE, "configs", "tiny-kexaone.json")) as f:
        tiny = json.load(f)
    pcfg = family.program_config(tiny, {"attn_impl": "reference",
                                        "paged_attn": "reference"})
    params = family.train_parts(pcfg)[0](jax.random.PRNGKey(0))
    eng = InferenceEngine(pcfg, params, page_size=8, max_num_seqs=2,
                          max_model_len=64)
    assert sum(eng.stats()["param_bytes"].values()) \
        == 4 * family.param_count(tiny)
    layers, kv, d, itemsize = family.kv_shape(tiny)
    assert (layers, itemsize) == (6, 4) and len(eng.cache.k) == 6
    assert eng.cache.token_bytes == 2 * layers * kv * d * itemsize
    assert eng._expert_tokens.shape == family.moe_shape(tiny)[:2]


def test_expert_and_by_kind_bytes(family, published):
    # 3 x 6144 x 2048 = 37,748,736 weights an expert, 75.5 MB in bf16.
    assert family.expert_ffn_flops(published, 32) == 32 * 75497472.0
    assert family.expert_ffn_bytes(published, 70) == 70 * 75497472.0
    # A page of 128 rows of K and of V, 8 heads of 128 in bf16: two full
    # pools (layer 3's and the module's) and four window pools.
    page = 2 * 128 * 8 * 128 * 2
    assert family.paged_attn_bytes_by_kind(published, 128, 10, 3) \
        == 2 * 10 * page + 4 * 3 * page


# ---- the mix ---------------------------------------------------------------------


def test_the_mix_holds_the_issues_traffic(mix):
    assert (mix["kind"], mix["clients"], mix["requests_per_client"],
            mix["order_seed"], mix["window_opens_after_client"]) \
        == ("closed", 16, 2, 1, 0)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 512,
                                    "hi": 2048}
    assert mix["new_tokens"] == {"dist": "const", "value": 8192}
    assert mix["first_wave_new_tokens"] == [512 * (i + 1)
                                            for i in range(16)]
    assert mix["sampling"] == {"temperature": 1.0}
    assert mix["check"]["decode_positions"] == 16
    assert all(n > 128 for n in mix["check"]["prompt_tokens"])  # the window
    assert traffic.request_sampling(mix, SEED, 5)["temperature"] == 1.0


def test_page_arithmetic_of_the_mix(mix, published):
    opts = mix["engine_options"]
    page, seqs = opts["page_size"], opts["max_num_seqs"]
    assert (page, seqs, opts["decode_buckets"]) == (128, 16, [16])
    # The longest prompt, its 8,192 tokens and the draft's slot, in whole
    # pages: 81; every client's at once and the scratch page.
    longest = mix["prompt_tokens"]["hi"] + mix["new_tokens"]["value"] + 1
    assert -(-longest // page) == 81
    assert opts["max_model_len"] == 81 * page == 10368
    assert opts["num_pages"] == seqs * 81 + 1 == 1297
    sizes = traffic.quantile_sizes(mix["prompt_tokens"], mix["clients"])
    assert 512 <= min(sizes) and max(sizes) <= 2048 \
        <= opts["prefill_buckets"][0] <= opts["prefill_chunk"]
    # Two full-attention pools (K and V): 1.36 GB in bf16.
    pool = opts["num_pages"] * page * 8 * 128 * 2
    assert round(2 * 2 * pool / 1e9, 2) == 1.36
    # Every table width the traffic and the check reach is warmed: the
    # check's 4, and 8 ... 64, 81 of contexts up to max_model_len.
    widths = {w for w in (1, 2, 4, 8, 16, 32, 64, 81)}
    reached = set()
    for prompt, new in mix["warmup"]:
        for tokens in (prompt + 2, prompt + new + 1):
            pages = -(-tokens // page)
            reached.add(min(w for w in widths if w >= pages))
    assert reached == {4, 8, 16, 32, 64, 81}
    needed = {min(w for w in widths if w >= -(-(n + 2) // page))
              for n in list(sizes) + mix["check"]["prompt_tokens"]}
    assert needed <= reached
