"""The ``mellum`` family and the readers of a cache of two kinds of pool:
the family's plain reference against a second, literal transcription of
the layer equations (loops over positions and heads, numpy, float64); a
tiny Mellum2 served end to end on the CPU through ``run.run_cell`` (the
check's prompts, longer than the window, through the whole-prompt
program, the traffic's through chunks, both kinds of pool, kernels
interpreted); the three readers on a hand-made step log and trace; the
family's counts against numbers worked out by hand."""

import json
import math
import os
import types

import numpy as np
import pytest

from perfbench import byname, paged_kinds, probe, run
from perfbench import trace_reduce as tr
from perfbench.peaks import PEAKS
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import SEED, benchmark_with, names

HERE = os.path.dirname(os.path.abspath(__file__))
MELLUM = os.path.join(HERE, "mellum")
CELL = "mellum2-long-decode"
NEW = ("paged_attn_kinds_roofline", "paged_attn_busy_pct",
       "kv_resident_vs_flat_pct")


def read(name, data):
    return byname.load_reader([run.HERE], name).read(data)


@pytest.fixture(scope="module")
def family():
    return run.load_family([run.HERE], {"family": "mellum"})


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(run.HERE, "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(MELLUM, "configs", "tiny-mellum.json")) as f:
        return json.load(f)


# ---- the reference against a literal transcription ---------------------------


def literal_logits(cfg, params, tokens):
    """ISSUE 32's equations, one position, head and expert at a time."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    e, h, kv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    eps, top = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]

    def norm(x, p):
        return x / np.sqrt((x * x).mean() + eps) * f(p["scale"])

    def inv_freq(kind):
        r = cfg["rope_parameters"][kind]
        theta = r["rope_theta"]
        plain = [theta ** (-2 * i / d) for i in range(d // 2)]
        if r["rope_type"] == "default":
            return plain, 1.0
        n = r["original_max_position_embeddings"]
        dim = lambda turns: d * math.log(n / (2 * math.pi * turns)) \
            / (2 * math.log(theta))  # noqa: E731
        low = min(max(math.floor(dim(r["beta_fast"])), 0), d - 1)
        high = min(max(math.ceil(dim(r["beta_slow"])), 0), d - 1)
        out = []
        for i, w in enumerate(plain):
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            out.append(w / r["factor"] * ramp + w * (1 - ramp))
        return out, r["attention_factor"]

    def rope(vec, pos, kind):
        freqs, scale = inv_freq(kind)
        out = np.empty(d)
        for i, w in enumerate(freqs):
            c, s = math.cos(pos * w) * scale, math.sin(pos * w) * scale
            a, b = vec[i], vec[i + d // 2]
            out[i], out[i + d // 2] = a * c - b * s, b * c + a * s
        return out

    xs = [f(params["embed_tokens"]["embedding"])[t] for t in tokens]
    for li in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][li]
        lp = params[f"layers_{li}"]
        a = {k: f(v["kernel"]) for k, v in lp["attn"].items()}
        qs, ks, vs = [], [], []
        for p, x in enumerate(xs):
            y = norm(x, lp["input_norm"])
            q, k, v = y @ a["q_proj"], y @ a["k_proj"], y @ a["v_proj"]
            qs.append([rope(q[r * d:(r + 1) * d], p, kind)
                       for r in range(h)])
            ks.append([rope(k[g * d:(g + 1) * d], p, kind)
                       for g in range(kv)])
            vs.append([v[g * d:(g + 1) * d] for g in range(kv)])
        after = []
        for p, x in enumerate(xs):
            first = max(0, p - cfg["sliding_window"] + 1) \
                if kind == "sliding_attention" else 0
            out = np.zeros(h * d)
            for r in range(h):
                g = r // (h // kv)
                s = np.array([qs[p][r] @ ks[j][g] / math.sqrt(d)
                              for j in range(first, p + 1)])
                w = np.exp(s - s.max())
                w /= w.sum()
                out[r * d:(r + 1) * d] = sum(
                    wj * vs[j][g] for wj, j in zip(w, range(first, p + 1)))
            after.append(x + out @ a["o_proj"])
        xs = []
        m = lp["moe"]
        for x in after:
            y = norm(x, lp["post_attn_norm"])
            z = y @ f(m["router"]["kernel"])
            prob = np.exp(z - z.max())
            prob /= prob.sum()
            chosen = np.argsort(-prob)[:top]
            total = prob[chosen].sum() if cfg["norm_topk_prob"] else 1.0
            ff = np.zeros(e)
            for ex in chosen:
                gate = y @ f(m["wg"][ex])
                ff += prob[ex] / total * (
                    (gate / (1 + np.exp(-gate)) * (y @ f(m["wi"][ex])))
                    @ f(m["wo"][ex]))
            xs.append(x + ff)
    head = f(params["lm_head"]["kernel"])
    return np.stack([norm(x, params["final_norm"]) @ head for x in xs])


def test_reference_is_the_literal_transcription(family, tiny):
    import jax
    import jax.numpy as jnp

    pcfg = family.program_config(tiny, {"attn_impl": "reference"})
    params = family.train_parts(pcfg)[0](jax.random.PRNGKey(3))
    # 40 positions: five windows of 8, past the 32 of YaRN's original
    # length, so both of its regimes and the ramp between are reached.
    tokens = np.random.default_rng(0).integers(1, 512, size=40)
    want = literal_logits(tiny, params, tokens)
    got = np.asarray(family.logits(tiny, params, jnp.asarray(tokens)[None]))
    assert np.abs(got[0] - want).max() <= 2e-5 * np.abs(want).max()
    rows = np.asarray(family.logits(tiny, params, jnp.asarray(tokens)[None],
                                    rows=[7, 39]))
    assert np.abs(rows[0] - want[[7, 39]]).max() \
        <= 2e-5 * np.abs(want).max()
    # The blocks of query rows are an implementation of the same sum.
    family.SCORE_ENTRIES, kept = 8 * 40 * 8, family.SCORE_ENTRIES
    try:
        blocked = np.asarray(family.logits(tiny, params,
                                           jnp.asarray(tokens)[None]))
    finally:
        family.SCORE_ENTRIES = kept
    assert np.abs(blocked - got).max() <= 1e-5 * np.abs(want).max()
    # And the program's training forward is the same function.
    from raytpu.models.mixtral import Mellum
    ours = np.asarray(Mellum(pcfg).apply(
        {"params": params}, jnp.asarray(tokens)[None]))
    assert np.abs(ours[0] - want).max() <= 1e-4 * np.abs(want).max()
    loss = float(family.loss(tiny, params, jnp.asarray(tokens)[None]))
    lse = np.log(np.exp(want[:-1]).sum(-1))
    by_hand = (lse - want[np.arange(39), tokens[1:]]).mean()
    assert loss == pytest.approx(by_hand, rel=1e-5)


def test_yarn_frequencies_at_the_published_numbers(family, published):
    freqs, scale = family.inv_frequencies(published, "full_attention")
    plain, one = family.inv_frequencies(published, "sliding_attention")
    assert one == 1.0 and scale == 1.2772588722239782
    assert scale == pytest.approx(0.1 * math.log(16) + 1)
    assert plain[1] == pytest.approx(500000 ** (-2 / 128))
    # dim(32) = 18.08 and dim(1) = 34.98: up to pair 18 the plain
    # frequency, from pair 35 on a sixteenth of it, a line between.
    assert np.array_equal(freqs[:19], plain[:19])
    assert np.allclose(freqs[35:], plain[35:] / 16)
    ramp = (27 - 18) / (35 - 18)
    assert freqs[27] == pytest.approx(plain[27] / 16 * ramp
                                      + plain[27] * (1 - ramp))


# ---- a tiny Mellum2 through the command path ---------------------------------


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_mellum_cell_end_to_end(traced, tmp_path, short_runs):
    bench = benchmark_with({"tiny-long-decode": (CELL, 1)},
                           config="tiny-mellum")
    result = run.run_cell(bench, [MELLUM, run.HERE], "tiny-long-decode",
                          SEED, 2.0, traced, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    got = result["metrics"]
    engine = probe.ProbedEngine.instances[-1]
    stats = engine.stats()
    # The check went through the whole-prompt program, the traffic
    # through chunks; both kinds of pool were written and slid.
    assert stats["prefill_compiles"] == {"16": 1}
    assert set(stats["chunk_prefill_compiles"]) >= {"16x8", "16x16"}
    assert stats["kv_pool_bytes_by_kind"]["window"] > 0
    assert stats["num_preemptions"] == 0
    log = engine.step_log()["steps"]
    assert sum(s["window_pages_released"] for s in log) > 0
    # What the window pools own stays inside their seats (3 pages a
    # sequence, 4 sequences) whatever the full pools hold.
    assert 0 < max(s["pages_owned_window"] for s in log) <= 4 * 3
    assert max(s["pages_owned_full"] for s in log) > 4 * 3
    # Window 8 on pages of 4: a decode reads 2 or 3 window pages a
    # sequence, whatever its context.
    assert all(2 * s["decodes"] <= s["live_pages_window"]
               <= 3 * s["decodes"] for s in log if s["decodes"])
    assert max(s["live_pages_full"] for s in log) \
        > 2 * max(s["live_pages_window"] for s in log)
    if not traced:
        # The cell's rate and tail spread too widely on the chip for the
        # steadier cells' bounds: it reports a stream's mean time per
        # output token end to end, them and the median of runs per layer.
        assert set(got) == names(bench, "end_to_end", CELL) \
            == {"tpot_mean_ms", "setup_s"}
        assert got["tpot_mean_ms"]["value"] > 0
        return
    assert 0 < got["tpot_p50_ms"]["value"] < got["itl_p95_ms.long"]["value"]
    assert got["itl_p95_ms.long"]["value"] \
        >= got["engine_step_ms_p50.long"]["value"] > 0
    assert got["out_tokens_per_s.long"]["value"] > 0
    assert "paged_attn_roofline" not in got
    # No TPU plane in a CPU trace: the two device metrics are left out,
    # the counter is a number. 2 full and 6 window layers: a sequence of
    # 44-68 positions owns 11-17 pages of 4 in each full layer and 3 in
    # each window layer.
    assert not {"paged_attn_kinds_roofline", "paged_attn_busy_pct"} \
        & set(got)
    assert 35.0 < got["kv_resident_vs_flat_pct"]["value"] < 60.0
    assert got["compiles_in_window.long"]["value"] == 0
    assert got["preemptions.long"]["value"] == 0
    assert 25.0 <= got["moe_experts_touched_pct.long"]["value"] <= 100.0


# ---- the readers on a hand-made log and trace --------------------------------


class Engine:
    def __init__(self, steps):
        self.log = {"steps": steps, "oldest_start": 0.0}

    def step_log(self, since=0.0):
        return self.log


def record(start, end, full, window, prefills=None, owned=None):
    owned = owned or (full, window)
    out = {"start": start, "end": end, "decodes": 32, "phases": [],
           "live_pages": full, "live_pages_full": full,
           "live_pages_window": window, "window_pages_released": 0,
           "pages_owned_full": owned[0], "pages_owned_window": owned[1]}
    if prefills:
        out["prefills"] = prefills
    return out


def run_data(family, cfg, monkeypatch, steps, trace=None, peaks=None):
    monkeypatch.setattr(probe.ProbedEngine, "instances", [Engine(steps)])
    traced = []
    if trace is not None:
        traced = [types.SimpleNamespace(
            start=s["start"] - 1e-3, end=s["end"] + 1e-3,
            decodes=s["decodes"], prefills=len(s.get("prefills", ())),
            program=types.SimpleNamespace(fields=s))
            for s in steps[:len(tr.spans(trace, "pb.engine.step"))]]
    return RunData(cell={}, cfg=cfg, mix={"engine_options":
                                          {"page_size": 128}},
                   family=family, chips=1, peaks=peaks,
                   window=(0.5, 100.0), end_to_end={}, memory_peak_bytes=0,
                   trace=trace, traced_steps=traced)


def test_resident_share_is_of_the_step_that_owns_most(family, published,
                                                      monkeypatch):
    # 32 sequences of 180 and of 200 pages in each of 2 full layers, 9 in
    # each of 6 window layers; the fullest step also holds a prompt's
    # chunk: 31 decoding sequences, 296 pages of prompt in the full pools
    # and a chunk's burst of 16 in the window pools. What the decodes
    # read is not what is owned.
    steps = [record(1, 2, 32 * 180, 32 * 9), record(2, 3, 32 * 200, 32 * 9),
             record(3, 4, 31 * 200, 31 * 9, prefills=[{"tokens": 2048}],
                    owned=(31 * 200 + 296, 31 * 9 + 9 + 16))]
    data = run_data(family, published, monkeypatch, steps)
    assert read("kv_resident_vs_flat_pct", data) == pytest.approx(
        100 * (2 * 6496 + 6 * 304) / (8 * 6496))
    assert [read(n, data) for n in NEW[:2]] == [None, None]
    # Pages the window tables failed to give back show.
    steps[1]["pages_owned_window"] = 32 * 100
    steps[1]["pages_owned_full"] = 6400 + 1
    data = run_data(family, published, monkeypatch, steps)
    assert read("kv_resident_vs_flat_pct", data) == pytest.approx(
        100 * (2 * 6401 + 6 * 3200) / (8 * 6401))


def test_a_program_or_family_of_one_kind_gives_nothing(family, published,
                                                       monkeypatch):
    steps = [{"start": 1, "end": 2, "decodes": 8, "phases": [],
              "live_pages": 90}]
    data = run_data(family, published, monkeypatch, steps)
    assert [read(n, data) for n in NEW] == [None] * 3
    olmoe = run.load_family([run.HERE], {"family": "olmoe"})
    data = run_data(olmoe, {}, monkeypatch, [record(1, 2, 5760, 288)])
    assert [read(n, data) for n in NEW] == [None] * 3
    # A program without a step log at all (the parent's).
    monkeypatch.setattr(probe.ProbedEngine, "instances", [object()])
    assert [read(n, data) for n in NEW] == [None] * 3


def test_roofline_and_busy_share_from_a_trace(family, published,
                                              monkeypatch):
    """Three traced steps of 20 ms; in each the chip works for 10 ms, 5
    of them in eight paged kernels. The second also prefilled a chunk
    and is left out, events and pages; a kernel outside any span is not
    counted."""
    def step_events(t0):
        return [tr.Event("%fusion.1 = bf16[32,2304] fusion()", t0 + 0.002,
                         t0 + 0.007)] + [
            tr.Event(f"%_paged_pallas.{i} = bf16[32,1,32,512]{{3,2,1,0}} "
                     f"custom-call(s32[32,352] %a)",
                     t0 + 0.007 + i * 0.000625, t0 + 0.007 + (i + 1)
                     * 0.000625) for i in range(8)]
    trace = tr.Trace(
        device={0: {"XLA Ops": step_events(10.0) + step_events(10.02)
                    + step_events(10.04) + [
            tr.Event("%_paged_pallas.9 = bf16[32,1,32,512] custom-call()",
                     10.065, 10.066)]}},
        host={"python": [tr.Event("pb.engine.step", 10.0, 10.02),
                         tr.Event("pb.engine.step", 10.02, 10.04),
                         tr.Event("pb.engine.step", 10.04, 10.06)]})
    steps = [record(1.0, 1.018, 5760, 288),
             record(1.02, 1.038, 5760, 288, prefills=[{"tokens": 2048}]),
             record(1.04, 1.058, 5792, 288)]
    peaks = PEAKS["TPU v5 lite"]
    data = run_data(family, published, monkeypatch, steps, trace, peaks)
    assert paged_kinds.traced_seconds(data) == pytest.approx((0.010, 0.020))
    assert paged_kinds.traced_pages(data) == {"full": 11552, "window": 576}
    assert read("paged_attn_busy_pct", data) == pytest.approx(50.0)
    # A page of one layer is 2 x 128 x 4 x 128 x 2 B = 262,144 B of K and V.
    least = (2 * 11552 + 6 * 576) * 262144 / 819e9
    assert read("paged_attn_kinds_roofline", data) == pytest.approx(
        100 * least / 0.010)
    # Off a TPU there are no peaks and no roofline share.
    data = run_data(family, published, monkeypatch, steps, trace)
    assert read("paged_attn_kinds_roofline", data) is None
    assert read("paged_attn_busy_pct", data) == pytest.approx(50.0)


# ---- the configuration and the family's counts, by hand ----------------------


# The ``config`` of the model's row in the driver's catalog of
# architectures (Mellum2-12B-A2.5B-Instruct), copied: the catalog lies
# outside the checkout.
CATALOG_SOURCE = ("https://huggingface.co/JetBrains/"
                  "Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
CATALOG_CONFIG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def test_configuration_holds_the_published_numbers(published):
    assert published["source"] == CATALOG_SOURCE
    for key, value in CATALOG_CONFIG.items():
        if key not in published["reduced"]:
            assert published[key] == value, key
    assert published["reduced"] == ["num_hidden_layers"]
    assert published["num_hidden_layers"] == 8
    assert {"assumed", "deployment", "source"} <= set(published)


def test_counts_of_the_published_model(family, published):
    whole = dict(published, num_hidden_layers=28)
    # Per layer: q 2304 x 4096, k and v 2304 x 512 each, o 4096 x 2304,
    # two norms, the router 2304 x 64, 64 experts of 3 x 2304 x 896.
    attention = 2304 * (32 + 2 * 4) * 128 + 32 * 128 * 2304
    layer = attention + 2 * 2304 + 2304 * 64 + 64 * 3 * 2304 * 896
    outside = 2 * 98304 * 2304 + 2304
    assert attention == 21233664 and layer == 417747456
    assert family.param_count(whole) == outside + 28 * layer == 12149915904
    active = layer - 56 * 3 * 2304 * 896
    assert family.active_param_count(whole) == outside + 28 * active \
        == 2439053568
    assert family.param_count(published) == outside + 8 * layer
    assert family.moe_shape(published) == (8, 64, 8, 2304, 896, 2)
    assert family.kv_shape(published) == (8, 4, 128, 2)
    assert family.layers_by_kind(published) == (2, 6)
    assert family.layers_by_kind(whole) == (7, 21)
    assert family.vocab_rows_held(published) == 98304
    pcfg = family.program_config(published)
    assert (pcfg.head_dim, pcfg.n_embd // pcfg.n_head) == (128, 72)
    assert pcfg.layer_types == ("sliding_attention",) * 3 \
        + ("full_attention",) + ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert pcfg.serving.layer_windows == (1024, 1024, 1024, None) * 2


@pytest.mark.parametrize("full,window,bytes_", [
    (1, 0, 2 * 262144.0), (0, 1, 6 * 262144.0),
    # 32 sequences of 23,400 positions: 183 pages each in the full
    # layers, 9 in the window layers: 3.52 GB a step.
    (32 * 183, 32 * 9, (2 * 32 * 183 + 6 * 32 * 9) * 262144.0)])
def test_paged_bytes_by_kind(family, published, full, window, bytes_):
    assert family.paged_attn_bytes_by_kind(published, 128, full,
                                           window) == bytes_


def test_expert_layer_flops_and_bytes(family, published):
    # 3 x 2304 x 896 = 6,193,152 weights an expert.
    assert family.expert_ffn_flops(published, 256) == 256 * 12386304.0
    assert family.expert_ffn_bytes(published, 504) == 504 * 12386304.0
