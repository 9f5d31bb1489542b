"""GLM-5: latent attention that reads only the cached positions a learned
indexer chooses, the index keys in a pool of their own beside the latent
pool, under one block table. All at a tiny size on the CPU
(``GlmDsaConfig.tiny``: a dense layer and two routed ones, 4 heads, a
latent of 128 and a roped key of 8, an indexer of 4 heads of 16 that keeps
16 positions, so a context of 60 is nearly four times what a query reads),
page size 8.

The model is held to the benchmark's plain float32 reference
(``perfbench/families/glm_moe_dsa.py``, written from the layer equations in
the expanded form with the choice as a mask, and not from the program): in
float32 they choose the same rows and the same experts and agree to
rounding, 1e-4 of the largest reference logit. The reference itself is
held to a literal transcription of the equations, one position, head and
expert at a time.
"""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import InferenceEngine
from raytpu.inference.sampling import SamplingParams
from raytpu.models import mixtral
from raytpu.models.mixtral import (GlmDsa, GlmDsaConfig, MoEFFN, init_params,
                                   mixtral_loss_fn)
from raytpu.models.mla import LatentAttention, SparseLatentAttention
from raytpu.ops import dsa_attention as dsa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = dict(dtype=jnp.float32, attn_impl="reference",
           paged_attn="reference", remat=False)
TINY = dataclasses.replace(GlmDsaConfig.tiny(), **F32)
ENGINE = dict(page_size=8, max_num_seqs=4, max_model_len=128)
IMPLS = ["reference", "interpret"]
TOPK = TINY.index_topk


@pytest.fixture(scope="module")
def family():
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families",
                       "glm_moe_dsa")


@pytest.fixture(scope="module")
def params():
    return init_params(GlmDsa(TINY), TINY, seed=1)


@pytest.fixture
def small_blocks(monkeypatch):
    """Eight queries of a sequence at a time, so that a chunk of 16 and a
    prompt of 43 go through the loop over query blocks."""
    monkeypatch.setattr(dsa, "QUERY_BLOCK", 8)


def file_config(c: GlmDsaConfig, held=None):
    """The configuration file the family's reference reads, for ``c``."""
    first, count = held or c.experts_held or (0, c.n_expert)
    return {
        "family": "glm_moe_dsa", "vocab_size": c.vocab_size,
        "max_position_embeddings": c.block_size,
        "num_hidden_layers": c.n_layer, "num_attention_heads": c.n_head,
        "num_key_value_heads": c.n_kv_head, "hidden_size": c.n_embd,
        "head_dim": c.head_dim, "intermediate_size": c.dense_inter,
        "moe_intermediate_size": c.n_inter, "n_routed_experts": count,
        "published_n_routed_experts": c.n_expert,
        "experts_held": [first, count], "n_shared_experts": c.n_shared,
        "first_k_dense_replace": c.first_dense,
        "num_experts_per_tok": c.n_expert_per_tok,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scale, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "moe_layer_freq": 1, "q_lora_rank": c.q_lora_rank,
        "kv_lora_rank": c.kv_lora_rank, "qk_nope_head_dim": c.qk_nope_dim,
        "qk_rope_head_dim": c.qk_rope_dim,
        "qk_head_dim": c.qk_nope_dim + c.qk_rope_dim,
        "v_head_dim": c.v_head_dim, "rope_interleave": c.rope_interleave,
        "rope_parameters": {"rope_theta": c.rope_theta,
                            "rope_type": "default"},
        "index_topk": c.index_topk, "index_n_heads": c.index_n_head,
        "index_head_dim": c.index_head_dim,
        "indexer_rope_interleave": c.index_rope_interleave,
        "rms_norm_eps": c.norm_eps, "attention_bias": False,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "assumed": {"e_score_correction_bias_std": c.choice_bias,
                    "index_k_norm_eps": c.index_norm_eps},
        "compute_dtype": "float32", "param_dtype": "float32"}


def rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float32)
                        - np.asarray(want, np.float32)).max()
                 / np.abs(np.asarray(want)).max())


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, TINY.vocab_size, size=n)]
            for n in lengths]


# ---- the config and the parameter tree ------------------------------------------


class TestConfig:
    def test_published_values(self):
        c = GlmDsaConfig()
        assert (c.n_layer, c.n_embd, c.n_head, c.vocab_size) \
            == (78, 6144, 64, 154880)
        assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim,
                c.v_head_dim) == (2048, 512, 192, 64, 256)
        assert (c.index_topk, c.index_n_head, c.index_head_dim) \
            == (2048, 32, 128)
        assert (c.n_expert, c.n_expert_per_tok, c.n_inter, c.n_shared,
                c.first_dense, c.dense_inter) == (256, 8, 2048, 1, 3, 12288)
        assert (c.scoring, c.routed_scale, c.norm_topk_prob,
                c.rope_theta, c.norm_eps) \
            == ("sigmoid", 2.5, True, 1e6, 1e-5)
        assert c.rope_interleave and c.index_rope_interleave
        assert c.serving.kv_row == 640 and c.serving.indexer == (128, 2048)

    def test_the_program_config_of_the_cells_file(self, family):
        with open(os.path.join(ROOT, "perfbench", "configs",
                               "glm-5.json")) as f:
            cfg = json.load(f)
        cut = dict(n_layer=5, first_dense=1, vocab_size=19360,
                   experts_held=(0, 8), choice_bias=0.01,
                   dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                   scan_layers=False)
        assert family.program_config(cfg) == dataclasses.replace(
            GlmDsaConfig(), **cut)

    def test_a_layer_has_an_indexer_beside_the_latent_attention(self, params):
        attn = params["layers_1"]["attn"]
        shapes = {k: v["kernel"].shape for k, v in attn.items()
                  if "kernel" in v}
        assert shapes["index_q_proj"] == (48, 4 * 16)
        assert shapes["index_k_proj"] == (64, 16)
        assert shapes["index_w_proj"] == (64, 4)
        assert set(attn["index_k_norm"]) == {"scale", "bias"}
        assert "mlp" in params["layers_0"] and "moe" in params["layers_1"]

    def test_other_latent_configs_have_no_indexer(self):
        for name in ("JoyAIConfig", "LongcatFlashConfig"):
            c = getattr(mixtral, name).tiny()
            assert c.serving.indexer is None
            assert type(c.attention()) is LatentAttention

    def test_an_index_key_holds_the_roped_values(self):
        with pytest.raises(ValueError, match="roped"):
            dataclasses.replace(TINY, index_head_dim=4)


# ---- the reference, against the equations one position at a time ------------------


def literal_logits(c: GlmDsaConfig, params, tokens):
    """The layer of ISSUE 55 in float64 numpy: one position, head and
    expert at a time, the rope by adjacent pairs, the choice by sorting."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    eps = c.norm_eps
    h, nope, rope, vd = c.n_head, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
    hi, di = c.index_n_head, c.index_head_dim

    def norm(x, scale):
        return x / np.sqrt((x * x).mean() + eps) * scale

    def rope_pairs(x, pos):
        out = np.empty_like(x)
        for j in range(len(x) // 2):
            ang = pos * c.rope_theta ** (-2.0 * j / len(x))
            a, b = x[2 * j], x[2 * j + 1]
            out[2 * j] = a * np.cos(ang) - b * np.sin(ang)
            out[2 * j + 1] = b * np.cos(ang) + a * np.sin(ang)
        return out

    def index_rope(x, pos):
        return np.concatenate([rope_pairs(x[:rope], pos), x[rope:]])

    def swiglu(w, y):
        g = y @ w["gate_proj"]["kernel"]
        return (g / (1 + np.exp(-g)) * (y @ w["up_proj"]["kernel"])) \
            @ w["down_proj"]["kernel"]

    def attention(a, ys):
        """``ys`` [T, E] normed -> the attention's output [T, E]."""
        qs, ks, vs, qi, ki, wi = [], [], [], [], [], []
        for pos, y in enumerate(ys):
            c_q = norm(y @ a["q_a_proj"]["kernel"], a["q_a_norm"]["scale"])
            q = (c_q @ a["q_b_proj"]["kernel"]).reshape(h, nope + rope)
            kva = y @ a["kv_a_proj"]["kernel"]
            c_kv = norm(kva[:c.kv_lora_rank], a["kv_a_norm"]["scale"])
            k_pe = rope_pairs(kva[c.kv_lora_rank:], pos)
            kv = (c_kv @ a["kv_b_proj"]["kernel"]).reshape(h, nope + vd)
            qs.append([np.concatenate([q[n, :nope],
                                       rope_pairs(q[n, nope:], pos)])
                       for n in range(h)])
            ks.append([np.concatenate([kv[n, :nope], k_pe])
                       for n in range(h)])
            vs.append(kv[:, nope:])
            q_i = (c_q @ a["index_q_proj"]["kernel"]).reshape(hi, di)
            qi.append([index_rope(q_i[n], pos) for n in range(hi)])
            k = y @ a["index_k_proj"]["kernel"]
            k = (k - k.mean()) / np.sqrt(k.var() + c.index_norm_eps) \
                * a["index_k_norm"]["scale"] + a["index_k_norm"]["bias"]
            ki.append(index_rope(k, pos))
            wi.append((y @ a["index_w_proj"]["kernel"])
                      * hi ** -0.5 * di ** -0.5)
        out = np.zeros_like(ys)
        for pos in range(len(ys)):
            index = [sum(wi[pos][n] * max(qi[pos][n] @ ki[j], 0.0)
                         for n in range(hi)) for j in range(pos + 1)]
            # The largest first, equal scores by rising position.
            kept = sorted(range(pos + 1), key=lambda j: (-index[j], j))[
                :min(c.index_topk, pos + 1)]
            heads = []
            for n in range(h):
                s = np.array([qs[pos][n] @ ks[j][n] for j in kept])
                w = np.exp((s - s.max()) / np.sqrt(nope + rope))
                w /= w.sum()
                heads.append(sum(w[i] * vs[j][n]
                                 for i, j in enumerate(kept)))
            out[pos] = np.concatenate(heads) @ a["o_proj"]["kernel"]
        return out

    def moe(m, y):
        s = 1 / (1 + np.exp(-(y @ m["router"]["kernel"])))
        chosen = np.argsort(-(s + m["bias"]))[:c.n_expert_per_tok]
        total = sum(s[e] for e in chosen)
        acc = swiglu(m["shared"], y)
        for e in chosen:
            g = y @ m["wg"][e]
            acc = acc + c.routed_scale * s[e] / total * (
                (g / (1 + np.exp(-g)) * (y @ m["wi"][e])) @ m["wo"][e])
        return acc

    x = p["embed_tokens"]["embedding"][np.asarray(tokens)]
    for l in range(c.n_layer):
        lp = p[f"layers_{l}"]
        x = x + attention(lp["attn"], np.stack(
            [norm(r, lp["input_norm"]["scale"]) for r in x]))
        ys = [norm(r, lp["post_attn_norm"]["scale"]) for r in x]
        x = x + np.stack([swiglu(lp["mlp"], y) if l < c.first_dense
                          else moe(lp["moe"], y) for y in ys])
    x = np.stack([norm(row, p["final_norm"]["scale"]) for row in x])
    return x @ p["lm_head"]["kernel"]


def test_reference_is_the_equations_position_by_position(family, params):
    tokens = prompts(40)[0]  # 2.5 times what a query keeps
    want = literal_logits(TINY, params, tokens)
    got = np.asarray(family.logits(file_config(TINY), params,
                                   jnp.asarray([tokens])))[0]
    assert rel_err(got, want) < 2e-5


def test_reference_rows_are_the_whole_logits_rows(family, params):
    tokens = jnp.asarray(prompts(40))
    whole = family.logits(file_config(TINY), params, tokens)
    some = family.logits(file_config(TINY), params, tokens, rows=[3, 39])
    np.testing.assert_allclose(some, whole[:, [3, 39]], rtol=1e-6)


def test_reference_in_blocks_is_the_reference(family, params, monkeypatch):
    tokens = jnp.asarray(prompts(40))
    whole = family.logits(file_config(TINY), params, tokens)
    monkeypatch.setattr(family, "SCORE_ENTRIES", 4 * 40 * 8)  # 8 rows a block
    monkeypatch.setattr(family, "SWIGLU_BLOCK", 32)
    np.testing.assert_allclose(
        family.logits(file_config(TINY), params, tokens), whole, atol=1e-5)


def test_reference_choice_keeps_the_best_and_ties_to_the_lower(family):
    cfg = {"index_topk": 3}
    scores = jnp.asarray([[[5.0, 1.0, 5.0, 5.0, 5.0, 9.0],
                           [0.0, 0.0, 0.0, 0.0, 0.0, 0.0]]])
    keep = np.asarray(family.chosen(cfg, scores, jnp.asarray([4, 1])))
    assert keep[0, 0].tolist() == [True, False, True, True, False, False]
    assert keep[0, 1].tolist() == [True, True, False, False, False, False]


def test_program_forward_is_the_references(family, params, small_blocks):
    tokens = jnp.asarray(prompts(24, seed=2) + prompts(24, seed=3))
    got = GlmDsa(TINY).apply({"params": params}, tokens)
    want = family.logits(file_config(TINY), params, tokens)
    assert rel_err(got, want) < 1e-4


def test_loss_and_gradients_against_the_reference(family, params):
    """The training forward selects as serving does and passes no
    gradient through the choice: the reference's mask does neither."""
    tokens = jnp.asarray(prompts(24, seed=4))
    c = dataclasses.replace(TINY, router_aux_coef=0.0)
    ours, g_ours = jax.value_and_grad(
        lambda p: mixtral_loss_fn(GlmDsa(c), p, tokens))(params)
    ref, g_ref = jax.value_and_grad(
        lambda p: family.loss(file_config(TINY), p, tokens))(params)
    assert abs(float(ours) - float(ref)) < 1e-4
    flat = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), g_ours, g_ref))
    assert max(flat) < 1e-4


# ---- the three ops ----------------------------------------------------------------


def pools_of(rng, b, ctx, page, width, d, n_pages=None):
    """Two pools of seeded noise and, for ``b`` sequences of ``ctx``
    positions, block tables of distinct pages (scratch in dead columns)."""
    p = -(-ctx // page)
    n_pages = n_pages or b * p + 3
    pages = jnp.asarray(rng.standard_normal((n_pages, page, width)),
                        jnp.float32)
    index_pages = jnp.asarray(rng.standard_normal((n_pages, page, d)),
                              jnp.float32)
    tables = np.zeros((b, p + 2), np.int32)
    tables[:, :p] = 1 + rng.permutation(n_pages - 1)[:b * p].reshape(b, p)
    return pages, index_pages, jnp.asarray(tables)


@pytest.mark.parametrize("t", [1, 8, 16])
def test_index_kernel_is_the_reference(t):
    rng = np.random.default_rng(t)
    b, ctx, page, d, heads = 3, 70, 8, 16, 4
    _, index_pages, tables = pools_of(rng, b, ctx, page, 128, d)
    q = jnp.asarray(rng.standard_normal((b, t, heads, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, t, heads)), jnp.float32)
    starts = np.asarray([ctx - t, 20, 0])
    positions = jnp.asarray(starts[:, None] + np.arange(t))
    want = dsa.index_scores(q, w, index_pages, tables, positions,
                            force="reference")
    got = dsa.index_scores(q, w, index_pages, tables, positions,
                           force="interpret")
    assert got.shape == want.shape == (b, t, tables.shape[1] * page)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # A future position and a dead column read -1e30, whatever they hold.
    slots = np.arange(want.shape[-1])
    assert (np.asarray(want)[slots[None, None] > np.asarray(
        positions)[..., None]] == -1e30).all()


def test_the_choice_is_exact_counts_and_names_no_future_position():
    rng = np.random.default_rng(0)
    scores = rng.standard_normal((2, 5, 40)).astype(np.float32)
    positions = np.asarray([[3, 4, 5, 6, 7], [30, 31, 32, 33, 34]])
    seen = np.arange(40)[None, None] <= positions[..., None]
    scores = np.where(seen, scores, -1e30)
    chosen, count = dsa.select_rows(jnp.asarray(scores),
                                    jnp.asarray(positions), 6)
    chosen, count = np.asarray(chosen), np.asarray(count)
    assert count.tolist() == [[4, 5, 6, 6, 6], [6] * 5]
    for b in range(2):
        for t in range(5):
            n, p = count[b, t], positions[b, t]
            kept = chosen[b, t, :n]  # by rising position
            want = np.argsort(-scores[b, t, :p + 1], kind="stable")[:n]
            assert kept.tolist() == sorted(want.tolist())
            assert (chosen[b, t, n:] == 0).all() and (kept <= p).all()


def test_equal_scores_go_to_the_lower_position():
    scores = jnp.asarray([[[2.0, 7.0, 2.0, 2.0, 2.0, -1e30]]])
    chosen, count = dsa.select_rows(scores, jnp.asarray([[4]]), 3)
    assert np.asarray(chosen)[0, 0].tolist() == [0, 1, 2]
    assert int(count[0, 0]) == 3
    # Negative scores, zeros of both signs, a table wider than a group.
    rng = np.random.default_rng(1)
    wide = np.round(rng.standard_normal((1, 3, 300)), 1).astype(np.float32)
    wide[0, :, 7] = -0.0
    positions = np.asarray([[250, 280, 299]])
    wide = np.where(np.arange(300) <= positions[..., None], wide, -1e30)
    chosen, count = dsa.select_rows(jnp.asarray(wide),
                                    jnp.asarray(positions), 64)
    for t in range(3):
        want = np.argsort(-wide[0, t], kind="stable")[:64]
        assert np.asarray(chosen)[0, t].tolist() == sorted(want.tolist())


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("t", [1, 16])
def test_sparse_attention_is_dense_attention_over_the_chosen(impl, t,
                                                             small_blocks):
    """The three ops in order against a dense softmax masked to the
    reference's choice, decode (T = 1) and a chunk in query blocks."""
    from raytpu.ops.mla_attention import latent_rows

    rng = np.random.default_rng(t)
    b, ctx, page, rank, rope, d, heads, topk = 2, 60, 8, 128, 8, 16, 4, 16
    pages, index_pages, tables = pools_of(rng, b, ctx, page, 256, d)
    q_lat = jnp.asarray(rng.standard_normal((b, t, 4, rank)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((b, t, 4, rope)), jnp.float32)
    q_idx = jnp.asarray(rng.standard_normal((b, t, heads, d)), jnp.float32)
    w_idx = jnp.asarray(rng.standard_normal((b, t, heads)), jnp.float32)
    positions = jnp.asarray(np.asarray([ctx - t, 9])[:, None]
                            + np.arange(t))
    got = dsa.dsa_paged_attention(
        q_lat, q_pe, q_idx, w_idx, pages, index_pages, tables, positions,
        index_topk=topk, sm_scale=0.2, force=impl)
    scores = np.asarray(dsa.index_scores_reference(
        q_idx, w_idx, index_pages, tables, positions))
    rows = np.asarray(pages)[np.asarray(tables)].reshape(b, -1, 256)
    q = np.asarray(latent_rows(q_lat, q_pe))
    for i in range(b):
        for j in range(t):
            p = int(positions[i, j])
            kept = np.argsort(-scores[i, j, :p + 1],
                              kind="stable")[:min(topk, p + 1)]
            s = np.einsum("hw,kw->hk", q[i, j], rows[i, kept]) * 0.2
            a = np.exp(s - s.max(-1, keepdims=True))
            want = (a / a.sum(-1, keepdims=True)) @ rows[i, kept, :rank]
            np.testing.assert_allclose(got[i, j], want, rtol=2e-4,
                                       atol=2e-5)


def test_a_context_within_the_top_k_is_dense_latent_attention(params):
    """With every cached position chosen the module is
    ``LatentAttention`` over the same parameters: whole (flash, expanded)
    and through the pools (absorbed over all rows, in the choice's order)."""
    from raytpu.ops.mla_attention import latent_row_width

    a = params["layers_1"]["attn"]
    base = {k: v for k, v in a.items() if not k.startswith("index_")}
    x = jnp.asarray(np.random.default_rng(5).standard_normal((1, 16, 64)),
                    jnp.float32)
    sparse, dense = SparseLatentAttention(TINY), LatentAttention(TINY)
    got, rows, keys = sparse.apply({"params": a}, x, method="prefill")
    want, want_rows = dense.apply({"params": base}, x, method="prefill")
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(rows, want_rows)
    assert keys.shape == (1, 16, TINY.index_head_dim)
    width = latent_row_width(TINY.kv_lora_rank, TINY.qk_rope_dim)
    pages = jnp.zeros((4, 8, width), jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    positions = jnp.arange(16)[None]
    dests = 8 + positions
    y, _, _ = sparse.apply(
        {"params": a}, x[0], pages, jnp.zeros((4, 8, 16), jnp.float32),
        dests, tables, positions, method="step")
    y_dense, _ = dense.apply({"params": base}, x[0], pages, dests, tables,
                             positions, method="step")
    np.testing.assert_allclose(y, y_dense, atol=1e-5)
    np.testing.assert_allclose(y, want[0], atol=1e-5)


# ---- the model, served, against the reference -----------------------------------


def served_logits(cfg, params, prompt, new, **engine):
    """Every logit row the engine's programs produce for one request:
    the prompt's last row, then one a decoded position."""
    eng = InferenceEngine(cfg, params, **dict(ENGINE, **engine))
    rows = []

    def keep(fn, pick):
        def kept(*a):
            res = fn(*a)
            rows.extend(pick(np.asarray(res[0])))
            return res
        return kept

    eng._prefill_fn = keep(eng._prefill_fn, lambda lg: [lg[len(prompt) - 1]])
    chunk = eng._chunk_fn

    def chunk_kept(*a):
        res = chunk(*a)
        if eng.scheduler.running[0].cached_len + a[3].shape[1] \
                >= len(prompt) and not eng.scheduler.running[0].generated:
            last = (len(prompt) - 1) % eng.prefill_chunk
            rows.append(np.asarray(res[0])[0, last])
        return res

    eng._chunk_fn = chunk_kept
    eng._decode_fn = keep(eng._decode_fn, lambda lg: [lg[0]])
    out = eng.generate([prompt], SamplingParams(max_new_tokens=new))[0]
    return eng, out, np.stack(rows[:new])


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk", [None, 16])
@pytest.mark.parametrize("held", [None, (8, 8)])
def test_served_logits_are_the_references(family, params, impl, chunk, held,
                                          small_blocks):
    """A prompt of 43 tokens (whole: the absorbed form over its own rows
    as a pool; or in chunks of 16 through both pools) and 14 decoded
    positions, contexts of 2.7 to 3.5 times ``index_topk``, against the
    reference's one expanded, masked forward pass; with every routed
    expert held, and with a share of them."""
    cfg = dataclasses.replace(TINY, attn_impl=impl, paged_attn=impl,
                              experts_held=held)
    if held:
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a[held[0]:held[0] + held[1]]
            if path[-1].key in ("wg", "wi", "wo") else a, params)
    prompt = prompts(43)[0]
    eng, out, got = served_logits(cfg, params, prompt, 14,
                                  prefill_chunk=chunk)
    want = np.asarray(family.logits(
        file_config(TINY, held), params, jnp.asarray([prompt + out[:-1]])))[0]
    assert rel_err(got, want[len(prompt) - 1:]) < 1e-4
    stats = eng.stats()
    assert bool(stats["chunk_prefill_compiles"]) == (chunk is not None)
    log = eng.step_log()["steps"]
    # A layer's indexers scored every cached position of every query, and
    # its attention read 16 of them at most.
    queries = np.arange(43 + 13)
    assert sum(s["dsa_rows_scored"] for s in log) == int((queries + 1).sum())
    assert sum(s["dsa_rows_selected"] for s in log) \
        == int(np.minimum(queries + 1, TOPK).sum())


def wrong(control):
    """``TINY`` wrong in one way: the cell's controls (``chip_glm5.py``)."""
    import chip_glm5

    return chip_glm5.wrong_config(TINY, control)


def test_controls_fail_where_the_program_passes(family, params):
    """What the cell's check must catch, at the tiny size and in float32:
    each departure from the equations moves the logits far outside the
    1e-4 the right program stands inside."""
    import chip_glm5

    prompt = prompts(60)[0]
    tokens = jnp.asarray([prompt])
    want = np.asarray(family.logits(file_config(TINY), params, tokens))[0]
    model = lambda c: np.asarray(GlmDsa(c).apply(  # noqa: E731
        {"params": params}, tokens))[0]
    assert rel_err(model(TINY), want) < 1e-4
    assert len(chip_glm5.PROGRAM_CONTROLS) == 7
    for control in chip_glm5.PROGRAM_CONTROLS:
        assert rel_err(model(wrong(control)), want) > 1e-3, control


@pytest.mark.parametrize("control", ["newest", "no_relu", "no_k_norm"])
def test_a_control_is_wrong_in_the_served_walk_too(family, params, control):
    prompt = prompts(40)[0]
    _, out, got = served_logits(wrong(control), params, prompt, 4,
                                prefill_chunk=16)
    want = np.asarray(family.logits(
        file_config(TINY), params, jnp.asarray([prompt + out[:-1]])))[0]
    assert rel_err(got, want[len(prompt) - 1:]) > 1e-3


def test_batched_decode_is_solo_decode(params):
    """The chosen set is the same whatever shares the batch."""
    eng = InferenceEngine(TINY, params, **ENGINE)
    batch = prompts(5, 31, 57)
    together = eng.generate(batch, SamplingParams(max_new_tokens=12))
    for prompt, out in zip(batch, together):
        solo = InferenceEngine(TINY, params, **ENGINE).generate(
            [prompt], SamplingParams(max_new_tokens=12))[0]
        assert solo == out


def test_engine_sizes_and_reports_two_pools_a_layer(params):
    eng = InferenceEngine(TINY, params, num_pages=20, **ENGINE)
    assert len(eng.cache.k) == len(eng.cache.v) == 3
    assert eng.cache.k[0].shape == (20, 8, 256)
    assert eng.cache.v[0].shape == (20, 8, 16)
    assert eng.cache.token_bytes == 3 * (256 + 16) * 4
    stats = eng.stats()
    assert stats["kv_pool_bytes"] == 3 * 20 * 8 * (256 + 16) * 4
    assert stats["kv_pool_bytes_by_kind"] == {
        "full": stats["kv_pool_bytes"], "window": 0}
    eng.generate(prompts(9), SamplingParams(max_new_tokens=3))
    assert eng.cache.v[0].shape == (20, 8, 16)
    log = eng.step_log()["steps"]
    assert all(s["kv_bytes_per_token"] == 3 * (256 + 16) * 4 for s in log)
    # The prompt's 9 queries, then one a decode step.
    assert [s["dsa_rows_scored"] for s in log if s["dsa_rows_scored"]] \
        == [45, 10, 11]
    assert [s["dsa_rows_selected"] for s in log if s["dsa_rows_scored"]] \
        == [45, 10, 11]


def test_a_model_without_an_indexer_reports_no_rows():
    cfg = dataclasses.replace(mixtral.JoyAIConfig.tiny(), **F32)
    eng = InferenceEngine(cfg, init_params(mixtral.JoyAI(cfg), cfg),
                          **ENGINE)
    eng.generate(prompts(9), SamplingParams(max_new_tokens=2))
    assert eng.cache.v == [] and eng.cache.token_bytes == 3 * 256 * 4
    assert all("dsa_rows_scored" not in s
               for s in eng.step_log()["steps"])


def test_the_counters_are_declared_and_counted(params):
    from raytpu.inference import engine as engine_mod
    from raytpu.util.metrics import DECLARED_METRICS

    names = ("raytpu_infer_dsa_rows_scored_total",
             "raytpu_infer_dsa_rows_selected_total")
    assert all(n in DECLARED_METRICS for n in names)
    seen = []
    scored, selected = (engine_mod._dsa_scored_total,
                        engine_mod._dsa_selected_total)
    before = scored.inc, selected.inc
    scored.inc = lambda n=1, **kw: seen.append(("scored", n))
    selected.inc = lambda n=1, **kw: seen.append(("selected", n))
    try:
        eng = InferenceEngine(TINY, params, **ENGINE)
        eng.generate(prompts(30), SamplingParams(max_new_tokens=3))
    finally:
        scored.inc, selected.inc = before
    queries = np.arange(32)
    assert sum(n for k, n in seen if k == "scored") \
        == int((queries + 1).sum())
    assert sum(n for k, n in seen if k == "selected") \
        == int(np.minimum(queries + 1, TOPK).sum())


def test_the_programs_carry_the_scopes(params):
    eng = InferenceEngine(TINY, params, **ENGINE)
    b = 4
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    text = eng._decode_fn.lower(
        eng._params, eng.cache.k, eng.cache.v, i32(b), i32(b), i32(b),
        i32(b, 2), i32(b)).as_text(debug_info=True).replace('"', "")
    for scope in ("attn.dsa.index", "attn.dsa.select", "attn.dsa.attend"):
        # Inside the layer's attention scope, under the module's methods.
        assert f"attn.mla/SparseLatentAttention.step/" \
            f"SparseLatentAttention._chosen/{scope}" in text, scope
    assert "moe.router" in text and "moe.shared" in text


def test_prefix_cache_shares_both_pools_pages(params):
    """A page is a page of both pools: a second prompt with the first's
    24-token prefix starts from its three pages, latent rows and index
    keys, through the chunk path, and decodes the same tokens as alone."""
    a, b = prompts(40, 19)
    shared = a[:24] + b
    eng = InferenceEngine(TINY, params, **ENGINE)
    assert eng.prefix_cache is not None
    before = eng.stats()["prefix_cache"]  # the counters are the process's
    eng.generate([a], SamplingParams(max_new_tokens=2))
    got = eng.generate([shared], SamplingParams(max_new_tokens=8))[0]
    hits = eng.stats()["prefix_cache"]
    assert hits["hits"] - before["hits"] == 1
    assert hits["hit_tokens"] - before["hit_tokens"] == 24
    alone = InferenceEngine(TINY, params, enable_prefix_cache=False,
                            **ENGINE).generate(
        [shared], SamplingParams(max_new_tokens=8))[0]
    assert got == alone


def test_a_preempted_sequence_recomputes_both_pools(params):
    """Too few pages for three long sequences at once: one is preempted,
    loses its pages of both pools, is prefilled again and ends with the
    tokens it has alone."""
    batch = prompts(50, 52, 54)
    eng = InferenceEngine(TINY, params, num_pages=24,
                          enable_prefix_cache=False, **ENGINE)
    together = eng.generate(batch, SamplingParams(max_new_tokens=20))
    assert eng.stats()["num_preemptions"] > 0
    for prompt, out in zip(batch, together):
        solo = InferenceEngine(TINY, params, **ENGINE).generate(
            [prompt], SamplingParams(max_new_tokens=20))[0]
        assert solo == out


class TestRouting:
    @pytest.fixture(scope="class")
    def x(self):
        return jnp.asarray(
            np.random.default_rng(9).standard_normal((25, 64)), jnp.float32)

    def test_the_shares_add_up_to_the_whole_layer(self, family, params, x):
        """16 chips hold one routed expert each (the cell: 32 hold 8).
        Their routed parts, with the shared expert counted once, are the
        uncut reference's layer; a pair whose expert is elsewhere costs no
        row."""
        moe = params["layers_1"]["moe"]
        cfg = file_config(TINY)
        with jax.default_matmul_precision("highest"):
            whole = family._experts(cfg, moe, x)
            shared = family._swiglu(moe["shared"], x)
            w = family.router_weights(cfg, moe, x)
        routed, rows = jnp.zeros_like(x), 0
        for chip in range(TINY.n_expert):
            held = (chip, 1)
            c = dataclasses.replace(TINY, experts_held=held)
            share = dict(moe, **{k: moe[k][chip:chip + 1]
                                 for k in ("wg", "wi", "wo")})
            part, counts = MoEFFN(c).apply({"params": share}, x)
            routed, rows = routed + (part - shared), rows + int(counts[0])
            with jax.default_matmul_precision("highest"):
                ref = family._experts(file_config(TINY, held), share, x)
            np.testing.assert_allclose(part, ref, atol=2e-5)
        assert rows == int((np.asarray(w) > 0).sum()) \
            == 25 * TINY.n_expert_per_tok
        np.testing.assert_allclose(shared + routed, whole, atol=1e-4)


# ---- what stays refused ----------------------------------------------------------


def test_the_model_is_served_on_one_device(params):
    with pytest.raises(ValueError, match="one device"):
        InferenceEngine(TINY, params, tp=2, **ENGINE)


def test_index_keys_stand_beside_a_latent_pool():
    from raytpu.inference.kv_cache import PagedKVCache

    with pytest.raises(ValueError, match="latent"):
        PagedKVCache(2, 8, 8, 4, 16, index_row=16)


def test_the_model_takes_no_disaggregated_role():
    from raytpu.inference.serving import LLMDeployment

    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="latent"):
            LLMDeployment._target(model="glm_moe_dsa", role=role)
    with pytest.raises(ValueError, match="'glm_moe_dsa'"):
        LLMDeployment._target(model="glm5")


def test_the_deployment_serves_the_family():
    from raytpu.inference.serving import LLMDeployment

    dep = LLMDeployment._target(model="glm_moe_dsa", engine_options=dict(
        page_size=8, max_num_seqs=2, max_model_len=64))
    try:
        prompt = prompts(30)[0]
        out = list(dep.generate(prompt, max_new_tokens=4))
        assert len(out) == 4 and all(0 <= t < 512 for t in out)
        stats = dep.stats()
        assert stats["kv_pool_bytes_by_kind"]["window"] == 0
        log = dep.step_log()
        assert sum(s["dsa_rows_selected"] for s in log) \
            == int(np.minimum(np.arange(33) + 1, TOPK).sum())
    finally:
        dep.shutdown()


# ---- the others' programs are what they were -------------------------------------


# Logits of the parent commit's tree (PR 54, ef29614) for the two latent
# configurations without an indexer, at their ``tiny()`` size in float32
# with seed 3, as ``tests/test_longcat_flash.py`` records the routed ones':
# the whole forward over two rows of 24 tokens, and the engine's
# whole-prompt and decode programs over 19 + 5; SHA-256 of the float32
# bytes, and the greedy tokens. ``CANARY``: that file's.
PARENT = {
    "JoyAIConfig": ("ba01aa3971f08e26", "a2308282aba17c94",
                    [234, 323, 146, 288, 57]),
    "LongcatFlashConfig": ("76e2eecf427838f8", "2abb1ce4f715243f",
                           [216, 378, 281, 358, 122])}


def digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(a, np.float32)).tobytes()).hexdigest()[:16]


def recorded(name):
    """``(forward digest, served digest, greedy tokens)`` of ``name`` at
    its ``tiny()`` size on this tree."""
    c = dataclasses.replace(getattr(mixtral, name).tiny(), **F32)
    model = mixtral.Mixtral(c)
    params = init_params(model, c, seed=3, batch=1)
    toks = np.random.default_rng(11).integers(1, c.vocab_size, (2, 24))
    whole = model.apply({"params": params}, jnp.asarray(toks, jnp.int32))
    eng = InferenceEngine(c, params, page_size=8, max_num_seqs=2,
                          max_model_len=64)
    rows = []

    def keep(fn):
        def kept(*a):
            res = fn(*a)
            rows.append(np.asarray(res[0], np.float32))
            return res
        return kept

    eng._prefill_fn, eng._decode_fn = (keep(eng._prefill_fn),
                                       keep(eng._decode_fn))
    got = eng.generate([[int(t) for t in toks[0, :19]]],
                       SamplingParams(max_new_tokens=5))[0]
    return (digest(whole),
            digest(np.concatenate([r.reshape(-1) for r in rows])), got)


@pytest.mark.parametrize("name", list(PARENT))
def test_a_config_without_an_indexer_gives_the_parents_logits(name):
    from tests.test_longcat_flash import CANARY, canary

    forward, served, tokens = PARENT[name]
    got = recorded(name)
    assert got[2] == tokens
    if canary() != CANARY:
        pytest.skip("this host's float32 arithmetic is not the one the "
                    "parent's digests were recorded with")
    assert got[:2] == (forward, served)


# ---- the chip script, rehearsed -------------------------------------------------


def rehearse(phase, extra, capsys):
    import chip_glm5

    tests = os.path.join(ROOT, "perfbench", "tests", "glm_dsa")
    rc = chip_glm5.main([
        phase, "--cpu",
        "--config", os.path.join(tests, "configs", "tiny-glm.json"),
        "--mix", os.path.join(tests, "traffic", "tiny-sparse-decode.json")]
        + extra)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_chip_glm5_check_rehearsal(capsys):
    """``chip_glm5.py check`` at the benchmark's tiny configuration: the
    program inside 1e-4 of the reference through the whole-prompt program
    (prompts of 27 and 31 against an ``index_topk`` of 16) and decodes
    through both pools, one engine reused from seed to seed, and the
    seven controls that bite in float32 far outside it (the eighth rounds
    bf16 matrices, of which a float32 tree has none)."""
    import chip_glm5

    rc, result = rehearse("check", ["--seeds", "5", "6", "--controls", "1"],
                          capsys)
    assert result["worst_rel_err"] < 1e-5
    assert rc == 1 and result["ok"] is False  # float8 cannot fail here
    judged = result["results"][-1]
    assert judged["forced"]["max"] < 1e-5
    biting = chip_glm5.CONTROLS[:-1]
    assert min(judged[c]["max"] for c in biting) > 1e-3
    assert [judged["caught_by"][c] for c in biting] == ["max"] * len(biting)
    assert judged["caught_by"]["float8"] is None
    assert [r["prompt_tokens"] for r in result["results"]] == [[27, 31]] * 2
    assert "forced" not in result["results"][0]


def test_chip_glm5_sweep_rehearsal(capsys):
    rc, result = rehearse("sweep", ["--contexts", "40", "100"], capsys)
    assert rc == 0 and result["ok"]
    assert [(r["context"], r["table_width"]) for r in result["results"]] \
        == [(40, 8), (100, 16)]
    assert all(r["decode_ms"] > 0 and r["chunk_ms"] > 0
               for r in result["results"])
