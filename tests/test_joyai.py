"""JoyAI-LLM-Flash: latent attention (MLA) behind one pool a layer and an
absorbed decode kernel, sigmoid routing with a correction bias, a shared
expert, a leading dense layer, and a share of the experts held. All at a
tiny size on the CPU (``JoyAIConfig.tiny``: a dense layer and two routed
ones, 4 heads, a latent of 128 and a roped key of 8, 16 experts of which
a token takes 4), page size 8.

The model is held to the benchmark's plain float32 reference
(``perfbench/families/joyai.py``, written from the layer equations in the
expanded form and not from the program): in float32 they choose the same
experts and agree to rounding, 1e-4 of the largest reference logit. The
reference itself is held to a literal transcription of the equations,
one position, head and expert at a time.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import InferenceEngine, PagedKVCache
from raytpu.inference.sampling import SamplingParams
from raytpu.models import llama as llama_mod
from raytpu.models.mixtral import (GlmDsaConfig, JoyAI, JoyAIConfig,
                                   LatentMoEConfig, LongcatFlashConfig,
                                   MixtralConfig, MoEFFN, OlmoeConfig,
                                   init_params)
from raytpu.models.mla import LatentAttention, deinterleave
from raytpu.ops.mla_attention import (expanded_parts, expands,
                                      latent_row_width, latent_rows,
                                      mla_paged_attention)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = dict(dtype=jnp.float32, attn_impl="reference",
           paged_attn="reference", remat=False, choice_bias=0.05)
TINY = dataclasses.replace(JoyAIConfig.tiny(), **F32)
ENGINE = dict(page_size=8, max_num_seqs=4, max_model_len=128)
IMPLS = ["reference", "interpret"]


@pytest.fixture(scope="module")
def family():
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families",
                       "joyai")


@pytest.fixture(scope="module")
def params():
    return init_params(JoyAI(TINY), TINY, seed=1)


def file_config(c: JoyAIConfig, held=None):
    """The configuration file the family's reference reads, for ``c``."""
    first, count = held or c.experts_held or (0, c.n_expert)
    return {
        "family": "joyai", "vocab_size": c.vocab_size,
        "max_position_embeddings": c.block_size,
        "num_hidden_layers": c.n_layer, "num_attention_heads": c.n_head,
        "num_key_value_heads": c.n_kv_head, "hidden_size": c.n_embd,
        "head_dim": c.head_dim, "intermediate_size": c.dense_inter,
        "moe_intermediate_size": c.n_inter,
        "n_routed_experts": count, "published_n_routed_experts": c.n_expert,
        "experts_held": [first, count], "n_shared_experts": c.n_shared,
        "num_experts_per_tok": c.n_expert_per_tok,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scale, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "first_k_dense_replace": c.first_dense, "moe_layer_freq": 1,
        "q_lora_rank": c.q_lora_rank, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_dim, "qk_rope_head_dim": c.qk_rope_dim,
        "qk_head_dim": c.qk_nope_dim + c.qk_rope_dim,
        "v_head_dim": c.v_head_dim, "rope_theta": c.rope_theta,
        "rope_interleave": c.rope_interleave, "rope_scaling": None,
        "rms_norm_eps": c.norm_eps, "attention_bias": False,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "assumed": {"e_score_correction_bias_std": c.choice_bias},
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
        c = JoyAIConfig()
        assert (c.n_layer, c.n_embd, c.n_head, c.vocab_size) \
            == (40, 2048, 32, 129280)
        assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_dim,
                c.qk_rope_dim, c.v_head_dim) == (1536, 512, 128, 64, 128)
        assert (c.n_expert, c.n_expert_per_tok, c.n_inter, c.n_shared,
                c.first_dense, c.dense_inter) == (256, 8, 768, 1, 1, 7168)
        assert (c.scoring, c.choice_bias, c.routed_scale, c.rope_theta,
                c.rope_interleave) == ("sigmoid", 0.0, 2.5, 32e6, True)
        s = c.serving
        assert s.kv_row == 640 and s.expert_counts == (39, 256)

    def test_the_program_config_of_a_file_is_the_config(self, family):
        assert family.program_config(
            file_config(TINY), dict(attn_impl="reference",
                                    paged_attn="reference", remat=False)) \
            == dataclasses.replace(TINY, experts_held=(0, 16))

    def test_feed_forward_by_layer_index(self, params):
        """Layer 0 is the dense SwiGLU of ``dense_inter``, the others the
        routed layer: chosen by index, for every family."""
        assert [TINY.ffn_width(i) for i in range(3)] == [96, None, None]
        assert set(params["layers_0"]) == {"attn", "input_norm", "mlp",
                                           "post_attn_norm"}
        assert params["layers_0"]["mlp"]["gate_proj"]["kernel"].shape \
            == (64, 96)
        assert set(params["layers_1"]["moe"]) == {"router", "bias", "wg",
                                                  "wi", "wo", "shared"}
        assert llama_mod.LlamaConfig.tiny().ffn_width(1) == 352
        assert OlmoeConfig.tiny().ffn_width(0) is None

    def test_param_tree_has_the_published_projections(self, params):
        attn = params["layers_1"]["attn"]
        shapes = {k: v["kernel"].shape for k, v in attn.items()
                  if "kernel" in v}
        assert shapes == {
            "q_a_proj": (64, 48), "q_b_proj": (48, 4 * 24),
            "kv_a_proj": (64, 128 + 8), "kv_b_proj": (128, 4 * 32),
            "o_proj": (4 * 16, 64)}
        assert attn["q_a_norm"]["scale"].shape == (48,)
        assert attn["kv_a_norm"]["scale"].shape == (128,)

    def test_bad_shares_and_scorings_are_refused(self):
        with pytest.raises(ValueError, match="experts_held"):
            dataclasses.replace(TINY, experts_held=(12, 8))
        with pytest.raises(ValueError, match="scoring"):
            dataclasses.replace(TINY, scoring="tanh")
        with pytest.raises(ValueError, match="dense_inter"):
            MixtralConfig(first_dense=1)


# ---- the reference, against the equations one position at a time ------------------


def literal_logits(c: JoyAIConfig, params, tokens):
    """Section 1 of ISSUE 34 in float64 numpy: one layer, position, head
    and expert at a time, the rope by adjacent pairs."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    eps = c.norm_eps

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

    def swiglu(w, y):
        g = y @ w["gate_proj"]["kernel"]
        return (g / (1 + np.exp(-g)) * (y @ w["up_proj"]["kernel"])) \
            @ w["down_proj"]["kernel"]

    t = len(tokens)
    h, nope, rope, vd = c.n_head, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
    x = p["embed_tokens"]["embedding"][np.asarray(tokens)]
    for i in range(c.n_layer):
        lp = p[f"layers_{i}"]
        a = lp["attn"]
        qs, ks, vs = [], [], []
        for pos in range(t):
            y = norm(x[pos], lp["input_norm"]["scale"])
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
        out = np.zeros_like(x)
        for pos in range(t):
            heads = []
            for n in range(h):
                s = np.array([qs[pos][n] @ ks[j][n] for j in range(pos + 1)])
                s = s / np.sqrt(nope + rope)
                w = np.exp(s - s.max())
                w /= w.sum()
                heads.append(sum(w[j] * vs[j][n] for j in range(pos + 1)))
            out[pos] = np.concatenate(heads) @ a["o_proj"]["kernel"]
        x = x + out
        for pos in range(t):
            y = norm(x[pos], lp["post_attn_norm"]["scale"])
            if i < c.first_dense:
                x[pos] = x[pos] + swiglu(lp["mlp"], y)
                continue
            moe = lp["moe"]
            s = 1 / (1 + np.exp(-(y @ moe["router"]["kernel"])))
            chosen = np.argsort(-(s + moe["bias"]))[:c.n_expert_per_tok]
            weights = s[chosen] / s[chosen].sum() * c.routed_scale
            acc = swiglu(moe["shared"], y)
            for e, w in zip(chosen, weights):
                g = y @ moe["wg"][e]
                acc = acc + w * ((g / (1 + np.exp(-g)) * (y @ moe["wi"][e]))
                                 @ moe["wo"][e])
            x[pos] = x[pos] + acc
    x = np.stack([norm(row, p["final_norm"]["scale"]) for row in x])
    return x @ p["lm_head"]["kernel"]


def test_reference_is_the_equations_position_by_position(family, params):
    tokens = prompts(11)[0]
    want = literal_logits(TINY, params, tokens)
    got = np.asarray(family.logits(file_config(TINY), params,
                                   jnp.asarray([tokens])))[0]
    assert rel_err(got, want) < 2e-5


def test_reference_rows_are_the_whole_logits_rows(family, params):
    tokens = jnp.asarray(prompts(20))
    whole = family.logits(file_config(TINY), params, tokens)
    some = family.logits(file_config(TINY), params, tokens, rows=[3, 19])
    np.testing.assert_allclose(some, whole[:, [3, 19]], rtol=1e-6)


# ---- the rope ---------------------------------------------------------------------


def test_interleaved_rope_scores_are_the_pairwise_rotations():
    """``[evens | odds]`` then rotation by halves permutes q and k alike:
    every score equals the one under rotation of adjacent pairs."""
    rng = np.random.default_rng(0)
    d, t, theta = 8, 13, 32e6
    q = rng.standard_normal((t, d)).astype(np.float32)
    k = rng.standard_normal((t, d)).astype(np.float32)
    cos, sin = llama_mod.rope_tables(d, jnp.arange(t), theta)
    halves = lambda x: np.asarray(llama_mod.apply_rope(  # noqa: E731
        deinterleave(jnp.asarray(x))[None, None], cos, sin))[0, 0]
    ang = np.arange(t)[:, None] * theta ** (-np.arange(0, d, 2) / d)

    def pairs(x):
        out = np.empty_like(x)
        out[:, 0::2] = x[:, 0::2] * np.cos(ang) - x[:, 1::2] * np.sin(ang)
        out[:, 1::2] = x[:, 1::2] * np.cos(ang) + x[:, 0::2] * np.sin(ang)
        return out

    np.testing.assert_allclose(halves(q) @ halves(k).T,
                               pairs(q) @ pairs(k).T, atol=1e-4)
    # And it is a permutation of the pairwise result, not another rope.
    np.testing.assert_allclose(halves(q), np.asarray(
        deinterleave(jnp.asarray(pairs(q)))), atol=1e-5)


# ---- absorbed = expanded ------------------------------------------------------------


def test_absorbed_attention_is_the_expanded(params):
    """One layer's ``prefill`` (expanded, flash reference) against
    ``step`` at a chunk's shape and at a decode row's (absorbed, through
    the latent pages) on the same rows, float32."""
    attn, lp = LatentAttention(TINY), params["layers_1"]["attn"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 21, 64)), jnp.float32)
    want, rows = attn.apply({"params": lp}, x, method="prefill")
    width = latent_row_width(128, 8)
    assert rows.shape == (1, 21, width) and width == 256
    assert not np.asarray(rows[..., 136:]).any()
    pages = jnp.zeros((8, 8, width), jnp.float32)
    table = jnp.asarray([[3, 5, 1, 0]], jnp.int32)
    slots = lambda pos: np.asarray(table)[0][pos // 8] * 8 + pos % 8  # noqa
    pos = np.arange(16)
    got, pages = attn.apply(
        {"params": lp}, x[0, :16], pages, jnp.asarray(slots(pos))[None],
        table, jnp.asarray(pos)[None], method="step")
    np.testing.assert_allclose(got, want[0, :16], atol=2e-5)
    for p in range(16, 21):
        got, pages = attn.apply(
            {"params": lp}, x[:, p], pages, jnp.asarray([[slots(p)]]),
            table, jnp.asarray([[p]]), method="step")
        np.testing.assert_allclose(got, want[:, p], atol=2e-5)
    # The pool holds what prefill returned, a row a token, once.
    held = np.asarray(pages).reshape(64, width)[slots(np.arange(21))]
    np.testing.assert_allclose(held, rows[0], atol=1e-6)


# ---- a chunk expands -----------------------------------------------------------------


@pytest.mark.parametrize("config, first", [
    (JoyAIConfig(), 171), (LongcatFlashConfig(), 171), (GlmDsaConfig(), 359),
    (TINY, 13)], ids=["joyai", "longcat", "glm5", "tiny"])
def test_the_break_even_is_a_number_of_queries(config, first):
    """``first`` is the fewest queries of one sequence that attend
    cheaper expanded, from the widths alone: 2 (640 + 512) against 2 (192
    + 192) FLOPs a (query, head, key) and 2 x 512 x 256 a (key, head)
    once at JoyAI's and LongCat's; a decode row and a verify step's two
    stay absorbed everywhere."""
    widths = dict(rank=config.kv_lora_rank, nope_dim=config.qk_nope_dim,
                  rope_dim=config.qk_rope_dim, v_dim=config.v_head_dim)
    assert [t for t in (1, 2, first - 1, first, 2048)
            if expands(t, **widths)] == [first, 2048]
    assert config.chunk_parts(first - 1, 4096, 128) is None
    # Segments of whole pages, no more rows than the chunk has; the
    # chunk's own rows are one part, a cut last segment one.
    assert config.chunk_parts(2048, 0, 128) == (2048, 1)
    assert config.chunk_parts(2048, 6144, 128) == (2048, 4)
    assert config.chunk_parts(2048, 6144 + 128, 128) == (2048, 5)
    assert expanded_parts(20, 17, 8) == (16, 3)
    # GLM-5's chunk reads the rows its indexer chooses: never expanded.
    served = config.serving.chunk_parts
    assert served is None if isinstance(config, GlmDsaConfig) \
        else served(first, 0, 128) == config.chunk_parts(first, 0, 128)


# start, tokens: a first chunk; a later one behind three whole segments;
# a short last one padded to its bucket; one that starts on a page's edge
# that is no segment's (a prefix-cache hit's tail); one inside a page.
_CHUNKS = {"first": (0, 16), "later": (48, 16), "short_last": (64, 5),
           "page_edge": (24, 16), "inside_a_page": (29, 11)}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(_CHUNKS))
def test_expanded_chunk_is_the_absorbed(params, impl, case, monkeypatch):
    """A chunk of 16 rows attends expanded at the tiny widths (segments
    of two pages): against the absorbed form over the same pages, by the
    float32 reference and by ``impl``, and against ``prefill`` over the
    whole sequence. The table's columns past the live pages and the
    bucket's padding rows name page 0, which holds 1e4."""
    start, take = _CHUNKS[case]
    cfg = dataclasses.replace(TINY, attn_impl=impl, paged_attn=impl)
    lp = params["layers_1"]["attn"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 80, 64)), jnp.float32)
    whole, rows = LatentAttention(TINY).apply({"params": lp}, x,
                                              method="prefill")
    width = rows.shape[-1]
    table = np.zeros((1, 11), np.int32)
    table[0, :10] = 1 + rng.permutation(10)
    slots = lambda pos: table[0][pos // 8] * 8 + pos % 8  # noqa: E731
    pool = np.full((11, 8, width), 1e4, np.float32)
    pool.reshape(-1, width)[slots(np.arange(start))] = rows[0, :start]
    pos, dests = np.zeros((2, 1, 16), np.int32)
    pos[0, :take] = np.arange(start, start + take)
    dests[0, :take] = slots(pos[0, :take])
    xs = jnp.zeros((16, 64)).at[:take].set(x[0, start:start + take])

    def step(c):
        return LatentAttention(c).apply(
            {"params": lp}, xs, jnp.asarray(pool), jnp.asarray(dests),
            jnp.asarray(table), jnp.asarray(pos), method="step")[0][:take]

    assert cfg.chunk_parts(16, start, 8) == (16, 1 + -(-start // 16))
    got = step(cfg)
    np.testing.assert_allclose(got, whole[0, start:start + take], atol=2e-5)
    monkeypatch.setattr(LatentMoEConfig, "chunk_parts", lambda *a: None)
    np.testing.assert_allclose(got, step(TINY), atol=2e-5)
    np.testing.assert_allclose(got, step(cfg), atol=2e-5)


# ---- the kernel, interpreted ----------------------------------------------------


def latent_case(b, t, h, lens, pages_per_seq, page=8, rank=128, rope=8,
                seed=0):
    rng = np.random.default_rng(seed)
    width = latent_row_width(rank, rope)
    n_pages = 1 + b * pages_per_seq
    pool = rng.standard_normal((n_pages, page, width)).astype(np.float32)
    pool[..., rank + rope:] = 0.0
    tables = np.zeros((b, pages_per_seq + 1), np.int32)  # a dead column
    for i in range(b):
        live = -(-lens[i] // page)
        tables[i, :live] = 1 + rng.permutation(
            np.arange(i * pages_per_seq, (i + 1) * pages_per_seq))[:live]
    q_lat = rng.standard_normal((b, t, h, rank)).astype(np.float32)
    q_pe = rng.standard_normal((b, t, h, rope)).astype(np.float32)
    positions = np.stack([np.arange(n - t, n) for n in lens]).astype(np.int32)
    return (jnp.asarray(q_lat), jnp.asarray(q_pe), jnp.asarray(pool),
            jnp.asarray(tables), jnp.asarray(positions))


def dense_latent_attention(q_lat, q_pe, pool, tables, positions, scale):
    """Per sequence, token and head, over the rows gathered by hand."""
    q_lat, q_pe, pool = (np.asarray(a, np.float64)
                         for a in (q_lat, q_pe, pool))
    b, t, h, rank = q_lat.shape
    rope = q_pe.shape[-1]
    out = np.zeros((b, t, h, rank))
    for i in range(b):
        rows = pool[np.asarray(tables)[i]].reshape(-1, pool.shape[-1])
        for j in range(t):
            n = int(positions[i, j]) + 1
            for head in range(h):
                s = (rows[:n, :rank] @ q_lat[i, j, head]
                     + rows[:n, rank:rank + rope] @ q_pe[i, j, head]) * scale
                w = np.exp(s - s.max())
                out[i, j, head] = (w / w.sum()) @ rows[:n, :rank]
    return out


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("shape", ["decode", "chunk"])
def test_latent_paged_attention(impl, shape):
    """Decode: four sequences of ragged contexts (one a single token, one
    ending on a page's last slot, one of several blocks of pages) behind
    a table with a dead column. Chunk: 24 query tokens of one sequence,
    three query blocks, the chunk's own rows among the keys."""
    if shape == "decode":
        case = latent_case(4, 1, 4, [1, 16, 37, 700], 88)
    else:
        case = latent_case(1, 24, 4, [85], 11, seed=1)
    scale = 24 ** -0.5
    got = mla_paged_attention(*case, sm_scale=scale, force=impl)
    want = dense_latent_attention(*case, scale)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_latent_kernel_checks_its_shapes():
    q_lat, q_pe, pool, tables, positions = latent_case(1, 1, 4, [9], 2)
    with pytest.raises(ValueError, match="do not match a pool"):
        mla_paged_attention(q_lat, q_pe, pool[..., :128], tables, positions,
                            sm_scale=1.0, force="interpret")
    assert latent_rows(jnp.ones((2, 512)), jnp.ones((2, 64))).shape \
        == (2, 640)


# ---- routing ------------------------------------------------------------------------


def routed(cfg, moe_params, x):
    """The routed layer's output without its shared expert, and counts."""
    c = dataclasses.replace(cfg, n_shared=0)
    p = {k: v for k, v in moe_params.items() if k != "shared"}
    return MoEFFN(c).apply({"params": p}, x)


class TestRouting:
    @pytest.fixture(scope="class")
    def x(self):
        return jnp.asarray(np.random.default_rng(5).standard_normal(
            (1, 40, 64)), jnp.float32)

    def test_bias_moves_the_choice_and_not_the_weight(self, family, params,
                                                      x):
        moe = params["layers_1"]["moe"]
        cfg = file_config(TINY)
        with_bias = np.asarray(family.router_weights(cfg, moe, x))
        without = np.asarray(family.router_weights(
            cfg, dict(moe, bias=jnp.zeros_like(moe["bias"])), x))
        moved = ((with_bias > 0) != (without > 0)).any(-1)
        assert 0.1 < moved.mean() < 1.0   # a measurable share of tokens
        # Where both choose an expert, its weight differs only through
        # the sum it is normalised by: the raw scores are the same.
        s = np.asarray(jax.nn.sigmoid(x @ moe["router"]["kernel"]))
        chosen = with_bias > 0
        raw = np.where(chosen, s, 0.0)
        np.testing.assert_allclose(
            with_bias, raw / raw.sum(-1, keepdims=True) * 2.5, rtol=1e-5)
        # A huge bias on one expert makes every token choose it, at the
        # weight of its own score.
        huge = dict(moe, bias=moe["bias"].at[3].set(100.0))
        w = np.asarray(family.router_weights(cfg, huge, x))
        assert (w[..., 3] > 0).all() and w.max() <= 2.5

    def test_weights_sum_to_the_scaling_factor(self, family, params, x):
        w = np.asarray(family.router_weights(
            file_config(TINY), params["layers_2"]["moe"], x))
        assert ((w > 0).sum(-1) == 4).all()
        np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)

    def test_one_group_is_the_identity(self, params, x):
        """``noaux_tc`` with ``n_group`` groups keeps the ``topk_group``
        groups whose two best biased scores sum highest, then takes the
        top k inside them; with one group of one kept that is plain
        top-k, which is what the layer computes."""
        moe = params["layers_1"]["moe"]
        s = np.asarray(jax.nn.sigmoid(x @ moe["router"]["kernel"]))[0]
        choice = s + np.asarray(moe["bias"])
        n_group, topk_group, k = 1, 1, 4
        groups = choice.reshape(len(choice), n_group, -1)
        score = np.sort(groups, -1)[..., -2:].sum(-1)
        kept = np.argsort(-score, -1)[:, :topk_group]
        mask = np.zeros_like(score, bool)
        np.put_along_axis(mask, kept, True, -1)
        masked = np.where(np.repeat(mask, groups.shape[-1], -1), choice, 0.0)
        grouped = np.sort(np.argsort(-masked, -1)[:, :k], -1)
        plain = np.sort(np.argsort(-choice, -1)[:, :k], -1)
        assert (grouped == plain).all()
        _, counts = routed(TINY, moe, x)
        assert (np.bincount(plain.ravel(), minlength=16)
                == np.asarray(counts)).all()

    def test_program_layer_is_the_references(self, family, params, x):
        moe = params["layers_1"]["moe"]
        got, counts = MoEFFN(TINY).apply({"params": moe}, x)
        with jax.default_matmul_precision("highest"):
            want = family._experts(file_config(TINY), moe, x)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert int(counts.sum()) == 40 * 4

    def test_the_shares_add_up_to_the_whole_layer(self, family, params, x):
        """Eight chips hold two experts each. Their routed parts, with
        the shared expert counted once, are the uncut layer; pairs whose
        expert is elsewhere cost no row."""
        moe = params["layers_2"]["moe"]
        with jax.default_matmul_precision("highest"):
            whole = family._experts(file_config(TINY), moe, x)
            shared = family._swiglu(moe["shared"], x)
        total, pairs = jnp.zeros_like(whole), 0
        for chip in range(8):
            held = (2 * chip, 2)
            c = dataclasses.replace(TINY, experts_held=held)
            share = dict(moe, **{w: moe[w][2 * chip:2 * chip + 2]
                                 for w in ("wg", "wi", "wo")})
            part, counts = routed(c, share, x)
            assert counts.shape == (2,)
            total, pairs = total + part, pairs + int(counts.sum())
            # The reference, given the same share, gives the same part.
            with jax.default_matmul_precision("highest"):
                ref = family._experts(file_config(TINY, held), share, x)
            np.testing.assert_allclose(part, ref - shared, atol=2e-5)
        assert pairs == 40 * 4
        np.testing.assert_allclose(total + shared, whole, atol=5e-5)

    def test_padding_and_absent_experts_are_dead_rows(self, params, x):
        c = dataclasses.replace(TINY, experts_held=(4, 8))
        moe = params["layers_1"]["moe"]
        share = dict(moe, **{w: moe[w][4:12] for w in ("wg", "wi", "wo")})
        live = jnp.arange(40)[None] < 25
        y, counts = MoEFFN(dataclasses.replace(c, n_shared=0)).apply(
            {"params": {k: v for k, v in share.items() if k != "shared"}},
            x, live)
        _, all_counts = routed(TINY, moe, x[:, :25])
        assert (np.asarray(counts) == np.asarray(all_counts)[4:12]).all()
        assert not np.asarray(y[:, 25:]).any()

    def test_softmax_layers_are_what_they_were(self):
        """OLMoE's layer: no bias, no shared expert, no new leaf."""
        c = dataclasses.replace(OlmoeConfig.tiny(), dtype=jnp.float32)
        x = jnp.ones((1, 3, 64))
        p = MoEFFN(c).init(jax.random.PRNGKey(0), x)["params"]
        assert set(p) == {"router", "wi", "wg", "wo"}
        assert c.n_expert_held == c.n_expert and c.serving.kv_row is None
        assert c.serving.expert_counts == (2, 8)


# ---- the cache ------------------------------------------------------------------


class TestLatentCache:
    def test_one_pool_a_layer(self):
        c = PagedKVCache(3, 10, 8, 4, 16, dtype=jnp.bfloat16,
                         latent_row=256)
        assert [a.shape for a in c.k] == [(10, 8, 256)] * 3 and c.v == []
        assert c.token_bytes == 3 * 256 * 2
        assert c.kinds == (0,) and c.window is None

    def test_a_kv_cache_is_what_it_was(self):
        c = PagedKVCache(2, 10, 8, 4, 16, dtype=jnp.float32)
        assert len(c.k) == len(c.v) == 2 and c.k[0].shape == (10, 8, 64)
        assert c.token_bytes == 2 * 2 * 64 * 4 and c.latent_row is None

    def test_allocate_and_free_are_unchanged(self):
        plain = PagedKVCache(1, 10, 8, 4, 16)
        latent = PagedKVCache(1, 10, 8, 4, 16, latent_row=256)
        for c in (plain, latent):
            assert c.allocate("a", 20) and c.allocate("b", 9)
            assert not c.allocate("c", 8 * 6)
            assert c.extend("a", 30)
            c.free("b")
        assert plain.block_table("a") == latent.block_table("a")
        assert plain.free_pages() == latent.free_pages() == 5
        assert [plain.slot("a", p) for p in (0, 9, 29)] \
            == [latent.slot("a", p) for p in (0, 9, 29)]
        assert (plain.chunk_dests("a", 4, 10, 16)
                == latent.chunk_dests("a", 4, 10, 16)).all()

    def test_no_window_layers_over_a_latent_pool(self):
        with pytest.raises(ValueError, match="latent pool"):
            PagedKVCache(2, 10, 8, 1, 8, layer_windows=(8, None),
                         window_pages=20, latent_row=256)


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
@pytest.mark.parametrize("chunk", [None, 8, 16])
@pytest.mark.parametrize("held", [None, (4, 8)])
def test_served_logits_are_the_references(family, params, impl, chunk,
                                          held):
    """A prompt of 43 tokens (whole, expanded through flash attention; in
    chunks of 8, absorbed; or in chunks of 16, over the tiny widths'
    break-even of 13 queries: expanded, the cached rows a segment of two
    pages at a time) and 14 decoded positions through the latent pages,
    against the reference's one expanded forward pass; with every expert
    held, and with a share of them."""
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
    # Chunks at 0, 16 and 32: their own rows and 0, 1, 2 segments.
    parts = [1, 2, 3] if chunk == 16 else []
    assert [s["chunk_expanded"] for s in log if "chunk_expanded" in s] \
        == parts
    assert (stats["chunks_expanded"], stats["chunk_segments_expanded"]) \
        == (len(parts), sum(parts))
    # Two routed layers, 4 experts a token: a pair whose expert is not
    # held is counted nowhere, as it is computed nowhere.
    # (A decode's counts come back with its ids, a step later: the
    # record of a batch's first decode holds none.)
    pairs = sum(s.get("moe_assignments", 0) for s in log)
    every = (43 + 13) * 2 * 4
    assert pairs == every if held is None else 0 < pairs < every
    assert np.asarray(stats["expert_tokens"]).shape \
        == (2, held[1] if held else 16)


def test_controls_fail_where_the_program_passes(family, params):
    """What the cell's check must catch, at the tiny size and in float32:
    each departure from the equations moves the logits far outside the
    1e-4 the right program stands inside."""
    prompt = prompts(40)[0]
    tokens = jnp.asarray([prompt])
    want = np.asarray(family.logits(file_config(TINY), params, tokens))[0]
    model = lambda c, p=params: np.asarray(JoyAI(c).apply(  # noqa: E731
        {"params": p}, tokens))[0]
    assert rel_err(model(TINY), want) < 1e-4
    no_bias = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if path[-1].key == "bias" else a,
        params)
    assert rel_err(model(TINY, no_bias), want) > 1e-2
    for wrong in (dict(norm_topk_prob=False), dict(routed_scale=1.0),
                  dict(rope_interleave=False), dict(rope_theta=1e4)):
        c = dataclasses.replace(TINY, **wrong)
        assert rel_err(model(c), want) > 1e-3, wrong


def test_batched_decode_is_solo_decode(params):
    eng = InferenceEngine(TINY, params, **ENGINE)
    batch = prompts(5, 21, 37)
    together = eng.generate(batch, SamplingParams(max_new_tokens=12))
    for prompt, out in zip(batch, together):
        solo = InferenceEngine(TINY, params, **ENGINE).generate(
            [prompt], SamplingParams(max_new_tokens=12))[0]
        assert solo == out


def test_engine_sizes_and_reports_one_pool_a_layer(params):
    eng = InferenceEngine(TINY, params, num_pages=20, **ENGINE)
    assert eng.cache.v == [] and len(eng.cache.k) == 3
    stats = eng.stats()
    assert stats["kv_pool_bytes"] == 3 * 20 * 8 * 256 * 4
    assert stats["kv_pool_bytes_by_kind"] == {
        "full": stats["kv_pool_bytes"], "window": 0}
    eng.generate(prompts(9), SamplingParams(max_new_tokens=3))
    assert eng.cache.v == [] and eng.cache.k[0].shape == (20, 8, 256)
    assert all(s["kv_bytes_per_token"] == 3 * 256 * 4
               for s in eng.step_log()["steps"])


def test_a_kv_engine_reports_its_rows():
    cfg = dataclasses.replace(llama_mod.LlamaConfig.tiny(),
                              dtype=jnp.float32, attn_impl="reference",
                              paged_attn="reference", remat=False)
    eng = InferenceEngine(cfg, llama_mod.init_params(
        llama_mod.Llama(cfg), cfg), page_size=4, max_num_seqs=2,
        max_model_len=32)
    row = 2 * 2 * 32 * 4  # K and V, kv heads x head_dim, float32
    eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
    log = eng.step_log()["steps"]
    assert log and all(s["kv_bytes_per_token"] == 2 * row for s in log)


def test_prefix_cache_shares_latent_pages(params):
    """Every layer is one kind and a page is addressable by its content:
    a second prompt with the first's 24-token prefix starts from its three
    pages, through the chunk path, and decodes the same tokens as alone."""
    a, b = prompts(30, 9)
    shared = a[:24] + b
    eng = InferenceEngine(TINY, params, **ENGINE)
    assert eng.prefix_cache is not None
    before = eng.stats()["prefix_cache"]  # the counters are the process's
    eng.generate([a], SamplingParams(max_new_tokens=2))
    got = eng.generate([shared], SamplingParams(max_new_tokens=8))[0]
    hits = eng.stats()["prefix_cache"]
    assert hits["hits"] - before["hits"] == 1
    assert hits["hit_tokens"] - before["hit_tokens"] == 24
    # The tail of 9 in a bucket of 16 expands: its own rows, a whole
    # segment of two pages and one page of the next.
    assert [s["chunk_expanded"] for s in eng.step_log()["steps"]
            if "chunk_expanded" in s] == [3]
    assert eng.stats()["chunk_segments_expanded"] == 3
    alone = InferenceEngine(TINY, params, enable_prefix_cache=False,
                            **ENGINE).generate(
        [shared], SamplingParams(max_new_tokens=8))[0]
    assert got == alone


# ---- what stays refused ----------------------------------------------------------


def test_a_latent_model_is_served_on_one_device(params):
    with pytest.raises(ValueError, match="one device"):
        InferenceEngine(TINY, params, tp=2, **ENGINE)
    dense = dataclasses.replace(TINY, first_dense=3)  # no routed layer
    with pytest.raises(ValueError, match="latent pool"):
        InferenceEngine(dense, init_params(JoyAI(dense), dense), tp=2,
                        **ENGINE)


def test_a_latent_model_takes_no_disaggregated_role():
    from raytpu.inference.serving import LLMDeployment

    with pytest.raises(ValueError, match="latent"):
        LLMDeployment._target(model="joyai", role="prefill")
    with pytest.raises(ValueError, match="'joyai'"):
        LLMDeployment._target(model="joy")


def test_the_deployment_serves_the_family():
    from raytpu.inference.serving import LLMDeployment

    dep = LLMDeployment._target(model="joyai", engine_options=dict(
        page_size=8, max_num_seqs=2, max_model_len=64))
    try:
        out = list(dep.generate([5, 6, 7, 8, 9], max_new_tokens=4))
        assert len(out) == 4 and all(0 <= t < 512 for t in out)
        assert dep.stats()["kv_pool_bytes_by_kind"]["window"] == 0
    finally:
        dep.shutdown()


# ---- training forward -------------------------------------------------------------


def test_loss_and_gradients_against_the_reference(family, params):
    from raytpu.models.mixtral import mixtral_loss_fn

    cfg = dataclasses.replace(TINY, router_aux_coef=0.0)
    tokens = jnp.asarray(prompts(32, 32, seed=3))
    want, wanted = jax.value_and_grad(
        lambda p: family.loss(file_config(TINY), p, tokens))(params)
    got, grads = jax.value_and_grad(
        lambda p: mixtral_loss_fn(JoyAI(cfg), p, tokens))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda g, w: float(jnp.abs(g - w).max()
                           / (jnp.abs(w).max() + 1e-12)), grads, wanted)))
    assert worst < 2e-3, worst


# ---- the chip script, rehearsed -------------------------------------------------


@pytest.mark.parametrize("phase,extra", [
    ("check", ["--seeds", "5", "6", "--controls", "1"]),
    ("long", ["--seeds", "5", "--tokens", "70", "--controls", "1"])])
def test_chip_joyai_rehearsal(phase, extra, capsys):
    """``chip_joyai.py`` at the benchmark's tiny configuration: the
    program inside 1e-4 of the reference through the whole-prompt program
    and absorbed decodes (``check``, one engine reused from seed to
    seed) and through five chunks and the latent pages (``long``), and
    the six controls that bite in float32 far outside it (the seventh
    rounds bf16 matrices, of which a float32 tree has none)."""
    import json

    import chip_joyai

    tests = os.path.join(ROOT, "perfbench", "tests", "joyai")
    rc = chip_joyai.main([
        phase, "--cpu",
        "--config", os.path.join(tests, "configs", "tiny-joyai.json"),
        "--mix", os.path.join(tests, "traffic", "tiny-latent-decode.json")]
        + extra)
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["worst_rel_err"] < 1e-5
    assert rc == 1 and result["ok"] is False  # float8 cannot fail here
    judged = result["results"][-1]
    assert judged["forced"]["max"] < 1e-5
    biting = chip_joyai.CONTROLS[:-1]
    assert min(judged[c]["max"] for c in biting) > 0.02
    assert [judged["caught_by"][c] for c in biting] == ["max"] * 6
    assert judged["float8"]["max"] < 1e-5
    assert judged["caught_by"]["float8"] is None
    assert judged["pairs_here"] > 0
    if phase == "check":
        assert [r["prompt_tokens"] for r in result["results"]] \
            == [[11, 15]] * 2
        assert "forced" not in result["results"][0]
    else:
        assert judged["prompt_tokens"] == [70]
        assert judged["programs"]["chunk_prefill_compiles"] \
            and not judged["programs"]["prefill_compiles"]
