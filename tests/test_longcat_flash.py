"""LongCat-Flash-Chat: a published layer of two latent-attention sublayers
and two dense feed-forwards with one routed layer beside them on a
shortcut, whose router scores identity experts after the real ones; two
latent pools a layer behind the absorbed decode kernel, both latents
scaled; a share of the routed experts held. All at a tiny size on the CPU
(``LongcatFlashConfig.tiny``: two published layers, so four sublayers, 4
heads, a latent of 128 and a roped key of 8, 32 routed and 16 identity
experts of which a token takes 6), page size 8.

The model is held to the benchmark's plain float32 reference
(``perfbench/families/longcat_flash.py``, written from the layer equations
in the expanded form and not from the program): in float32 they choose the
same experts and agree to rounding, 1e-4 of the largest reference logit.
The reference itself is held to a literal transcription of the equations,
one position, head and expert at a time.
"""

import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import InferenceEngine
from raytpu.inference.sampling import SamplingParams
from raytpu.models import mixtral
from raytpu.models.mixtral import (LongcatFlash, LongcatFlashConfig, MoEFFN,
                                   init_params, mixtral_loss_fn)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = dict(dtype=jnp.float32, attn_impl="reference",
           paged_attn="reference", remat=False)
TINY = dataclasses.replace(LongcatFlashConfig.tiny(), **F32)
ENGINE = dict(page_size=8, max_num_seqs=4, max_model_len=128)
IMPLS = ["reference", "interpret"]
E, Z, K = TINY.n_expert, TINY.n_zero_expert, TINY.n_expert_per_tok


@pytest.fixture(scope="module")
def family():
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families",
                       "longcat_flash")


@pytest.fixture(scope="module")
def params():
    return init_params(LongcatFlash(TINY), TINY, seed=1)


def file_config(c: LongcatFlashConfig, held=None):
    """The configuration file the family's reference reads, for ``c``."""
    first, count = held or c.experts_held or (0, c.n_expert)
    return {
        "family": "longcat_flash", "vocab_size": c.vocab_size,
        "max_position_embeddings": c.block_size,
        "num_layers": c.n_layer // 2, "num_attention_heads": c.n_head,
        "hidden_size": c.n_embd, "ffn_hidden_size": c.dense_inter,
        "expert_ffn_hidden_size": c.n_inter, "n_routed_experts": count,
        "published_n_routed_experts": c.n_expert,
        "experts_held": [first, count], "zero_expert_num": c.n_zero_expert,
        "zero_expert_type": "identity", "moe_topk": c.n_expert_per_tok,
        "routed_scaling_factor": c.routed_scale,
        "q_lora_rank": c.q_lora_rank, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_dim, "qk_rope_head_dim": c.qk_rope_dim,
        "v_head_dim": c.v_head_dim, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "rope_theta": c.rope_theta,
        "rms_norm_eps": c.norm_eps, "attention_bias": False,
        "attention_method": "MLA",
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


def share_of(params, held):
    """``params`` with the routed experts ``held`` = (first, count) alone."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a[held[0]:held[0] + held[1]]
        if path[-1].key in ("wg", "wi", "wo") else a, params)


# ---- the config and the parameter tree ------------------------------------------


class TestConfig:
    def test_published_values(self):
        c = LongcatFlashConfig()
        assert (c.n_layer, c.n_embd, c.n_head, c.vocab_size) \
            == (2 * 28, 6144, 64, 131072)
        assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_dim,
                c.qk_rope_dim, c.v_head_dim) == (1536, 512, 128, 64, 128)
        assert (c.n_expert, c.n_zero_expert, c.n_expert_per_tok, c.n_inter,
                c.n_shared, c.dense_inter) == (512, 256, 12, 2048, 0, 12288)
        assert (c.scoring, c.norm_topk_prob, c.routed_scale, c.rope_theta,
                c.norm_eps, c.rope_interleave) \
            == ("softmax", False, 6.0, 1e7, 1e-5, True)
        assert c.mla_scale_q_lora and c.mla_scale_kv_lora
        s = c.serving
        assert s.kv_row == 640 and s.expert_counts == (28, 512)
        assert s.expert_pairs

    def test_the_program_config_of_a_file_is_the_config(self, family):
        assert family.program_config(file_config(TINY), dict(
            attn_impl="reference", paged_attn="reference", remat=False)) \
            == dataclasses.replace(TINY, experts_held=(0, E))

    def test_a_layer_is_two_sublayers_and_a_shortcut(self, params):
        """``n_layer`` counts sublayers: every one has an attention and a
        dense feed-forward, the even ones a routed layer beside it whose
        output is added at the end of the next."""
        assert [TINY.ffn_width(i) for i in range(4)] == [96] * 4
        assert [TINY.shortcut_to(i) for i in range(4)] == [1, None, 3, None]
        assert [TINY.layer_scope(i) for i in range(4)] \
            == ["sublayer.0", "sublayer.1"] * 2
        for i in (0, 2):
            assert set(params[f"layers_{i}"]) == {
                "attn", "input_norm", "mlp", "moe", "post_attn_norm"}
            assert set(params[f"layers_{i + 1}"]) == {
                "attn", "input_norm", "mlp", "post_attn_norm"}
        moe = params["layers_0"]["moe"]
        assert set(moe) == {"router", "bias", "wg", "wi", "wo"}
        # The router and its bias are as wide as real + identity experts;
        # the identities have no matrices.
        assert moe["router"]["kernel"].shape == (64, E + Z)
        assert moe["bias"].shape == (E + Z,)
        assert moe["wg"].shape == (E, 64, 32)
        with pytest.raises(ValueError, match="odd"):
            dataclasses.replace(TINY, n_layer=3)

    def test_other_configs_have_no_shortcut_and_no_identities(self):
        for cls in (mixtral.OlmoeConfig, mixtral.JoyAIConfig,
                    mixtral.ExaoneMoeConfig, mixtral.Lfm2MoeConfig):
            c = cls.tiny()
            assert c.n_zero_expert == 0 and not c.serving.expert_pairs
            assert {c.shortcut_to(i) for i in range(c.n_layer)} == {None}
            assert {c.layer_scope(i) for i in range(c.n_layer)} == {None}
        assert not mixtral.JoyAIConfig().mla_scale_q_lora


# ---- the reference, against the equations one position at a time ------------------


def literal_logits(c: LongcatFlashConfig, params, tokens):
    """Section 1 of ISSUE 51 in float64 numpy: one published layer,
    position, head and expert at a time, the rope by adjacent pairs."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    eps = c.norm_eps
    h, nope, rope, vd = c.n_head, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim

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

    def mla(a, ys):
        """``ys`` [T, E] normed -> the attention's output [T, E]."""
        qs, ks, vs = [], [], []
        for pos, y in enumerate(ys):
            c_q = np.sqrt(c.n_embd / c.q_lora_rank) * norm(
                y @ a["q_a_proj"]["kernel"], a["q_a_norm"]["scale"])
            q = (c_q @ a["q_b_proj"]["kernel"]).reshape(h, nope + rope)
            kva = y @ a["kv_a_proj"]["kernel"]
            c_kv = np.sqrt(c.n_embd / c.kv_lora_rank) * norm(
                kva[:c.kv_lora_rank], a["kv_a_norm"]["scale"])
            k_pe = rope_pairs(kva[c.kv_lora_rank:], pos)   # not scaled
            kv = (c_kv @ a["kv_b_proj"]["kernel"]).reshape(h, nope + vd)
            qs.append([np.concatenate([q[n, :nope],
                                       rope_pairs(q[n, nope:], pos)])
                       for n in range(h)])
            ks.append([np.concatenate([kv[n, :nope], k_pe])
                       for n in range(h)])
            vs.append(kv[:, nope:])
        out = np.zeros_like(ys)
        for pos in range(len(ys)):
            heads = []
            for n in range(h):
                s = np.array([qs[pos][n] @ ks[j][n] for j in range(pos + 1)])
                w = np.exp(s / np.sqrt(nope + rope))
                w /= w.sum()
                heads.append(sum(w[j] * vs[j][n] for j in range(pos + 1)))
            out[pos] = np.concatenate(heads) @ a["o_proj"]["kernel"]
        return out

    def moe(m, y):
        z = y @ m["router"]["kernel"]
        s = np.exp(z - z.max())
        s /= s.sum()
        chosen = np.argsort(-(s + m["bias"]))[:c.n_expert_per_tok]
        acc = np.zeros_like(y)
        for e in chosen:
            w = c.routed_scale * s[e]          # without the bias, as it is
            if e >= c.n_expert:
                acc = acc + w * y              # an identity expert
                continue
            g = y @ m["wg"][e]
            acc = acc + w * ((g / (1 + np.exp(-g)) * (y @ m["wi"][e]))
                             @ m["wo"][e])
        return acc

    x = p["embed_tokens"]["embedding"][np.asarray(tokens)]
    for l in range(c.n_layer // 2):
        first, second = p[f"layers_{2 * l}"], p[f"layers_{2 * l + 1}"]
        x1 = x + mla(first["attn"], np.stack(
            [norm(r, first["input_norm"]["scale"]) for r in x]))
        h1 = np.stack([norm(r, first["post_attn_norm"]["scale"])
                       for r in x1])
        s = np.stack([moe(first["moe"], r) for r in h1])
        x2 = x1 + np.stack([swiglu(first["mlp"], r) for r in h1])
        x3 = x2 + mla(second["attn"], np.stack(
            [norm(r, second["input_norm"]["scale"]) for r in x2]))
        x = x3 + np.stack([swiglu(second["mlp"], norm(
            r, second["post_attn_norm"]["scale"])) for r in x3]) + s
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


def test_reference_swiglu_in_blocks_is_the_swiglu(family, params):
    y = jnp.asarray(np.random.default_rng(3).standard_normal((5, 64)),
                    jnp.float32)
    mlp = params["layers_1"]["mlp"]
    w = {k: v["kernel"] for k, v in mlp.items()}
    want = (jax.nn.silu(y @ w["gate_proj"]) * (y @ w["up_proj"])) \
        @ w["down_proj"]
    for block in (96, 32, 7):    # 7: no divisor but 6 and below
        family.SWIGLU_BLOCK, before = block, family.SWIGLU_BLOCK
        try:
            np.testing.assert_allclose(family._swiglu(mlp, y), want,
                                       atol=1e-5)
        finally:
            family.SWIGLU_BLOCK = before


# ---- the program's forward, loss and gradients ----------------------------------


def test_program_forward_is_the_references(family, params):
    tokens = jnp.asarray(prompts(40, 40, seed=2))
    got = LongcatFlash(TINY).apply({"params": params}, tokens)
    want = family.logits(file_config(TINY), params, tokens)
    assert rel_err(got, want) < 1e-4


def test_loss_and_gradients_against_the_reference(family, params):
    cfg = dataclasses.replace(TINY, router_aux_coef=0.0)
    tokens = jnp.asarray(prompts(32, 32, seed=3))
    want, wanted = jax.value_and_grad(
        lambda p: family.loss(file_config(TINY), p, tokens))(params)
    got, grads = jax.value_and_grad(
        lambda p: mixtral_loss_fn(LongcatFlash(cfg), p, tokens))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda g, w: float(jnp.abs(g - w).max()
                           / (jnp.abs(w).max() + 1e-12)), grads, wanted)))
    assert worst < 2e-3, worst


def test_the_tiny_preset_trains(params):
    import optax

    cfg = dataclasses.replace(TINY, remat="dots")
    model = LongcatFlash(cfg)
    opt = optax.adam(3e-3)
    step = jax.jit(mixtral.make_train_step(model, opt))
    tokens = jnp.asarray(prompts(32, 32, 32, 32, seed=4))
    state, p, losses = opt.init(params), params, []
    for _ in range(8):
        p, state, loss = step(p, state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses


# ---- the two scale corrections -----------------------------------------------------


def test_latents_are_scaled_where_the_equations_scale_them(params):
    """The cached row holds the scaled normed latent and the unscaled
    roped key; the query latent's scale reaches the scores."""
    from raytpu.models.mla import LatentAttention

    lp = params["layers_0"]["attn"]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((1, 9, 64)),
                    jnp.float32)
    plain = dataclasses.replace(TINY, mla_scale_q_lora=False,
                                mla_scale_kv_lora=False)
    _, rows = LatentAttention(TINY).apply({"params": lp}, x,
                                          method="prefill")
    _, unscaled = LatentAttention(plain).apply({"params": lp}, x,
                                               method="prefill")
    np.testing.assert_allclose(rows[..., :128],
                               unscaled[..., :128] * (64 / 128) ** 0.5,
                               rtol=1e-6)
    np.testing.assert_allclose(rows[..., 128:], unscaled[..., 128:])
    q_only = dataclasses.replace(plain, mla_scale_q_lora=True)
    got = LatentAttention(q_only).apply({"params": lp}, x,
                                        method="_project")[0]
    want = LatentAttention(plain).apply({"params": lp}, x,
                                        method="_project")[0]
    np.testing.assert_allclose(got, want * (64 / 48) ** 0.5, rtol=1e-5,
                               atol=1e-5)


# ---- routing: identities, the bias, the weights ---------------------------------


class TestRouting:
    @pytest.fixture(scope="class")
    def x(self):
        return jnp.asarray(np.random.default_rng(5).standard_normal(
            (1, 40, 64)), jnp.float32)

    @pytest.fixture(scope="class")
    def moe(self, params):
        return params["layers_2"]["moe"]

    def test_program_layer_is_the_references(self, family, moe, x):
        got, counts = MoEFFN(TINY).apply({"params": moe}, x)
        with jax.default_matmul_precision("highest"):
            want = family._moe(file_config(TINY), moe, x)
        np.testing.assert_allclose(got, want, atol=2e-5)
        w = np.asarray(family.router_weights(file_config(TINY), moe, x))[0]
        # Tokens each real expert received, then the identity pairs and
        # all pairs: every choice is one or the other.
        assert counts.shape == (E + 2,)
        assert (np.asarray(counts[:E]) == (w[:, :E] > 0).sum(0)).all()
        assert int(counts[E]) == int((w[:, E:] > 0).sum())
        assert int(counts[E + 1]) == 40 * K
        assert int(counts[:E].sum() + counts[E]) == 40 * K
        assert 0.15 < int(counts[E]) / (40 * K) < 0.55

    def test_weights_are_the_scores_times_six_not_renormalised(
            self, family, moe, x):
        w = np.asarray(family.router_weights(file_config(TINY), moe, x))[0]
        s = np.asarray(jax.nn.softmax(x[0] @ moe["router"]["kernel"], -1))
        assert ((w > 0).sum(-1) == K).all()
        np.testing.assert_allclose(w, np.where(w > 0, 6.0 * s, 0.0),
                                   rtol=1e-5)
        # Six times the chosen scores' sum: far from 6, which
        # renormalised weights would sum to.
        np.testing.assert_allclose(w.sum(-1), 6.0 * (s * (w > 0)).sum(-1),
                                   rtol=1e-5)
        assert w.sum(-1).max() < 5.0

    def test_bias_moves_the_choice_and_not_the_weight(self, family, moe, x):
        cfg = file_config(TINY)
        with_bias = np.asarray(family.router_weights(cfg, moe, x))
        without = np.asarray(family.router_weights(
            cfg, dict(moe, bias=jnp.zeros_like(moe["bias"])), x))
        moved = ((with_bias > 0) != (without > 0)).any(-1)
        assert 0.1 < moved.mean() <= 1.0
        both = (with_bias > 0) & (without > 0)
        np.testing.assert_allclose(with_bias[both], without[both],
                                   rtol=1e-6)
        # A huge bias on one identity makes every token choose it, at the
        # weight of its own score; the program agrees.
        huge = dict(moe, bias=moe["bias"].at[E + 3].set(100.0))
        w = np.asarray(family.router_weights(cfg, huge, x))
        assert (w[..., E + 3] > 0).all() and w[..., E + 3].max() < 6.0
        got, _ = MoEFFN(TINY).apply({"params": huge}, x)
        with jax.default_matmul_precision("highest"):
            want = family._moe(cfg, huge, x)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_all_identities_and_none(self, family, moe, x):
        """A token whose six choices are all identities gets ``(sum w)
        h`` and costs no row; one with none gets the routed sum alone."""
        s = jax.nn.softmax(x[0] @ moe["router"]["kernel"], -1)
        zeros = jnp.where(jnp.arange(E + Z) >= E, 50.0, 0.0)
        all_zero = dict(moe, bias=zeros)
        y, counts = MoEFFN(TINY).apply({"params": all_zero}, x)
        top = jnp.sort(s[:, E:], -1)[:, -K:].sum(-1)
        np.testing.assert_allclose(y[0], 6.0 * top[:, None] * x[0],
                                   rtol=2e-5, atol=1e-7)
        assert not np.asarray(counts[:E]).any()
        assert int(counts[E]) == int(counts[E + 1]) == 40 * K
        none = dict(moe, bias=-zeros)
        y, counts = MoEFFN(TINY).apply({"params": none}, x)
        assert int(counts[E]) == 0 and int(counts[:E].sum()) == 40 * K
        plain = dataclasses.replace(TINY, n_zero_expert=0)
        want, _ = MoEFFN(plain).apply({"params": dict(
            none, bias=jnp.zeros(E),
            router={"kernel": moe["router"]["kernel"][:, :E]})}, x)
        # (The softmax is over 48 outputs there and 32 here: the same
        # choice among the real experts, other weights; compare through
        # the reference, which scores all 48.)
        with jax.default_matmul_precision("highest"):
            ref = family._moe(file_config(TINY), none, x)
        np.testing.assert_allclose(y, ref, atol=2e-5)
        assert want.shape == y.shape

    def test_padding_counts_nowhere(self, moe, x):
        live = jnp.arange(40)[None] < 25
        _, counts = MoEFFN(TINY).apply({"params": moe}, x, live)
        _, want = MoEFFN(TINY).apply({"params": moe}, x[:, :25])
        assert (np.asarray(counts) == np.asarray(want)).all()
        assert int(counts[E + 1]) == 25 * K

    def test_the_shares_add_up_to_the_whole_layer(self, family, params, x):
        """32 chips hold one routed expert each. Their routed parts, with
        the identity term and the dense path counted once, are the uncut
        reference's layer; a pair whose expert is elsewhere, or is an
        identity, costs no row."""
        first, second = params["layers_2"], params["layers_3"]
        cfg = file_config(TINY)
        with jax.default_matmul_precision("highest"):
            whole = family._layer(cfg, x, first, second)
            h1 = family._rms_norm(
                x + family._attention(cfg, first["attn"], family._rms_norm(
                    x, first["input_norm"], TINY.norm_eps)),
                first["post_attn_norm"], TINY.norm_eps)
            w = family.router_weights(cfg, first["moe"], h1)
            identity = w[..., E:].sum(-1, keepdims=True) * h1
        moe = first["moe"]
        routed, rows = jnp.zeros_like(x), 0
        for chip in range(32):
            held = (chip, 1)
            c = dataclasses.replace(TINY, experts_held=held)
            share = dict(moe, **{k: moe[k][chip:chip + 1]
                                 for k in ("wg", "wi", "wo")})
            part, counts = MoEFFN(c).apply({"params": share}, h1)
            assert counts.shape == (1 + 2,)
            # Every chip adds the identity term whole: it owns its tokens.
            routed, rows = routed + (part - identity), rows + int(counts[0])
            with jax.default_matmul_precision("highest"):
                ref = family._moe(file_config(TINY, held), share, h1)
            np.testing.assert_allclose(part, ref, atol=2e-5)
        assert rows == int((np.asarray(w[..., :E]) > 0).sum())
        with jax.default_matmul_precision("highest"):
            # The layer with no routed expert at all: the dense path and
            # the identity term.
            none = dict(first, moe=dict(moe, **{
                k: moe[k][:1] * 0 for k in ("wg", "wi", "wo")}))
            dense = family._layer(file_config(TINY, (0, 1)), x, none,
                                  second)
        np.testing.assert_allclose(dense + routed, whole, atol=1e-4)


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
def test_served_logits_are_the_references(family, params, impl, chunk,
                                          held):
    """A prompt of 43 tokens (whole, expanded through flash attention; or
    in chunks of 16, absorbed) and 14 decoded positions through the two
    latent pools a layer, against the reference's one expanded forward
    pass; with every routed expert held, and with a share of them."""
    cfg = dataclasses.replace(TINY, attn_impl=impl, paged_attn=impl,
                              experts_held=held)
    if held:
        params = share_of(params, held)
    prompt = prompts(43)[0]
    eng, out, got = served_logits(cfg, params, prompt, 14,
                                  prefill_chunk=chunk)
    want = np.asarray(family.logits(
        file_config(TINY, held), params, jnp.asarray([prompt + out[:-1]])))[0]
    assert rel_err(got, want[len(prompt) - 1:]) < 1e-4
    stats = eng.stats()
    assert bool(stats["chunk_prefill_compiles"]) == (chunk is not None)
    log = eng.step_log()["steps"]
    # Two routed layers, 6 choices a token: a pair is an identity, a held
    # expert's, or another chip's, which is counted nowhere.
    every = (43 + 13) * 2 * K
    # (A decode's counts come back with its ids, a step later: the
    # record of a batch's first decode holds none.)
    assert sum(s.get("moe_pairs", 0) for s in log) == stats["moe_pairs"] \
        == every
    zero = sum(s.get("moe_zero_pairs", 0) for s in log)
    here = sum(s.get("moe_assignments", 0) for s in log)
    assert zero == stats["moe_zero_pairs"] and 0.15 < zero / every < 0.55
    assert zero + here == every if held is None else zero + here < every
    assert np.asarray(stats["expert_tokens"]).shape \
        == (2, held[1] if held else E)
    assert int(np.asarray(stats["expert_tokens"]).sum()) == here


def wrong(control):
    """``TINY`` wrong in one way: the cell's controls
    (``chip_longcat.py``)."""
    import chip_longcat

    return chip_longcat.wrong_config(TINY, control)


def test_controls_fail_where_the_program_passes(family, params):
    """What the cell's check must catch, at the tiny size and in float32:
    each departure from the equations moves the logits far outside the
    1e-4 the right program stands inside."""
    import chip_longcat

    prompt = prompts(40)[0]
    tokens = jnp.asarray([prompt])
    want = np.asarray(family.logits(file_config(TINY), params, tokens))[0]
    model = lambda c: np.asarray(LongcatFlash(c).apply(  # noqa: E731
        {"params": params}, tokens))[0]
    assert rel_err(model(TINY), want) < 1e-4
    for control in chip_longcat.PROGRAM_CONTROLS:
        assert rel_err(model(wrong(control)), want) > 1e-3, control


@pytest.mark.parametrize("control", ["no_identity", "shortcut_early",
                                     "bias_in_weights"])
def test_a_control_is_wrong_in_the_served_walk_too(family, params, control):
    """The three controls that are no field of the config, through the
    engine's programs: the walk asks the config for its routed layer and
    for where a shortcut ends."""
    prompt = prompts(21)[0]
    _, out, got = served_logits(wrong(control), params, prompt, 4)
    want = np.asarray(family.logits(
        file_config(TINY), params, jnp.asarray([prompt + out[:-1]])))[0]
    assert rel_err(got, want[len(prompt) - 1:]) > 1e-3


def test_batched_decode_is_solo_decode(params):
    eng = InferenceEngine(TINY, params, **ENGINE)
    batch = prompts(5, 21, 37)
    together = eng.generate(batch, SamplingParams(max_new_tokens=12))
    for prompt, out in zip(batch, together):
        solo = InferenceEngine(TINY, params, **ENGINE).generate(
            [prompt], SamplingParams(max_new_tokens=12))[0]
        assert solo == out


def test_engine_sizes_and_reports_two_pools_a_layer(params):
    eng = InferenceEngine(TINY, params, num_pages=20, **ENGINE)
    assert eng.cache.v == [] and len(eng.cache.k) == 4  # 2 layers x 2
    stats = eng.stats()
    assert stats["kv_pool_bytes"] == 4 * 20 * 8 * 256 * 4
    assert stats["kv_pool_bytes_by_kind"] == {
        "full": stats["kv_pool_bytes"], "window": 0}
    assert (stats["moe_zero_pairs"], stats["moe_pairs"]) == (0, 0)
    eng.generate(prompts(9), SamplingParams(max_new_tokens=3))
    assert eng.cache.v == [] and eng.cache.k[0].shape == (20, 8, 256)
    log = eng.step_log()["steps"]
    assert all(s["kv_bytes_per_token"] == 4 * 256 * 4 for s in log)
    # The prompt's 9 tokens, then a token a decode step: 6 pairs each in
    # each of the 2 routed layers, in the record of the step that fetched
    # the decode's ids, the one after its dispatch.
    assert [s.get("moe_pairs", 0) for s in log] \
        == [9 * 2 * K, 0, 2 * K, 2 * K]
    assert eng.stats()["moe_pairs"] == 11 * 2 * K


def test_a_router_without_identities_reports_no_pairs():
    cfg = dataclasses.replace(mixtral.JoyAIConfig.tiny(), **F32)
    eng = InferenceEngine(cfg, init_params(mixtral.JoyAI(cfg), cfg),
                          **ENGINE)
    eng.generate(prompts(9), SamplingParams(max_new_tokens=2))
    stats = eng.stats()
    assert stats["moe_pairs"] is None and stats["moe_zero_pairs"] is None
    log = eng.step_log()["steps"]
    assert all("moe_pairs" not in s for s in log)
    # The prefill's, none yet in the decode's own step, the decode's.
    assert ["moe_assignments" in s for s in log] == [True, False, True]


def test_the_counters_are_declared_and_counted(params):
    from raytpu.inference import engine as engine_mod
    from raytpu.util.metrics import DECLARED_METRICS

    names = ("raytpu_infer_moe_pairs_total",
             "raytpu_infer_moe_zero_pairs_total")
    assert all(n in DECLARED_METRICS for n in names)
    seen = []
    pairs, zero = engine_mod._moe_pairs_total, engine_mod._moe_zero_pairs_total
    before = pairs.inc, zero.inc
    pairs.inc = lambda n=1, **kw: seen.append(("pairs", n))
    zero.inc = lambda n=1, **kw: seen.append(("zero", n))
    try:
        eng = InferenceEngine(TINY, params, **ENGINE)
        eng.generate(prompts(9), SamplingParams(max_new_tokens=2))
    finally:
        pairs.inc, zero.inc = before
    stats = eng.stats()
    assert sum(n for k, n in seen if k == "pairs") == stats["moe_pairs"]
    assert sum(n for k, n in seen if k == "zero") == stats["moe_zero_pairs"]


def test_the_programs_carry_the_scopes(params):
    """``moe.zero`` and a scope a sublayer are named scopes of the
    serving programs; ``attn.mla`` stays the attention's."""
    eng = InferenceEngine(TINY, params, **ENGINE)
    b = 4
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    text = eng._decode_fn.lower(
        eng._params, eng.cache.k, [], i32(b), i32(b), i32(b), i32(b, 2),
        i32(b)).as_text(debug_info=True)
    for scope in ("moe.zero", "sublayer.0", "sublayer.1", "attn.mla",
                  "moe.router", "moe.experts"):
        assert scope in text, scope
    assert "sublayer.0/attn.mla" in text.replace('"', "")


def test_prefix_cache_shares_both_pools_pages(params):
    """Every pool is one kind and a page is addressable by its content: a
    second prompt with the first's 24-token prefix starts from its three
    pages in every one of the four pools, through the chunk path, and
    decodes the same tokens as alone."""
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
    alone = InferenceEngine(TINY, params, enable_prefix_cache=False,
                            **ENGINE).generate(
        [shared], SamplingParams(max_new_tokens=8))[0]
    assert got == alone


# ---- what stays refused ----------------------------------------------------------


def test_the_model_is_served_on_one_device(params):
    with pytest.raises(ValueError, match="one device"):
        InferenceEngine(TINY, params, tp=2, **ENGINE)


def test_the_model_takes_no_disaggregated_role():
    from raytpu.inference.serving import LLMDeployment

    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="latent"):
            LLMDeployment._target(model="longcat_flash", role=role)
    with pytest.raises(ValueError, match="'longcat_flash'"):
        LLMDeployment._target(model="longcat")


def test_the_deployment_serves_the_family():
    from raytpu.inference.serving import LLMDeployment

    dep = LLMDeployment._target(model="longcat_flash", engine_options=dict(
        page_size=8, max_num_seqs=2, max_model_len=64))
    try:
        out = list(dep.generate([5, 6, 7, 8, 9], max_new_tokens=4))
        assert len(out) == 4 and all(0 <= t < 512 for t in out)
        stats = dep.stats()
        assert stats["kv_pool_bytes_by_kind"]["window"] == 0
        assert stats["moe_pairs"] == (5 + 3) * 2 * K
        assert 0 < stats["moe_zero_pairs"] < stats["moe_pairs"]
    finally:
        dep.shutdown()


# ---- the others' programs are what they were -------------------------------------


# Logits of the parent commit's tree (PR 50, 06fafe5) for each routed
# configuration without identity experts, at its ``tiny()`` size in
# float32 with seed 3: the whole forward over two rows of 24 tokens, and
# the engine's whole-prompt and decode programs over 19 + 5; SHA-256 of the
# float32 bytes, and the greedy tokens. A digest is this host's
# arithmetic too: ``CANARY`` is the digest of a product, a softmax and a
# norm that no program of the repository computes, and where it reads
# otherwise the digests are not compared (the tokens still are).
CANARY = "10adaf11a145b375"
PARENT = {
    "OlmoeConfig": ("41ea6ec032fd07d4", "cd6167ffffa744a7",
                    [86, 314, 86, 126, 86]),
    "JoyAIConfig": ("ba01aa3971f08e26", "a2308282aba17c94",
                    [234, 323, 146, 288, 57]),
    "ExaoneMoeConfig": ("2a719d902d032375", "6dd312bac5fd4477",
                        [165, 326, 194, 187, 192]),
    "Lfm2MoeConfig": ("9a5b0c2a9ce8d877", "4e4a5dcb36afe2b7",
                      [104, 105, 463, 373, 190])}


def digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(a, np.float32)).tobytes()).hexdigest()[:16]


def canary() -> str:
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((96, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 80)), jnp.float32)
    y = jax.nn.softmax(a @ b, axis=-1) @ b.T
    return digest(y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                                    + 1e-5))


@pytest.mark.parametrize("name", list(PARENT))
def test_a_config_without_identities_gives_the_parents_logits(name):
    forward, served, tokens = PARENT[name]
    c = dataclasses.replace(getattr(mixtral, name).tiny(), **F32)
    model = mixtral.Mixtral(c)
    params = init_params(model, c, seed=3, batch=1)
    toks = np.random.default_rng(11).integers(1, c.vocab_size, (2, 24))
    whole = model.apply({"params": params}, jnp.asarray(toks, jnp.int32))
    eng = InferenceEngine(c, params, page_size=8, max_num_seqs=2,
                          max_model_len=64,
                          **({"drafting": False} if c.mtp_layers else {}))
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
    assert got == tokens
    if canary() != CANARY:
        pytest.skip("this host's float32 arithmetic is not the one the "
                    "parent's digests were recorded with")
    assert digest(whole) == forward
    assert digest(np.concatenate([r.reshape(-1) for r in rows])) == served


# ---- the chip script, rehearsed -------------------------------------------------


@pytest.mark.parametrize("phase,extra", [
    ("check", ["--seeds", "5", "6", "--controls", "1"]),
    ("long", ["--seeds", "5", "--tokens", "70", "--controls", "1"])])
def test_chip_longcat_rehearsal(phase, extra, capsys):
    """``chip_longcat.py`` at the benchmark's tiny configuration: the
    program inside 1e-4 of the reference through the whole-prompt program
    and absorbed decodes (``check``, one engine reused from seed to
    seed) and through five chunks and the latent pages (``long``), and
    the seven controls that bite in float32 far outside it (the eighth
    rounds bf16 matrices, of which a float32 tree has none)."""
    import json

    import chip_longcat

    tests = os.path.join(ROOT, "perfbench", "tests", "longcat")
    rc = chip_longcat.main([
        phase, "--cpu",
        "--config", os.path.join(tests, "configs", "tiny-longcat.json"),
        "--mix", os.path.join(tests, "traffic", "tiny-shortcut-decode.json")]
        + extra)
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["worst_rel_err"] < 1e-5
    assert rc == 1 and result["ok"] is False  # float8 cannot fail here
    judged = result["results"][-1]
    assert judged["forced"]["max"] < 1e-5
    biting = chip_longcat.CONTROLS[:-1]
    assert min(judged[c]["max"] for c in biting) > 1e-3
    assert [judged["caught_by"][c] for c in biting] == ["max"] * len(biting)
    assert judged["float8"]["max"] < 1e-5
    assert judged["caught_by"]["float8"] is None
    assert judged["pairs_here"] > 0 and judged["zero_pairs"] > 0
    if phase == "check":
        assert [r["prompt_tokens"] for r in result["results"]] \
            == [[11, 15]] * 2
        assert "forced" not in result["results"][0]
    else:
        assert judged["prompt_tokens"] == [70]
        assert judged["programs"]["chunk_prefill_compiles"] \
            and not judged["programs"]["prefill_compiles"]
