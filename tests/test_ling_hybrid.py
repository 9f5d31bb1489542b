"""Ling-3.0-flash-VL's language model: five delta-rule linear-attention
(KDA) layers to every latent-attention layer, a float32 matrix a head and
the short convolutions' tails at a sequence's seat beside one latent pool,
experts chosen inside the best groups. All at a tiny size on the CPU
(``LingHybridConfig.tiny`` cut to three layers, published layers 1, 10 and
11: a dense KDA layer, a routed KDA layer and the routed latent layer; of
16 experts in 4 groups a token takes 4 inside its best 2), page size 16,
float32. (``perfbench/tests/test_ling.py`` serves the whole period.)

The served engine is held to the benchmark's plain float32 reference
(``perfbench/families/ling_hybrid.py``, written from the layer equations
and not from the program: the recurrence one position after another, no
cache, no state carried, no chunked form): in float32 they choose the
same experts and agree to rounding at every position of a prompt, through
chunks, and at every decoded row.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import InferenceEngine, PagedKVCache
from raytpu.inference.sampling import SamplingParams
from raytpu.models.gpt2 import State, state_specs
from raytpu.models.mixtral import (LingHybrid, LingHybridConfig,
                                   MixtralConfig, MoEFFN, init_params)
from raytpu.ops import kda

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = [1, 10, 11]
TINY = dataclasses.replace(
    LingHybridConfig.tiny(), n_layer=3,
    layer_types=("kda", "kda", "full_attention"), block_size=64,
    dtype=jnp.float32, attn_impl="reference", paged_attn="reference",
    remat=False, choice_bias=0.05)
# One decode bucket and one length of prompt and of chunk: few programs.
ENGINE = dict(page_size=16, max_num_seqs=3, max_model_len=48,
              decode_buckets=[3], prefill_buckets=[32])
# Float32 rounding between two orders of the same sums, over the largest
# reference logit (the chunked form sums a block's rows in another order
# than the recurrence does).
ROUNDING = 2e-4


@pytest.fixture(scope="module")
def family():
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families",
                       "ling_hybrid")


@pytest.fixture(scope="module")
def params():
    return init_params(LingHybrid(TINY), TINY, seed=1, batch=1)


def file_config(c: LingHybridConfig, held=None):
    """The configuration file the family's reference reads, for ``c``."""
    return {
        "family": "ling_hybrid", "vocab_size": c.vocab_size,
        "max_position_embeddings": c.block_size,
        "num_hidden_layers": c.n_layer, "layers_held": HELD,
        "layer_group_size": 6, "first_k_dense_replace": 2,
        "num_attention_heads": c.n_head, "num_key_value_heads": c.n_kv_head,
        "hidden_size": c.n_embd, "head_dim": c.head_dim,
        "intermediate_size": c.dense_inter,
        "moe_intermediate_size": c.n_inter,
        "moe_shared_expert_intermediate_size": c.n_inter,
        "num_experts": (held or (0, c.n_expert))[1],
        "published_num_experts": c.n_expert,
        "experts_held": list(held or (0, c.n_expert)),
        "num_experts_per_tok": c.n_expert_per_tok, "n_group": c.n_group,
        "topk_group": c.topk_group, "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scale, "score_function": "sigmoid",
        "moe_router_enable_expert_bias": True, "use_qk_norm": True,
        "q_lora_rank": None, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_dim, "qk_rope_head_dim": c.qk_rope_dim,
        "rotary_dim": c.qk_rope_dim, "v_head_dim": c.v_head_dim,
        "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps,
        "short_conv_kernel_size": c.conv_taps, "linear_silu": True,
        "kda_safe_gate": True, "kda_lower_bound": c.kda_lower_bound,
        "no_kda_lora": True, "use_kda_lora": False, "group_norm_size": 1,
        "gated_attention_proj_granularity_type": "head_wise",
        "expert_swiglu_limit_list": [0] * 42,
        "share_expert_swiglu_limit_list": [0] * 42,
        "assumed": {"expert_bias_std": c.choice_bias,
                    "kda_gate_init": {"A": list(c.kda_gate_init[0]),
                                      "dt_bias": list(c.kda_gate_init[1])}},
        "compute_dtype": "float32", "param_dtype": "float32"}


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, TINY.vocab_size, size=n)]
            for n in lengths]


# ---- the recurrence's two forms against the literal one -------------------------


def operands(b, t, h=3, d=16, seed=0, gate=3.0):
    """Seeded ``q, k, v, g, beta`` of a KDA layer and a state that is not
    zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = -5.0 * jax.nn.sigmoid(gate * jax.random.normal(ks[3], (b, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, h, d, d))


@pytest.mark.parametrize("case", ["from-a-state", "bound-for-a-block",
                                  "padded-rows"])
def test_chunked_form_is_the_literal_recurrence(family, case):
    """Blocks of 16 against one position after another: from a state that
    is not zero; with every gate at the bound -5 for a whole block (the
    factorised products hold e^{40} about the block's middle); with a tail
    of padding rows, which decay nothing and write nothing, behind a last
    live row inside a block."""
    (q, k, v, g, beta), state = operands(2, 48)
    if case == "bound-for-a-block":
        g = g.at[:, 16:32].set(-5.0)
    if case == "padded-rows":
        g, beta = g.at[:, 29:].set(0.0), beta.at[:, 29:].set(0.0)
    with jax.default_matmul_precision("highest"):
        want, end = jax.jit(family.kda_recurrence)(q, k, v, g, beta, state)
        if case == "padded-rows":  # the state after the last live row
            _, end = family.kda_recurrence(
                *(x[:, :29] for x in (q, k, v, g, beta)), state)
    got, got_end = jax.jit(kda.kda_chunked)(q, k, v, g, beta, state)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_end, end, atol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_decode_form_is_both(family, impl):
    """One row a sequence at its seat, in ``jax.numpy`` and through the
    kernel interpreted: the literal recurrence's row, the chunked form's,
    the seat written and no other, a row at position 0 from zeros."""
    (q, k, v, g, beta), state = operands(3, 1, seed=2)
    seats = jnp.asarray([4, 1, 0], jnp.int32)
    first = jnp.asarray([False, True, False])
    array = jnp.ones((6, 3, 16, 16), jnp.float32).at[seats].set(state)
    start = jnp.where(first[:, None, None, None], 0.0, array[seats])
    with jax.default_matmul_precision("highest"):
        want, end = family.kda_recurrence(q, k, v, g, beta, start)
    chunked, _ = kda.kda_chunked(q, k, v, g, beta, start)
    got, written = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], array, seats, first,
                                  force=impl)
    np.testing.assert_allclose(got, want[:, 0], atol=1e-5)
    np.testing.assert_allclose(got, chunked[:, 0], atol=1e-5)
    np.testing.assert_allclose(written[seats], end, atol=1e-5)
    untouched = np.asarray([2, 3, 5])
    assert (np.asarray(written)[untouched] == 1.0).all()


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_decode_form_refuses_a_state_that_is_not_float32(impl):
    """No silent second path: a state of another type never reaches the
    kernel's place unseen (it is ``kda_decode_reference``'s to step, which
    a check's control calls by name)."""
    (q, k, v, g, beta), _ = operands(2, 1, seed=3)
    seats = jnp.asarray([1, 2], jnp.int32)
    first = jnp.asarray([False, False])
    array = jnp.ones((3, 3, 16, 16), jnp.bfloat16)
    with pytest.raises(ValueError, match="float32"):
        kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                       array, seats, first, force=impl)
    o, written = kda.kda_decode_reference(
        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], array, seats, first)
    assert o.dtype == jnp.float32 and written.dtype == jnp.bfloat16


# ---- the cache: a latent pool beside typed state arrays ------------------------


def test_cache_holds_a_latent_pool_and_two_arrays_a_layer_of_two_dtypes():
    specs = [(State((4, 16, 16), jnp.float32), State((3, 192)))] * 2
    cache = PagedKVCache(1, 16, 4, 4, 16, dtype=jnp.bfloat16, latent_row=256,
                         state_shapes=specs, seats=2)
    assert len(cache.k) == 1 and cache.v == [] \
        and cache.k[0].shape == (16, 4, 256)
    assert [(a.shape, a.dtype) for a in cache.state] == [
        ((3, 4, 16, 16), jnp.float32), ((3, 3, 192), jnp.bfloat16)] * 2
    assert cache.state_bytes == 2 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert cache.token_bytes == 256 * 2
    assert cache.allocate("a", 5) and cache.allocate("b", 3)
    assert sorted((cache.seat("a"), cache.seat("b"))) == [1, 2]
    assert not cache.allocate("c", 1) and cache.free_pages() == 15 - 3
    seat = cache.seat("a")
    cache.free("a")
    assert cache.allocate("c", 1) and cache.seat("c") == seat
    # A bare shape is one array in the cache's dtype, as it always was.
    assert state_specs((2, 64)) == (State((2, 64)),) \
        and state_specs(None) == ()


def test_published_values_and_the_cache_it_builds(params):
    c = LingHybridConfig()
    assert (c.n_layer, c.n_embd, c.n_head, c.head_dim, c.conv_taps) \
        == (42, 2560, 32, 128, 4)
    assert (c.n_expert, c.n_expert_per_tok, c.n_group, c.topk_group,
            c.n_inter, c.dense_inter, c.n_shared, c.first_dense) \
        == (512, 8, 8, 4, 768, 6144, 1, 2)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim,
            c.v_head_dim, c.attn_head_gate) == (None, 512, 128, 64, 128, True)
    assert c.layer_types.count("kda") == 35 and c.layer_types[:6] \
        == ("kda",) * 5 + ("full_attention",)
    served = c.serving
    assert served.layer_states[5] is None and served.layer_states[0] == (
        State((32, 128, 128), jnp.float32), State((3, 12288)))
    assert served.kv_row == 640 and served.layer_windows == (None,) * 7
    assert served.expert_counts == (40, 512)
    eng = InferenceEngine(TINY, params, **ENGINE)
    cache = eng.cache
    assert len(cache.k) == 1 and cache.v == [] and len(cache.state) == 4
    assert cache.state_bytes == 2 * (4 * 16 * 16 + 3 * 192) * 4
    assert eng.prefix_cache is None
    stats = eng.stats()
    assert (stats["state_seats_total"], stats["state_bytes"]) \
        == (3, 4 * cache.state_bytes)
    assert set(params["layers_1"]["kda"]) == {
        "q_proj", "k_proj", "v_proj", "f_proj", "b_proj", "g_proj",
        "o_proj", "conv_kernel", "A_log", "dt_bias", "o_norm"}
    assert set(params["layers_2"]["attn"]) == {
        "q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "g_proj", "o_proj"}


# ---- the engine against the reference -------------------------------------------


class Recording(InferenceEngine):
    """An engine that keeps the logits of every row its programs computed
    for a sequence, by (request, position)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.rows = {}
        for name in ("_prefill_fn", "_chunk_fn", "_decode_fn"):
            setattr(self, name, self._keeping(getattr(self, name)))

    def _keeping(self, fn):
        def kept(*a):
            res = fn(*a)
            self._last = res[0]
            return res

        return kept

    def _run_prefill(self, seq, out):
        before = seq.cached_len
        n = super()._run_prefill(seq, out)
        logits = np.asarray(self._last)
        logits = logits.reshape(-1, logits.shape[-1])
        for j in range(seq.cached_len - before):
            self.rows[seq.request_id, before + j] = logits[j]
        return n

    def _run_decode(self, seqs, out):
        before = [s.cached_len for s in seqs]
        n = super()._run_decode(seqs, out)
        logits = np.asarray(self._last)
        for i, (seq, at) in enumerate(zip(seqs, before)):
            self.rows[seq.request_id, at] = logits[i]
        return n


def serve(params, requests, new_tokens=5, engine=None, **options):
    eng = engine or Recording(TINY, params, **{**ENGINE, **options})
    first = len(eng.rows)
    ids = [f"r{first}-{i}" for i in range(len(requests))]
    for rid, prompt in zip(ids, requests):
        eng.add_request(rid, prompt, SamplingParams(
            max_new_tokens=new_tokens))
    tokens = {rid: [] for rid in ids}
    while eng.has_unfinished():
        for o in eng.step():
            tokens[o.request_id].append(o.token_id)
    return [tokens[rid] for rid in ids], ids, eng


_REFERENCE = {}


def reference(family, params, tokens):
    """The reference's logits of ``tokens``, one compiled program for
    every length: the rows are causal, so the tokens are padded to the
    longest sequence an engine here holds and the padding's rows cut."""
    if family not in _REFERENCE:
        _REFERENCE[family] = jax.jit(
            lambda p, t: family.logits(file_config(TINY), p, t))
    padded = np.zeros((1, ENGINE["max_model_len"]), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(_REFERENCE[family](params, padded))[0, :len(tokens)]


def moved(family, params, eng, rid, prompt, generated):
    """Of every row the engine computed for ``rid``, its largest
    difference from the reference's row of that position, teacher-forced
    over the tokens served, over the largest reference logit."""
    tokens = prompt + generated[:-1]
    want = reference(family, params, tokens)
    got = np.stack([eng.rows[rid, p] for p in range(len(tokens))])
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def chunked(family, params):
    """One engine whose prompts go through chunks of 16 rows (the chunked
    form, the latent layer expanded), shared by the tests that need no
    other."""
    return Recording(TINY, params, **{**ENGINE, "prefill_buckets": [16]},
                     prefill_chunk=16, chunk_buckets=[16])


def test_whole_prompt_padded_and_decoded_rows_are_the_references(
        family, params):
    """A prompt of 23 tokens whole in a bucket of 32 (the state left is
    the last live row's), then 5 decoded rows through the seats."""
    (prompt,) = prompts(23)
    (out,), (rid,), eng = serve(params, [prompt])
    assert eng.stats()["prefill_compiles"] \
        and not eng.stats()["chunk_prefill_compiles"]
    assert moved(family, params, eng, rid, prompt, out) < ROUNDING


def test_chunks_carry_the_state_and_a_batch_decodes_as_alone(
        family, params, chunked):
    """Prompts of 5, 23 and 41 tokens, the first whole and the others
    through chunks of 16 (the last of 7 and 9 live rows in a bucket of
    16), decoded together: every row of every chunk and every decoded row
    is the reference's, a seat a sequence while it runs and none after."""
    batch = prompts(5, 23, 41, seed=3)
    together, ids, eng = serve(params, batch, engine=chunked)
    assert eng.stats()["chunk_prefill_compiles"]
    for prompt, out, rid in zip(batch, together, ids):
        assert moved(family, params, eng, rid, prompt, out) < ROUNDING
    steps = eng.step_log()["steps"]
    assert max(s["state_seats"] for s in steps) == 3
    assert max(s["state_bytes"] for s in steps) == 3 * eng.cache.state_bytes
    assert steps[-1]["state_seats"] == 0 == eng.cache.seats_in_use()


def test_a_seat_reused_by_a_second_sequence_starts_from_zeros(
        family, params, chunked):
    """After the batch above every seat holds what a finished sequence
    left; the next sequences take them and read none of it, through a
    chunk that holds position 0 and through a whole prompt's program."""
    assert all(np.abs(np.asarray(a[1:])).max() > 0
               for a in chunked.cache.state)
    second = prompts(19, 37, 7, seed=5)
    outs, ids, eng = serve(params, second, engine=chunked)
    for prompt, out, rid in zip(second, outs, ids):
        assert moved(family, params, eng, rid, prompt, out) < ROUNDING


def test_the_training_forward_is_the_references(family, params):
    (prompt,) = prompts(ENGINE["max_model_len"], seed=9)
    got = jax.jit(lambda p, t: LingHybrid(TINY).apply({"params": p}, t))(
        params, jnp.asarray([prompt]))[0]
    want = reference(family, params, prompt)
    assert float(np.abs(got - want).max() / np.abs(want).max()) < ROUNDING


# ---- routing by groups, and a share of it ---------------------------------------


ROUTED = MixtralConfig(
    vocab_size=64, n_layer=1, n_head=2, n_kv_head=2, n_embd=32, n_inter=16,
    n_expert=16, n_expert_per_tok=4, n_group=8, topk_group=4,
    scoring="sigmoid", choice_bias=0.2, routed_scale=2.5, n_shared=1,
    dtype=jnp.float32, param_dtype=jnp.float32)


def routed_layer():
    x = jnp.asarray(np.random.default_rng(5).standard_normal((24, 32)),
                    jnp.float32)
    return x, MoEFFN(ROUTED).init(jax.random.PRNGKey(2), x)["params"]


def test_the_eight_shares_add_up_to_the_uncut_layer(family):
    """Eight chips hold one routing group each. Their routed parts, with
    the shared expert counted once, are the uncut layer's output (the
    program's and the reference's); a token none of whose four groups is
    the share's contributes nothing there, and a token's experts lie in
    four groups at most."""
    x, moe = routed_layer()
    whole, counts = MoEFFN(ROUTED).apply({"params": moe}, x)
    cfg = dict(file_config(TINY), hidden_size=32, num_experts_per_tok=4,
               n_group=8, topk_group=4, published_num_experts=16,
               routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        w = family.router_weights(cfg, moe, x)
        shared = family._swiglu(moe["shared"], x)
        want = family._experts(
            dict(cfg, num_experts=16, experts_held=[0, 16]), moe, x)
    np.testing.assert_allclose(whole, want, atol=2e-5)
    chosen = np.asarray(w) > 0
    assert (chosen.sum(-1) == 4).all() and int(counts.sum()) == 4 * 24
    in_group = chosen.reshape(24, 8, 2).any(-1)
    assert (in_group.sum(-1) <= 4).all() and not in_group.all(0).all()
    routed = jnp.zeros_like(x)
    for chip in range(8):
        held = (2 * chip, 2)
        c = dataclasses.replace(ROUTED, experts_held=held)
        share = dict(moe, **{k: moe[k][held[0]:held[0] + 2]
                             for k in ("wg", "wi", "wo")})
        part, rows = MoEFFN(c).apply({"params": share}, x)
        routed = routed + (part - shared)
        assert int(rows.sum()) == int(chosen[:, held[0]:held[0] + 2].sum())
        elsewhere = ~in_group[:, chip]
        assert elsewhere.any()
        np.testing.assert_allclose(np.asarray(part - shared)[elsewhere], 0.0,
                                   atol=1e-6)
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)


def test_plain_top_k_is_another_choice(family):
    """The control the groups exist against: without them some token takes
    an expert of a fifth group."""
    x, moe = routed_layer()
    plain = dataclasses.replace(ROUTED, n_group=1, topk_group=1)
    grouped, _ = MoEFFN(ROUTED).apply({"params": moe}, x)
    ungrouped, _ = MoEFFN(plain).apply({"params": moe}, x)
    assert float(jnp.abs(grouped - ungrouped).max()) > 1e-2
    with pytest.raises(ValueError, match="groups"):
        dataclasses.replace(ROUTED, n_group=3)


# ---- what refuses such a model, by name -----------------------------------------


def test_prefix_cache_a_sharded_engine_and_drafting_refuse(params):
    with pytest.raises(ValueError, match="keep a state"):
        InferenceEngine(TINY, params, enable_prefix_cache=True, **ENGINE)
    with pytest.raises(ValueError, match="keep a state"):
        InferenceEngine(TINY, params, tp=2, **ENGINE)
    with pytest.raises(ValueError, match="no prediction module"):
        InferenceEngine(TINY, params, drafting=True, **ENGINE)
