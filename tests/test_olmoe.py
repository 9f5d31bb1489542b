"""OLMoE: the dropless routed-expert layer, the q/k norms, and the model
served through ``InferenceEngine`` against the benchmark's plain
reference (``perfbench/families/olmoe.py``), all at a tiny size on the
CPU: 2 layers, 8 experts of width 32, hidden 64.

Tolerances. In float32 compute the program and the reference choose the
same experts, so they agree to rounding: 1e-4 of the largest reference
logit (measured 1e-6). In bf16 compute they are held to the tolerance the
benchmark's mix states (``perfbench/traffic/moe-batch-decode.json``),
which an 8-bit path or a renormalised router must fail.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu import serve
from raytpu.inference import InferenceEngine
from raytpu.inference.sampling import SamplingParams
from raytpu.models import llama as llama_mod
from raytpu.models.mixtral import (MixtralConfig, Mixtral, MoEFFN,
                                   OlmoeConfig, init_params)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = dict(dtype=jnp.float32, attn_impl="reference",
           paged_attn="reference", remat=False)
TINY = dataclasses.replace(OlmoeConfig.tiny(), **F32)
ENGINE = dict(page_size=8, max_num_seqs=4, max_model_len=64)


@pytest.fixture(scope="module")
def family():
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families",
                       "olmoe")


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "moe-batch-decode.json")) as f:
        return json.load(f)


def file_config(c: MixtralConfig, compute="float32"):
    """The configuration file the family's reference reads, for ``c``."""
    return {"family": "olmoe", "vocab_size": c.vocab_size,
            "max_position_embeddings": c.block_size,
            "num_hidden_layers": c.n_layer,
            "num_attention_heads": c.n_head,
            "num_key_value_heads": c.n_kv_head, "hidden_size": c.n_embd,
            "intermediate_size": c.n_inter, "num_experts": c.n_expert,
            "num_experts_per_tok": c.n_expert_per_tok,
            "norm_topk_prob": c.norm_topk_prob, "rope_theta": c.rope_theta,
            "rms_norm_eps": c.norm_eps, "compute_dtype": compute,
            "param_dtype": "float32"}


def rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float32)
                        - np.asarray(want, np.float32)).max()
                 / np.abs(np.asarray(want)).max())


# ---- the layer against a per-token loop -------------------------------------


def layer_and_params(k, norm, seed=0, hidden=64, width=32, experts=8):
    c = dataclasses.replace(TINY, n_embd=hidden, n_inter=width,
                            n_expert=experts, n_expert_per_tok=k,
                            norm_topk_prob=norm)
    layer = MoEFFN(c)
    params = layer.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, hidden)))["params"]
    return c, layer, params


def per_token_loop(c, params, x, live=None):
    """The layer's arithmetic one token and one expert at a time."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    x = np.asarray(x, np.float64)
    y = np.zeros_like(x)
    counts = np.zeros(c.n_expert, np.int64)
    for t in range(x.shape[0]):
        if live is not None and not live[t]:
            continue
        z = x[t] @ p["router"]["kernel"]
        probs = np.exp(z - z.max())
        probs /= probs.sum()
        chosen = np.argsort(-probs, kind="stable")[:c.n_expert_per_tok]
        w = probs[chosen] / (probs[chosen].sum() if c.norm_topk_prob else 1)
        for e, we in zip(chosen, w):
            g = x[t] @ p["wg"][e]
            h = g / (1 + np.exp(-g)) * (x[t] @ p["wi"][e])
            y[t] += we * (h @ p["wo"][e])
            counts[e] += 1
    return y, counts


def skew_router(params, to=0, never=1):
    """Every token's first feature is 1 below; expert ``to`` then takes
    every token and expert ``never`` none."""
    kernel = np.array(params["router"]["kernel"])
    kernel[0, to], kernel[0, never] = 50.0, -50.0
    return {**params, "router": {"kernel": jnp.asarray(kernel)}}


@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("n", [1, 8, 33, 256])
def test_dropless_layer_matches_a_per_token_loop(n, k):
    c, layer, params = layer_and_params(k, norm=False)
    params = skew_router(params)
    x = np.array(jax.random.normal(jax.random.PRNGKey(n), (n, c.n_embd)))
    x[:, 0] = 1.0
    y, counts = layer.apply({"params": params}, jnp.asarray(x))
    want, want_counts = per_token_loop(c, params, x)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert np.array_equal(np.asarray(counts), want_counts)
    # Dropless: every pair is computed, whatever the imbalance.
    assert int(counts.sum()) == n * k and counts.dtype == jnp.int32
    assert int(counts[0]) == n                 # one expert takes them all
    assert int(counts[1]) == (0 if k < 8 else n)  # top-8 of 8 takes all


@pytest.mark.parametrize("norm", [True, False])
def test_topk_weights_renormalised_only_where_the_config_says(norm):
    c, layer, params = layer_and_params(2, norm=norm)
    x = jax.random.normal(jax.random.PRNGKey(5), (8, c.n_embd))
    y, _ = layer.apply({"params": params}, x)
    want, _ = per_token_loop(c, params, x)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    other, _ = MoEFFN(dataclasses.replace(c, norm_topk_prob=not norm)).apply(
        {"params": params}, x)
    assert np.abs(np.asarray(other) - want).max() > 1e-2


@pytest.mark.parametrize("k", [2, 8])
def test_a_tokens_output_alone_equals_its_output_in_a_batch(k):
    c, layer, params = layer_and_params(k, norm=False, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (33, c.n_embd))
    batched, _ = layer.apply({"params": params}, x)
    for t in (0, 17, 32):
        alone, counts = layer.apply({"params": params}, x[t:t + 1])
        np.testing.assert_allclose(np.asarray(alone[0]),
                                   np.asarray(batched[t]), atol=1e-6)
        assert int(counts.sum()) == k


def test_padding_is_routed_nowhere_and_not_counted():
    c, layer, params = layer_and_params(2, norm=False, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, c.n_embd))
    live = np.ones((2, 8), bool)
    live[0, 5:] = live[1, 2:] = False
    y, counts = layer.apply({"params": params}, x, jnp.asarray(live))
    want, want_counts = per_token_loop(c, params, x.reshape(16, -1),
                                       live.reshape(16))
    np.testing.assert_allclose(np.asarray(y).reshape(16, -1), want,
                                atol=2e-5)
    assert np.array_equal(np.asarray(counts), want_counts)
    assert int(counts.sum()) == 7 * 2
    assert not np.asarray(y)[~live].any()


def test_the_layer_is_differentiable_and_balanced_at_init():
    c, layer, params = layer_and_params(2, norm=True)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, c.n_embd))

    def loss(p):
        (y, _), mut = layer.apply({"params": p}, x,
                                  mutable=["intermediates"])
        return jnp.sum(y * y), mut["intermediates"]["moe_aux"][0]

    (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert np.isfinite(float(value)) and 0.5 < float(aux) < 2.5
    for name in ("wi", "wg", "wo"):
        assert float(jnp.abs(grads[name]).max()) > 0
    assert float(jnp.abs(grads["router"]["kernel"]).max()) > 0


# ---- the model, training forward --------------------------------------------


def model_and_params(c, seed=0):
    model = Mixtral(c)
    return model, init_params(model, c, seed=seed, batch=1)


@pytest.mark.parametrize("scan", [True, False])
@pytest.mark.parametrize("k", [2, 8])
def test_training_forward_matches_the_plain_reference(family, scan, k):
    c = dataclasses.replace(TINY, n_expert_per_tok=k, scan_layers=scan)
    model, params = model_and_params(c)
    moe = params["layers" if scan else "layers_0"]["moe"]
    assert moe["router"]["kernel"].shape[-2:] == (64, 8)
    assert "q_norm" in params["layers" if scan else "layers_0"]["attn"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 1, 512)
    got = model.apply({"params": params}, tokens)
    want = family.logits(file_config(c), params, tokens)
    assert rel_err(got, want) < 1e-4


def test_reference_follows_norm_topk_prob_and_each_matches(family):
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 1, 512)
    outs = {}
    for norm in (False, True):
        c = dataclasses.replace(TINY, norm_topk_prob=norm)
        model, params = model_and_params(c)
        outs[norm] = model.apply({"params": params}, tokens)
        want = family.logits(file_config(c), params, tokens)
        assert rel_err(outs[norm], want) < 1e-4
    assert rel_err(outs[True], outs[False]) > 1e-2


def test_qk_norm_on_and_off_differ(family):
    model, params = model_and_params(TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 24), 1, 512)
    on = model.apply({"params": params}, tokens)
    off_cfg = dataclasses.replace(TINY, qk_norm=False)
    # The same weights without the two norms: flax ignores the leaves a
    # module does not ask for.
    off = Mixtral(off_cfg).apply({"params": params}, tokens)
    assert rel_err(off, on) > 1e-2
    assert rel_err(on, family.logits(file_config(TINY), params,
                                     tokens)) < 1e-4
    assert "q_norm" not in init_params(Mixtral(off_cfg), off_cfg,
                                       batch=1)["layers"]["attn"]


# ---- the model through the engine -----------------------------------------------


def served_logits(eng, prompt, new_tokens):
    """Greedy decode of one prompt; the logits the engine sampled each
    token from, in order of position, and the tokens."""
    rows = []
    # Which row of a program's logits the engine samples from: the
    # prompt's last position, the chunk's last live row (padding goes to
    # the scratch page), the batch's only row.
    fns = {"_prefill_fn": lambda res, a: res[0][len(prompt) - 1],
           "_chunk_fn": lambda res, a: res[0][0, int(
               (np.asarray(a[5]) >= eng.page_size).sum()) - 1],
           "_decode_fn": lambda res, a: res[0][0]}

    def keep(name, fn):
        def wrapped(*a):
            res = fn(*a)
            rows.append((name, np.asarray(fns[name](res, a))))
            return res
        return wrapped

    plain = {name: getattr(eng, name) for name in fns}
    for name, fn in plain.items():
        setattr(eng, name, keep(name, fn))
    try:
        out = eng.generate([prompt], SamplingParams(max_new_tokens=new_tokens))
    finally:
        for name, fn in plain.items():
            setattr(eng, name, fn)
    return rows, out[0]


def check_engine_against_reference(family, c, params, compute, tolerance,
                                   **options):
    eng = InferenceEngine(c, params, **dict(ENGINE, **options))
    prompt = [int(t) for t in np.random.default_rng(7).integers(1, 512, 21)]
    rows, generated = served_logits(eng, prompt, 6)
    tokens = jnp.asarray([prompt + generated[:-1]])
    want = np.asarray(family.logits(file_config(c, compute), params,
                                    tokens))[0]
    # The last program of the prefill gives position len(prompt) - 1;
    # each decode the next.
    names = [name for name, _ in rows]
    firsts = [i for i, n in enumerate(names) if n != "_decode_fn"]
    sampled = [rows[firsts[-1]][1]] + [r for n, r in rows
                                      if n == "_decode_fn"]
    assert len(sampled) == 6
    worst = max(rel_err(got, want[len(prompt) - 1 + i])
                for i, got in enumerate(sampled))
    assert worst < tolerance, worst
    return eng, names, worst


@pytest.mark.parametrize("k", [2, 8])
def test_engine_prefill_then_decode_matches_reference_in_float32(family, k):
    c = dataclasses.replace(TINY, n_expert_per_tok=k, scan_layers=False)
    _, params = model_and_params(c)
    eng, names, _ = check_engine_against_reference(
        family, c, params, "float32", 1e-4)
    assert names == ["_prefill_fn"] + ["_decode_fn"] * 5
    # 21 prompt tokens and 5 decoded, 2 layers, k experts each; the
    # prefill bucket's 11 padded positions and the decode bucket's padded
    # rows are not counted.
    total = np.asarray(eng.stats()["expert_tokens"])
    assert total.shape == (2, 8) and total.sum() == (21 + 5) * 2 * k
    steps = eng.step_log()["steps"]
    assert steps[0]["moe_assignments"] == 21 * 2 * k
    # A decode's counts come back with its ids, in the step after its
    # dispatch: none in the first decode's, the last in a step that
    # dispatches nothing.
    assert "moe_assignments" not in steps[1] and len(steps) == 1 + 5 + 1
    assert all(s["moe_assignments"] == 2 * k and s["moe_expert_max"] == 1
               and s["moe_experts_touched"] == 2 * k for s in steps[2:])


def test_engine_chunked_prefill_matches_reference_in_float32(family):
    c = dataclasses.replace(TINY, scan_layers=False)
    _, params = model_and_params(c)
    eng, names, _ = check_engine_against_reference(
        family, c, params, "float32", 1e-4, prefill_chunk=8)
    assert names == ["_chunk_fn"] * 3 + ["_decode_fn"] * 5
    assert np.asarray(eng.stats()["expert_tokens"]).sum() == 26 * 2 * 2


def test_engine_in_bf16_is_within_the_tolerance_the_mix_states(family, mix):
    c = dataclasses.replace(TINY, scan_layers=False, dtype=jnp.bfloat16)
    _, params = model_and_params(c)
    tolerance = mix["check"]["tolerance"]
    _, _, worst = check_engine_against_reference(
        family, c, params, "bfloat16", tolerance)
    assert worst > 1e-4  # bf16 is not float32: the comparison can fail


def test_live_rows_counted_over_a_batch_of_three(family):
    c = dataclasses.replace(TINY, scan_layers=False)
    _, params = model_and_params(c)
    eng = InferenceEngine(c, params, **ENGINE)
    prompts = [list(range(1, 10)), list(range(3, 20)), list(range(5, 12))]
    eng.generate(prompts, SamplingParams(max_new_tokens=4))
    # Bucket 4 holds 3 live rows: 3 decode steps of 3 rows, 2 layers, k=2.
    steps = eng.step_log()["steps"]
    decodes = [s for s in steps if s["decodes"] == 3
               and not s.get("prefills")]
    assert len(decodes) == 3 and all(s["bucket"] == 4 for s in decodes)
    # Each one's counts are in the record of the step after it.
    counted = [s for s in steps if not s.get("prefills")
               and "moe_assignments" in s]
    assert counted == steps[-3:] and all(
        s["moe_assignments"] == 3 * 2 * 2 for s in counted)
    total = np.asarray(eng.stats()["expert_tokens"]).sum()
    assert total == (9 + 17 + 7 + 3 * 3) * 2 * 2
    # A dense family's engine has no count and its records no such field.
    dense = dataclasses.replace(llama_mod.LlamaConfig.tiny(), **F32)
    eng = InferenceEngine(dense, llama_mod.init_params(
        llama_mod.Llama(dense), dense, batch=1), **ENGINE)
    eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
    assert eng.stats()["expert_tokens"] is None
    assert "moe_assignments" not in eng.step_log()["steps"][-1]


# ---- the working copy --------------------------------------------------------------


def _converts(text):
    found = re.findall(r"stablehlo\.convert [^\n]*\(tensor<([0-9x]+)xf32>\)"
                       r" -> tensor<\1xbf16>", text)
    return {tuple(int(n) for n in dims.split("x")) for dims in found}


def _lowered(eng, params):
    i32 = jnp.int32
    return (eng._prefill_fn.lower(
        params, eng.cache.k, eng.cache.v, jnp.zeros((1, 16), i32),
        jnp.zeros((16,), i32)).as_text(),
        eng._decode_fn.lower(
            params, eng.cache.k, eng.cache.v, jnp.zeros((4,), i32),
            jnp.zeros((4,), i32), jnp.zeros((4,), i32),
            jnp.zeros((4, 2), i32), jnp.ones((4,), i32)).as_text())


@pytest.mark.parametrize("scan", [True, False])
def test_serving_params_cover_the_expert_matrices(scan):
    c = dataclasses.replace(TINY, dtype=jnp.bfloat16, scan_layers=scan)
    _, params = model_and_params(c)
    working = llama_mod.serving_params(c, params)
    weights = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(working)[0]:
        keys = [k.key for k in path]
        held_f32 = "router" in keys or any("norm" in k for k in keys)
        assert leaf.dtype == (jnp.float32 if held_f32 else jnp.bfloat16), keys
        if not held_f32:
            weights |= {leaf.shape, leaf.shape[1:]}
    assert (8, 64, 32) in weights and (8, 32, 64) in weights
    eng = InferenceEngine(c, params, **ENGINE)
    for text in _lowered(eng, eng._params):
        assert not _converts(text) & weights
    # The same programs on the float32 tree convert them, so the check
    # above can fail.
    for text in _lowered(eng, params):
        assert {(8, 64, 32), (8, 32, 64)} & _converts(text)


def test_a_bf16_tree_is_served_as_the_callers_own_arrays():
    c = dataclasses.replace(TINY, dtype=jnp.bfloat16,
                            param_dtype=jnp.bfloat16, scan_layers=False)
    _, params = model_and_params(c)
    moe = params["layers_0"]["moe"]
    assert moe["wi"].dtype == jnp.bfloat16
    assert moe["router"]["kernel"].dtype == jnp.float32
    assert params["layers_0"]["attn"]["q_proj"]["kernel"].dtype \
        == params["embed_tokens"]["embedding"].dtype == jnp.bfloat16
    eng = InferenceEngine(c, params, **ENGINE)
    for a, b in zip(jax.tree_util.tree_leaves(eng._params),
                    jax.tree_util.tree_leaves(params)):
        assert a is b
    assert eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))


# ---- the registry -----------------------------------------------------------------


# ---- the pools ---------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flat_donated_pools_give_the_4d_pools_bits(dtype, impl):
    """Whole prefill, two chunks and two decode steps of the routed model
    through the engine's programs (pools ``[pages, page_size, kv * d]``,
    given donated) against the llama walks on the parent's 4-D pools:
    logits and all pools, bit for bit (tests/kv_pool_4d.py)."""
    from kv_pool_4d import check_engine_programs

    c = dataclasses.replace(TINY, dtype=dtype, paged_attn=impl)
    eng = InferenceEngine(c, model_and_params(c)[1], page_size=4,
                          num_pages=19, max_num_seqs=4, max_model_len=32,
                          enable_prefix_cache=False)
    assert eng.cache.k[0].shape == (19, 4, c.n_kv_head * c.head_dim)
    assert check_engine_programs(
        eng, list(range(3, 14)), list(range(20, 36))) == [
            "prefill", "chunk@0", "chunk@8", "decode#0", "decode#1"]


def test_the_routed_programs_take_every_pool_donated_and_relay_none():
    from kv_pool_4d import pool_facts

    c = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    eng = InferenceEngine(c, model_and_params(c)[1], num_pages=19, **ENGINE)
    for text in _lowered(eng, eng._params):
        facts = pool_facts(eng, text)
        assert facts["pool_args"] == 2 * c.n_layer == facts["donated_pools"]
        assert facts["donated"] == 2 * c.n_layer and not facts["relaid"]
    # A step returns the count beside pools written in place.
    before = eng.cache.k + eng.cache.v
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_new_tokens=2))
    assert all(a.is_deleted() for a in before)
    assert eng.stats()["kv_pool_bytes"] == sum(
        a.nbytes for a in eng.cache.k + eng.cache.v)
    assert np.asarray(eng.stats()["expert_tokens"]).sum() > 0


def test_llm_deployment_streams_olmoe():
    dep = serve.LLMDeployment._target(model="olmoe", engine_options=ENGINE)
    try:
        prompt = list(range(1, 9))
        streamed = list(dep.generate(prompt, max_new_tokens=5))
        model, params = model_and_params(TINY)
        toks = list(prompt)
        for _ in range(5):
            logits = model.apply({"params": params}, jnp.asarray([toks]))
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert streamed == toks[len(prompt):]
        assert sum(map(sum, dep.stats()["expert_tokens"])) \
            == (8 + 4) * 2 * 2
    finally:
        dep.shutdown()


def test_routed_configs_are_served_by_llamas_description():
    """``MixtralConfig`` and ``OlmoeConfig`` say how they are served with
    no line of their own: llama's entry points, and the count's shape."""
    dense = llama_mod.LlamaConfig.tiny().serving
    assert dense.expert_counts is None
    for c in (TINY, MixtralConfig.tiny()):
        assert "serving" not in vars(type(c))
        served = c.serving
        assert (served.prefill, served.step, served.params) \
            == (dense.prefill, dense.step, dense.params)
        assert (served.kv_heads, served.head_dim) == (c.n_kv_head, c.head_dim)
        assert served.expert_counts == (c.n_layer, c.n_expert)
    _, params = model_and_params(TINY)
    eng = InferenceEngine(TINY, params, **ENGINE)
    assert np.asarray(eng.stats()["expert_tokens"]).shape \
        == TINY.serving.expert_counts


def test_errors_name_the_families_that_exist():
    with pytest.raises(ValueError, match="'olmoe'"):
        serve.LLMDeployment._target(model="moe")
    # The engine knows no family by name: it says what the config lacks.
    with pytest.raises(TypeError, match="object does not say.*`serving`"):
        InferenceEngine(object(), {})
    _, params = model_and_params(TINY)
    with pytest.raises(ValueError, match="one device"):
        InferenceEngine(TINY, params, tp=2, **ENGINE)
