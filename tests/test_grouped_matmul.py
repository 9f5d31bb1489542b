"""The grouped-matmul kernel of the routed-expert layer
(``raytpu/ops/grouped_matmul.py``): against ``jax.lax.ragged_dot`` in
interpret mode at the served families' expert shapes, whole and in
blocks of columns, the rule that says which products take it and in what
blocks, ``MoEFFN`` through it against the benchmark's plain references,
its gradients, and the step record's ``moe_grouped_calls``. All on the
CPU; a time comes only from the chip.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.models.mixtral import (JoyAIConfig, MellumConfig, MixtralConfig,
                                   MoEFFN, OlmoeConfig)
from raytpu.ops import grouped_matmul as gm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = dict(dtype=jnp.float32, attn_impl="reference",
           paged_attn="reference", remat=False)

# (K, N) of one expert's gate and up matrices as published.
MELLUM, OLMOE, JOYAI = (2304, 896), (2048, 1024), (2048, 768)
LFM2, EXAONE = (2048, 1536), (6144, 2048)
MIXTRAL = (4096, 14336)   # 8x7B's, not served: 117 MB an expert matrix


def operands(m, e, k, n, dtype, seed=0):
    a, b, c, d = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = jax.random.normal(a, (m, k), jnp.float32).astype(dtype)
    ws = [(jax.random.normal(key, shape, jnp.float32)
           * shape[1] ** -0.5).astype(dtype)
          for key, shape in ((b, (e, k, n)), (c, (e, k, n)), (d, (e, n, k)))]
    return rows, ws


def both_ways(rows, ws, tokens, **kernel):
    tokens = jnp.asarray(tokens, jnp.int32)
    got = gm._moe_grouped_pallas(rows, tuple(ws), tokens, interpret=True,
                                 **kernel)
    want = gm._ragged(rows, tuple(ws), tokens)
    return (np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)), int(tokens.sum()))


def within_rounding(got, want, live, dtype):
    """The float32 sums are ``ragged_dot``'s; what differs is where a
    bf16 result is rounded (once here, after each product there)."""
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -6
    scale = max(1.0, float(np.abs(want[:live]).max(initial=0.0)))
    assert np.abs(got[:live] - want[:live]).max(initial=0.0) <= tol * scale
    assert not got[live:].any()   # dead rows are written, as zeros


# ---- the kernel against ragged_dot ------------------------------------------


@pytest.mark.parametrize("products", ["gate_up", "down"])
@pytest.mark.parametrize("name,kn,experts,tokens", [
    # a few rows over a few of the experts: a decode step's shape, small
    ("mellum-16", MELLUM, 6, [3, 0, 9, 0, 1, 3]),
    ("olmoe-16", OLMOE, 6, [2, 2, 0, 8, 0, 4]),
    ("joyai-32", JOYAI, 4, [1, 0, 2, 1]),        # 28 dead rows of 32
])
def test_published_widths_at_a_few_rows(name, kn, experts, tokens, products):
    k, n = kn
    m = int(name.split("-")[1])
    rows, (wg, wi, wo) = operands(m, experts, k, n, jnp.bfloat16)
    if products == "gate_up":
        got, want, live = both_ways(rows, [wg, wi], tokens)
    else:
        got, want, live = both_ways(rows[:, :n], [wo], tokens)
    within_rounding(got, want, live, jnp.bfloat16)


@pytest.mark.parametrize("kn", [MELLUM, OLMOE, JOYAI],
                         ids=["mellum", "olmoe", "joyai"])
def test_published_widths_at_256_rows(kn):
    """Two row tiles; the third group straddles them, the last rows are
    dead, two experts are empty."""
    k, n = kn
    tokens = [100, 0, 60, 40, 0, 6]
    rows, (wg, wi, wo) = operands(256, 6, k, n, jnp.bfloat16)
    got, want, live = both_ways(rows, [wg, wi], tokens)
    within_rounding(got, want, live, jnp.bfloat16)
    got, want, live = both_ways(jnp.asarray(got, jnp.bfloat16), [wo], tokens)
    within_rounding(got, want, live, jnp.bfloat16)


CASES = {
    "empty groups": (256, [0, 90, 0, 0, 166, 0, 0, 0]),
    "no live row": (256, [0] * 8),
    "one group holds every row": (256, [0, 0, 0, 256, 0, 0, 0, 0]),
    "a group straddles a row tile": (256, [120, 20, 116, 0, 0, 0, 0, 0]),
    "a group spans three tiles": (384, [100, 200, 0, 84, 0, 0, 0, 0]),
    "groups end on the tiles' edges": (256, [128, 0, 64, 64, 0, 0, 0, 0]),
    "dead rows at the end": (256, [5, 0, 30, 0, 0, 1, 0, 0]),
    "a dead tile": (384, [60, 0, 0, 0, 0, 60, 0, 0]),
    "every expert a row": (16, [2] * 8),
    "rows in one small tile": (48, [10, 0, 7, 0, 0, 20, 0, 3]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_groups(case, dtype):
    m, tokens = CASES[case]
    rows, (wg, wi, wo) = operands(m, len(tokens), 128, 256, dtype, seed=3)
    got, want, live = both_ways(rows, [wg, wi], tokens)
    within_rounding(got, want, live, dtype)
    got, want, live = both_ways(rows, [wg], tokens)
    within_rounding(got, want, live, dtype)
    got, want, live = both_ways(jnp.tile(rows, (1, 2)), [wo], tokens)
    within_rounding(got, want, live, dtype)


@pytest.mark.parametrize("kn,matrices,tn", [
    (MIXTRAL, 2, 1024), (MIXTRAL[::-1], 1, 512),
    (EXAONE, 2, 512), (EXAONE[::-1], 1, 3072),
], ids=["mixtral-up", "mixtral-down", "exaone-up", "exaone-down"])
def test_an_expert_that_does_not_fit_whole_goes_through_in_blocks(
        kn, matrices, tn):
    """Blocks along N, none along K: the most whole lanes that divide N
    and fit the buffers, and the kernel traces at the published width."""
    k, n = kn
    assert gm.takes_kernel(256, k, n, 2, matrices)
    assert gm._block_width(k, n, 2, matrices) == tn
    assert n % tn == 0 and tn % 128 == 0
    assert gm._fits(k, tn, 2, matrices)
    assert not any(n % wider == 0 and gm._fits(k, wider, 2, matrices)
                   for wider in range(tn + 128, n + 1, 128))
    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    out = jax.eval_shape(gm._moe_grouped_pallas, bf16((256, k)),
                         (bf16((8, k, n)),) * matrices,
                         jax.ShapeDtypeStruct((8,), jnp.int32))
    assert out.shape == (256, n) and out.dtype == jnp.bfloat16


@pytest.fixture
def narrow_buffers(monkeypatch):
    """The experts' buffers cut down until ``[128, 512]`` fits in two
    blocks (bf16) or four (float32) and no wider; the kernel is traced
    anew under them, and again without."""
    monkeypatch.setattr(gm, "_EXPERT_BUFFER_BYTES", 2 * 2 * 128 * 256 * 2)
    gm._moe_grouped_pallas.clear_cache()
    yield
    gm._moe_grouped_pallas.clear_cache()


BLOCKED = ["a group straddles a row tile", "dead rows at the end",
           "a dead tile", "no live row", "a group spans three tiles"]


@pytest.mark.parametrize("dtype,tn", [(jnp.float32, 128),
                                      (jnp.bfloat16, 256)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", BLOCKED)
def test_groups_in_blocks(case, dtype, tn, narrow_buffers):
    """The walk of ``test_groups`` over experts cut along N by the rule's
    own reckoning: gate and up at the same columns, the down product's
    one matrix in blocks twice as wide."""
    m, tokens = CASES[case]
    rows, (wg, wi, wo) = operands(m, len(tokens), 128, 512, dtype, seed=5)
    item = jnp.dtype(dtype).itemsize
    assert gm._block_width(128, 512, item, 2) == tn
    assert gm._block_width(128, 512, item, 1) == 2 * tn
    got, want, live = both_ways(rows, [wg], tokens)
    within_rounding(got, want, live, dtype)
    got, want, live = both_ways(rows, [wg, wi], tokens)
    within_rounding(got, want, live, dtype)
    # Whatever the blocks, the float32 sums and the one rounding are the
    # same: no sum crosses a block. (A wider one does not fit here.)
    for width in sorted({128, tn}):
        cut, _, _ = both_ways(rows, [wg, wi], tokens, tn=width)
        np.testing.assert_array_equal(cut, got)


def test_the_kernel_refuses_blocks_it_cannot_take():
    rows, (wg, wi, wo) = operands(32, 4, 128, 512, jnp.bfloat16)
    tokens = jnp.asarray([3, 0, 20, 5], jnp.int32)
    for tn in (64, 192, 384):      # half a lane tile; no divisor of 512
        with pytest.raises(ValueError, match="takes_kernel"):
            gm._moe_grouped_pallas(rows, (wg, wi), tokens, tn=tn,
                                   interpret=True)


def test_a_width_with_no_block_of_whole_lanes_stays_a_ragged_dot(
        monkeypatch):
    """8,192 x 1,000 in float32: too large whole, and no multiple of 128
    divides 1,000."""
    monkeypatch.setattr(gm, "_EXPERT_BUFFER_BYTES", 1 << 20)
    assert gm._block_width(8192, 1000, 4, 2) == 0
    assert not gm.takes_kernel(256, 8192, 1000, 4, 2)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    with pytest.raises(ValueError, match="takes_kernel"):
        jax.eval_shape(gm._moe_grouped_pallas.__wrapped__, f32((256, 8192)),
                       (f32((8, 8192, 1000)),) * 2,
                       jax.ShapeDtypeStruct((8,), jnp.int32))


def test_the_walk_names_each_touched_expert_once():
    tokens = jnp.asarray([100, 0, 60, 0, 0, 40, 6, 0], jnp.int32)
    starts, experts, n, live = map(np.asarray, gm._visits(tokens, 256, 128))
    assert int(n) == 6 and int(live[0]) == 206
    assert starts[:7].tolist() == [0, 100, 128, 160, 200, 206, 256]
    # The straddling group's two visits and the dead rows' name the block
    # that is there already: four fetches for four touched experts.
    assert experts[:6].tolist() == [0, 2, 2, 5, 6, 6]
    assert (starts[6:] == 256).all() and len(experts) == 2 + 8


# ---- which shapes take the kernel -------------------------------------------


@pytest.mark.parametrize("what,rows,kn,takes", [
    ("Mellum decode, 32 x 8", 256, MELLUM, True),
    ("OLMoE decode, 16 x 8", 128, OLMOE, True),
    ("JoyAI decode, 32 x 8 over a share of 32", 256, JOYAI, True),
    ("the check's decode, 2 x 8", 16, MELLUM, True),
    ("OLMoE prefill 256", 2048, OLMOE, True),
    ("Mellum prefill 1280", 10240, MELLUM, True),
    ("Mellum chunk 2048", 16384, MELLUM, True),
    ("JoyAI chunk 2048", 16384, JOYAI, True),
    ("OLMoE training, 16 x 1024 x 8", 131072, OLMOE, True),
    ("LFM2 decode, 64 x 4", 256, LFM2, True),
    ("LFM2 chunk 2048", 8192, LFM2, True),
    ("K-EXAONE verify, 16 x 2 x 8: in blocks", 256, EXAONE, True),
    ("K-EXAONE chunk 2048: in blocks", 16384, EXAONE, True),
    ("Mixtral 8x7B, 8 x 2048 x 2: in blocks", 32768, MIXTRAL, True),
    ("Mixtral 8x7B's decode: in blocks", 256, MIXTRAL, True),
    ("one sequence's decode: 8 rows, half a bf16 tile", 8, OLMOE, False),
    ("rows that fill no whole tile", 200, OLMOE, False),
    ("no row", 0, OLMOE, False),
])
def test_shape_rule(what, rows, kn, takes):
    """Both orientations as the layer has them in bf16: gate and up
    ``[k, n]`` in one pass, down ``[n, k]`` alone."""
    k, n = kn
    assert gm.takes_kernel(rows, k, n, 2, 2) is takes, what
    assert gm.takes_kernel(rows, n, k, 2, 1) is takes, what


@pytest.mark.parametrize("kn,itemsize,matrices,tn", [
    # LFM2's gate and up: 25.2 MB in flight as bf16, 50.3 MB as float32.
    (LFM2, 2, 2, 1536), (LFM2, 4, 2, 768),
    (LFM2[::-1], 2, 1, 2048), (LFM2[::-1], 4, 1, 2048),
    (OLMOE, 2, 2, 1024), (OLMOE, 4, 2, 1024),
    (MELLUM, 2, 2, 896), (JOYAI, 4, 2, 768),
    (EXAONE, 4, 2, 256), (EXAONE[::-1], 4, 1, 2048),
])
def test_the_item_size_and_the_matrices_streamed_decide_the_block(
        kn, itemsize, matrices, tn):
    k, n = kn
    assert gm._block_width(k, n, itemsize, matrices) == tn
    assert gm.takes_kernel(256, k, n, itemsize, matrices)


def test_the_rule_knows_no_model():
    import inspect

    assert list(inspect.signature(gm.takes_kernel).parameters) == [
        "rows", "k", "n", "itemsize", "matrices"]
    # No default stands in for an operand: the rule and the kernel's own
    # guard read the same two numbers off the matrices.
    assert all(p.default is p.empty for p in
               inspect.signature(gm.takes_kernel).parameters.values())


def test_off_the_tpu_the_products_are_ragged_dots():
    rows, (wg, wi, wo) = operands(32, 4, 128, 256, jnp.float32)
    tokens = jnp.asarray([3, 0, 20, 5], jnp.int32)
    assert gm.takes_kernel(32, 128, 256, 4, 2)
    # Both are traced, and only the backend's own is lowered.
    traced = str(jax.make_jaxpr(gm.grouped_swiglu)(rows, wg, wi, tokens))
    assert "pallas_call" in traced and "ragged_dot" in traced
    text = jax.jit(gm.grouped_swiglu).lower(rows, wg, wi, tokens).as_text()
    assert "tpu_custom_call" not in text
    np.testing.assert_array_equal(
        gm.grouped_swiglu(rows, wg, wi, tokens),
        jax.nn.silu(jax.lax.ragged_dot(rows, wg, tokens))
        * jax.lax.ragged_dot(rows, wi, tokens))
    np.testing.assert_array_equal(gm.grouped_matmul(rows, wg, tokens),
                                  jax.lax.ragged_dot(rows, wg, tokens))


def layers_traced(n_layers, rows=32, **note):
    """The kernel's products ``kernel_calls`` notes of a program of
    ``n_layers`` routed layers' products, and the program as traced."""
    x, (wg, wi, wo) = operands(rows, 4, 128, 256, jnp.float32)
    tokens = jnp.asarray([3, 0, rows - 12, 5], jnp.int32)

    def program(x):
        for _ in range(n_layers):
            x = gm.grouped_matmul(gm.grouped_swiglu(x, wg, wi, tokens), wo,
                                  tokens)
        return x

    with gm.kernel_calls(**note) as calls:
        traced = str(jax.make_jaxpr(program)(x))
    return calls[0], traced


@pytest.mark.parametrize("platform,calls", [("tpu", 6), ("cpu", 0),
                                            ("gpu", 0)])
def test_the_note_counts_what_a_program_lowered_for_a_tpu_sends(
        platform, calls):
    got, traced = layers_traced(3, platform=platform)
    assert got == calls and "pallas_call" in traced


def test_the_note_counts_no_product_that_stays_a_ragged_dot():
    # Rows that fill no tile; a program sharded over a mesh.
    got, traced = layers_traced(2, rows=24, platform="tpu")
    assert got == 0 and "pallas_call" not in traced
    with jax.set_mesh(jax.make_mesh(
            (2,), ("ep",), axis_types=(jax.sharding.AxisType.Auto,))):
        got, traced = layers_traced(2, platform="tpu")
    assert got == 0 and "pallas_call" not in traced
    assert layers_traced(2, platform="tpu")[0] == 4


def test_notes_nest_and_are_a_threads_own():
    import threading

    seen = {}

    def other():
        seen["other"] = layers_traced(1, platform="tpu")[0]

    with gm.kernel_calls("tpu") as outer:
        inner, _ = layers_traced(2, platform="tpu")
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert (inner, outer[0], seen["other"]) == (4, 4, 2)
    assert not gm._notes.open


# ---- MoEFFN through the kernel ----------------------------------------------


@pytest.fixture
def through_the_kernel(monkeypatch):
    """``MoEFFN`` as a TPU program has it, the kernel interpreted: the
    branch ``platform_dependent`` would lower for a TPU is taken here."""
    calls = []

    def kernel(rows, ws, tokens):
        calls.append((rows.shape, len(ws)))
        return gm._moe_grouped_pallas(rows, ws, tokens, interpret=True)

    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: kernel(*args))
    return calls


def family_of(name):
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families", name)


def reference_config(c: MixtralConfig):
    """What a family's ``_experts`` reads of its configuration file."""
    first, count = c.experts_held or (0, c.n_expert)
    return {"num_experts_per_tok": c.n_expert_per_tok,
            "norm_topk_prob": c.norm_topk_prob,
            "routed_scaling_factor": c.routed_scale,
            "experts_held": [first, count], "n_routed_experts": count,
            "published_n_routed_experts": c.n_expert}


TINIES = {
    "olmoe": dataclasses.replace(OlmoeConfig.tiny(), **F32),
    "mellum": dataclasses.replace(MellumConfig.tiny(), **F32),
    "joyai": dataclasses.replace(JoyAIConfig.tiny(), **F32),
    "joyai-held": dataclasses.replace(JoyAIConfig.tiny(), **F32,
                                      experts_held=(4, 8)),
}


@pytest.mark.parametrize("name", sorted(TINIES))
def test_layer_through_the_kernel_is_the_plain_reference(
        name, through_the_kernel):
    c = TINIES[name]
    family = family_of(name.split("-")[0])
    x = jnp.asarray(np.random.default_rng(7).standard_normal(
        (24, c.n_embd)), jnp.float32)
    live = jnp.arange(24) < 21           # three rows of padding
    moe = MoEFFN(c).init(jax.random.PRNGKey(2), x)["params"]
    through_the_kernel.clear()
    got, counts = MoEFFN(c).apply({"params": moe}, x, live)
    # Gate and up in one call, down in another, both through the kernel.
    rows = 24 * c.n_expert_per_tok
    assert through_the_kernel == [((rows, c.n_embd), 2),
                                  ((rows, c.n_inter), 1)]
    with jax.default_matmul_precision("highest"):
        want = family._experts(reference_config(c), moe, x)
        if c.n_shared:
            # The shared expert runs on padding too; the routed part not.
            shared = family._swiglu(moe["shared"], x)
            want = jnp.where(live[:, None], want - shared, 0.0) + shared
        else:
            want = jnp.where(live[:, None], want, 0.0)
    np.testing.assert_allclose(got, want, atol=3e-5)
    held = c.experts_held[1] if c.experts_held else c.n_expert
    assert counts.shape == (held,)
    assert int(counts.sum()) <= 21 * c.n_expert_per_tok
    if not c.experts_held:
        assert int(counts.sum()) == 21 * c.n_expert_per_tok


def test_layer_through_the_kernel_is_the_layer_through_ragged_dot(
        through_the_kernel):
    c = TINIES["mellum"]
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 32, c.n_embd)), jnp.float32)
    moe = MoEFFN(c).init(jax.random.PRNGKey(0), x)["params"]
    got, counts = MoEFFN(c).apply({"params": moe}, x)
    assert through_the_kernel
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gm, "takes_kernel", lambda *shape: False)
        want, want_counts = MoEFFN(c).apply({"params": moe}, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(counts, want_counts)


def plain_layer(c, p, x):
    """The layer without the op: every expert on every token."""
    probs = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
    topw, topi = jax.lax.top_k(probs, c.n_expert_per_tok)
    if c.norm_topk_prob:
        topw = topw / topw.sum(-1, keepdims=True)
    w = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topw)
    every = jnp.einsum(
        "ten,end->ted",
        jax.nn.silu(jnp.einsum("td,edn->ten", x, p["wg"]))
        * jnp.einsum("td,edn->ten", x, p["wi"]), p["wo"])
    return jnp.einsum("te,ted->td", w, every)


@pytest.mark.parametrize("path", ["ragged_dot", "kernel"])
def test_gradients_of_the_layer_are_unchanged(path, request):
    """The op's backward pass is ``ragged_dot``'s whatever multiplied on
    the way forward: the layer's gradients are the plain layer's."""
    if path == "kernel":
        assert request.getfixturevalue("through_the_kernel") == []
    c = TINIES["olmoe"]
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (16, c.n_embd)), jnp.float32)
    p = MoEFFN(c).init(jax.random.PRNGKey(4), x)["params"]

    def loss(layer, p, x):
        return jnp.sum(jnp.square(layer(p, x)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(functools.partial(
            loss, lambda p, x: MoEFFN(c).apply({"params": p}, x)[0]),
            argnums=(0, 1))(p, x)
        want = jax.grad(functools.partial(
            loss, functools.partial(plain_layer, c)), argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


# ---- the step record --------------------------------------------------------


def engine_records(c, model):
    from raytpu.inference import InferenceEngine
    from raytpu.inference.sampling import SamplingParams

    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(c, params, page_size=8, max_num_seqs=2,
                          max_model_len=64)
    eng.add_request("a", list(range(1, 12)),
                    SamplingParams(max_new_tokens=3))
    while eng.has_unfinished():
        eng.step()
    return eng, eng.step_log()["steps"]


def test_a_routed_engines_records_count_the_grouped_calls():
    from raytpu.models.mixtral import Mixtral

    c = TINIES["olmoe"]
    eng, steps = engine_records(c, Mixtral(c))
    routed = [s for s in steps if "moe_assignments" in s]
    assert routed and all("moe_grouped_calls" in s for s in routed)
    # Traced for the CPU: every product is a ragged_dot, in a prefill and
    # in a decode.
    assert {s["moe_grouped_calls"] for s in routed} == {0}
    assert {name for name, _ in eng._grouped_calls} == {"_prefill", "_decode"}
    # The same engine with its pools on a TPU, in programs not traced
    # yet: two calls a routed layer where the rows fill a tile (a prompt
    # of 32 tokens x 2 experts), none in a decode of two rows.
    eng._devices = ["tpu:0"]
    eng.add_request("b", list(range(30, 49)))
    eng.step()
    last = eng.step_log()["steps"][-1]
    assert last["prefills"] and last["moe_grouped_calls"] == 2 * c.n_layer
    assert eng._grouped_calls["_prefill", 32] == 2 * c.n_layer


def test_a_chunks_record_counts_the_grouped_calls_too():
    from raytpu.inference import InferenceEngine
    from raytpu.models.mixtral import Mixtral

    c = TINIES["olmoe"]
    params = Mixtral(c).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(c, params, page_size=8, max_num_seqs=2,
                          max_model_len=64, prefill_chunk=16)
    eng._devices = ["tpu:0"]
    eng.add_request("a", list(range(1, 40)))
    eng.step()
    first = eng.step_log()["steps"][-1]
    assert {name for name, _ in eng._grouped_calls} == {"_chunk"}
    assert first["moe_grouped_calls"] == 2 * c.n_layer


def abstract_engine(c, seqs):
    """An engine over a tree of shapes: its programs can be traced,
    nothing of the model is held. Its pools are told to lie on a TPU."""
    from raytpu.inference import InferenceEngine
    from raytpu.models.mixtral import Mixtral

    given = jax.eval_shape(
        lambda: Mixtral(c).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))["params"])
    eng = InferenceEngine(c, given, page_size=8, max_num_seqs=seqs,
                          max_model_len=64)
    eng._devices = ["tpu:0"]
    return eng


def shapes_of(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def ids(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def lfm2_decode(c, seqs):
    """LFM2's decode program of ``seqs`` sequences, traced: the products
    noted, and the layers that are routed."""
    eng = abstract_engine(c, seqs)
    pools, states = shapes_of(eng.cache.k), shapes_of(eng.cache.state)
    jax.eval_shape(eng._decode_fn, eng._params, pools, pools, states,
                   ids(seqs), ids(seqs), ids(seqs), ids(seqs),
                   ids(seqs, 8), ids(seqs))
    (_, calls), = eng._grouped_calls.items()
    return calls, c.n_layer - c.first_dense


def exaone_verify(c, seqs):
    """K-EXAONE's verify program (two positions a sequence) and its
    module's draft program: the products noted in each, and the routed
    layers of each."""
    eng = abstract_engine(c, seqs)
    pools, state = shapes_of(eng.cache.k), shapes_of(eng._draft_state)
    pair = (ids(seqs, 2),) * 2
    tables = (ids(seqs, 8),) * 2
    jax.eval_shape(eng._decode_fn, eng._params, pools, pools, state,
                   ids(seqs), ids(seqs), ids(seqs), pair, tables)
    rows = (jax.ShapeDtypeStruct((seqs,), jnp.float32), ids(seqs),
            jax.ShapeDtypeStruct((seqs,), jnp.uint32))
    jax.eval_shape(eng._draft_fn, eng._params, pools, pools, state,
                   ids(seqs),
                   jax.ShapeDtypeStruct((seqs, 2, c.n_embd), c.dtype),
                   ids(seqs, 2), ids(seqs), ids(seqs), pair, tables, *rows)
    calls = {name: n for (name, _), n in eng._grouped_calls.items()}
    return (calls["_decode"] + calls["_draft"],
            c.n_layer - c.first_dense + c.mtp_layers)


# A family's program of its published expert widths at the cell's batch
# (LFM2: 64 streams x 4 experts; K-EXAONE: 16 x 2 positions x 8), cut in
# depth and vocabulary, which no product of the routed layer sees.
PUBLISHED = {
    "lfm2": ("Lfm2MoeConfig", 4, LFM2, lfm2_decode, 64, 4),
    "exaone": ("ExaoneMoeConfig", 3, EXAONE, exaone_verify, 16, 16),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("seqs", ["the cell's batch", 2])
@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_programs_at_published_expert_widths_note_what_the_rule_says(
        name, seqs, dtype):
    from raytpu.models import mixtral

    cls, layers, kn, program, batch, rows_a_seq = PUBLISHED[name]
    c = getattr(mixtral, cls)(
        n_layer=layers, vocab_size=512, block_size=256, dtype=dtype,
        param_dtype=dtype, attn_impl="reference", paged_attn="reference",
        remat=False)
    assert (c.n_embd, c.n_inter) == kn
    seqs = batch if seqs == "the cell's batch" else seqs
    calls, routed = program(c, seqs)
    rows, item = seqs * rows_a_seq, jnp.dtype(dtype).itemsize
    says = (gm.takes_kernel(rows, c.n_embd, c.n_inter, item, 2)
            + gm.takes_kernel(rows, c.n_inter, c.n_embd, item, 1))
    assert routed >= 2 and calls == says * routed
    # The cell's batch fills two row tiles and both products are the
    # kernel's in either type (float32 in narrower blocks); two
    # sequences' rows fill no tile of 16 in LFM2 and two in K-EXAONE.
    assert says == (2 if rows % 16 == 0 else 0)
    assert (says == 2) == (seqs == batch or name == "exaone")


def test_a_dense_engines_records_do_not():
    from raytpu.models.llama import Llama, LlamaConfig

    c = dataclasses.replace(LlamaConfig.tiny(), **F32)
    _, steps = engine_records(c, Llama(c))
    assert steps and not any("moe_grouped_calls" in s for s in steps)
