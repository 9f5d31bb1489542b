"""The working copy's lookup tables (``gpt2.serving_params``): at a
hidden width that is not a whole number of 128-lane tiles the device
holds ``wte.embedding`` column-major, as the tied head's product reads
it, and a program that gathers rows from it re-lays the whole table in
every call. The working copy then holds the rows a second time, padded
to whole tiles, as a leaf ``lookup`` the forwards gather from. Here, on
the CPU: when the leaf exists, that it changes no bit, and which leaf
each operation of the lowered step reads. That the device's copy is gone
is read from the compiler (``perfbench/tests/aot_v5e_text.py``) and from
the chip (PERF.md section 6, PR 56)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import InferenceEngine, SamplingParams
from raytpu.models import gpt2

WHOLE, ODD = 128, 192  # one lane tile; one and a half
WIDTHS = [WHOLE, ODD]
VOCAB, BLOCK, PAGE = 512, 64, 8


def _family(width, seed=0):
    cfg = gpt2.GPT2Config(vocab_size=VOCAB, block_size=BLOCK, n_layer=2,
                          n_head=2, n_embd=width)
    return cfg, gpt2.init_params(gpt2.GPT2(cfg), cfg, seed=seed, batch=1)


def _cast_as_before(cfg, params):
    """The working copy as it was before it held a lookup leaf."""
    return gpt2.cast_leaves(
        params, cfg.dtype,
        lambda keys: keys[-2] in gpt2._SERVED_IN_COMPUTE_DTYPE)


def _pools(cfg):
    return [jnp.zeros((9, PAGE, cfg.n_embd), cfg.dtype)
            for _ in range(cfg.n_layer)]


def _step_args(cfg, b=3, t=1):
    i32 = jnp.int32
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (b, t)), i32)
    positions = jnp.asarray(np.arange(t)[None] + 3 * np.arange(b)[:, None],
                            i32)
    tables = jnp.asarray(1 + 2 * np.arange(b)[:, None] + np.arange(2)[None],
                         i32)
    dests = tables[:, :1] * PAGE + positions
    return tokens, positions, dests, tables, _pools(cfg), _pools(cfg)


def _prefill_args(cfg, t=16):
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, VOCAB, (1, t)), jnp.int32)
    return tokens, PAGE + jnp.arange(t, dtype=jnp.int32), _pools(cfg), \
        _pools(cfg)


def _same_bits(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("width", WIDTHS)
def test_lookup_leaves_exist_by_the_widths_remainder(width):
    cfg, params = _family(width)
    before = jax.tree_util.tree_map(np.asarray, params)
    working = gpt2.serving_params(cfg, params)
    # The tree given is the caller's: same leaves, same values.
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(before)
    _same_bits(params, before)
    cast = _cast_as_before(cfg, params)
    if width % gpt2.LANES == 0:
        assert jax.tree_util.tree_structure(working) == \
            jax.tree_util.tree_structure(cast)
        assert gpt2.lookup_table(working["wte"]["embedding"]) is None
    else:
        padded = -(-width // gpt2.LANES) * gpt2.LANES
        for name, rows in (("wte", VOCAB), ("wpe", BLOCK)):
            assert sorted(working[name]) == ["embedding", "lookup"]
            lookup = working[name]["lookup"]
            assert lookup.shape == (rows, padded)
            assert lookup.dtype == cfg.dtype
            _same_bits(lookup[:, :width], cast[name]["embedding"])
            assert not np.asarray(lookup[:, width:], np.float32).any()
        # Nothing else differs from the cast.
        for name in ("wte", "wpe"):
            working[name] = {"embedding": working[name]["embedding"]}
    _same_bits(working, cast)


@pytest.mark.parametrize("width", WIDTHS)
def test_abstract_tree_gives_the_same_shapes(width):
    # perfbench's AOT compiles build the engine on shapes alone.
    cfg, params = _family(width)
    working = gpt2.serving_params(cfg, params)
    shapes = gpt2.serving_params(cfg, jax.eval_shape(lambda: params))
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(working)
    for a, b in zip(jax.tree_util.tree_leaves(shapes),
                    jax.tree_util.tree_leaves(working)):
        assert isinstance(a, jax.ShapeDtypeStruct)
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("width", WIDTHS)
def test_prefill_logits_are_the_same_bits(width):
    cfg, params = _family(width)
    fn = jax.jit(functools.partial(gpt2.gpt2_prefill, cfg))
    args = _prefill_args(cfg)
    got = fn(gpt2.serving_params(cfg, params), *args)
    _same_bits(got, fn(_cast_as_before(cfg, params), *args))
    # And from the tree as given (float32 leaves the forward casts).
    _same_bits(got, fn(params, *args))


@pytest.mark.parametrize("t", [1, 2])
@pytest.mark.parametrize("width", WIDTHS)
def test_step_logits_are_the_same_bits(width, t):
    cfg, params = _family(width)
    fn = jax.jit(functools.partial(gpt2.gpt2_step, cfg))
    args = _step_args(cfg, t=t)
    got = fn(gpt2.serving_params(cfg, params), *args)
    assert got[0].shape == (3, t, VOCAB)
    _same_bits(got, fn(_cast_as_before(cfg, params), *args))
    _same_bits(got, fn(params, *args))


def _readers(text, rows, width):
    """The operations of the lowered ``text`` that read the program's
    bf16 ``[rows, width]`` parameter; ``None`` where it has none (jit
    drops a parameter nothing reads)."""
    main = text[text.index("@main("):]
    arg = re.search(rf"(%arg\d+): tensor<{rows}x{width}xbf16>",
                    main[:main.index(") -> ")])
    if arg is None:
        return None
    ops = []
    for line in main.splitlines()[1:]:
        if re.search(re.escape(arg.group(1)) + r"\b", line):
            ops.append(re.search(r"stablehlo\.\w+", line).group(0))
    return ops


@pytest.mark.parametrize("width", WIDTHS)
def test_lowered_step_gathers_from_the_lookup_and_multiplies_by_embedding(
        width):
    cfg, params = _family(width)
    text = jax.jit(functools.partial(gpt2.gpt2_step, cfg)).lower(
        gpt2.serving_params(cfg, params), *_step_args(cfg)).as_text()
    if width % gpt2.LANES == 0:
        # One table for both, as before.
        assert sorted(_readers(text, VOCAB, width)) == [
            "stablehlo.dot_general", "stablehlo.gather"]
        assert _readers(text, BLOCK, width) == ["stablehlo.gather"]
        return
    padded = -(-width // gpt2.LANES) * gpt2.LANES
    assert _readers(text, VOCAB, padded) == ["stablehlo.gather"]
    assert _readers(text, BLOCK, padded) == ["stablehlo.gather"]
    # The head alone reads wte.embedding; nothing reads wpe.embedding.
    assert _readers(text, VOCAB, width) == ["stablehlo.dot_general"]
    assert _readers(text, BLOCK, width) is None


@pytest.mark.parametrize("width", WIDTHS)
def test_engine_counts_the_relaid_bytes(width):
    cfg, params = _family(width)
    eng = InferenceEngine(cfg, params, page_size=PAGE, max_num_seqs=2,
                          max_model_len=32)
    want = 0
    if width % gpt2.LANES:
        want = sum(eng._params[name]["lookup"].nbytes
                   for name in ("wte", "wpe"))
        assert want == (VOCAB + BLOCK) * 256 * 2
    stats = eng.stats()
    assert stats["relaid_param_bytes"] == want
    # They are among the bytes the programs take.
    assert sum(stats["param_bytes"].values()) == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(eng._params))
    # And the engine serves what an engine on the plain cast serves.
    prompts = [[5, 6, 7, 8], [9, 10]]
    sampling = SamplingParams(max_new_tokens=4)
    out = eng.generate(prompts, sampling)
    plain = InferenceEngine(
        cfg, params, page_size=PAGE, max_num_seqs=2, max_model_len=32)
    plain._params = _cast_as_before(cfg, params)
    assert out == plain.generate(prompts, sampling)


@pytest.mark.parametrize("width", WIDTHS)
def test_working_copy_of_new_weights_carries_new_lookups(width):
    cfg, old = _family(width, seed=0)
    _, new = _family(width, seed=1)
    first = cfg.serving.params(cfg, old)
    second = cfg.serving.params(cfg, new)
    assert ("lookup" in second["wte"]) == bool(width % gpt2.LANES)
    for name in ("wte", "wpe"):
        table = second[name].get("lookup", second[name]["embedding"])
        _same_bits(table[:, :width],
                   new[name]["embedding"].astype(cfg.dtype))
        assert not np.array_equal(
            np.asarray(table, np.float32),
            np.asarray(first[name].get("lookup", first[name]["embedding"]),
                       np.float32))
    # An engine on the new weights serves from the new tables.
    eng = InferenceEngine(cfg, new, page_size=PAGE, max_num_seqs=2,
                          max_model_len=32)
    _same_bits(eng._params, second)
