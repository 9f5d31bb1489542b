"""A module's one paged method, ``step`` over the rows ``x [B * T, E]`` of
``[B, T]`` positions, against the dense form of the same module at the
three shapes a program gives it: a prompt's chunk ``(1, T)``, a decode
step ``(B, 1)`` and a step that verifies a draft ``(B, 2)``. The dense
form is the module's own ``prefill`` of the whole sequences (flash
attention's reference, no pool read), float32; where the module reads a
pool, under both implementations a CPU has (``paged_attn``: the kernel
interpreted, the reference gather).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.models.gpt2 import CausalSelfAttention, GPT2Config
from raytpu.models.llama import FULL, WINDOW, LlamaAttention, LlamaConfig
from raytpu.models.mixtral import JoyAIConfig
from raytpu.models.mla import LatentAttention
from raytpu.models.short_conv import ShortConv

PAGE, LENGTH, SEQS = 4, 12, 2
# A sequence's pages, in the order of its positions; page 0 is scratch.
TABLES = np.array([[3, 5, 1, 0], [2, 6, 4, 0]], np.int32)
# What a program hands ``step``: the sequences it takes and each one's
# first position (its ``T`` rows follow one another from there).
SHAPES = {"chunk_1xT": ([0], [4], 8), "decode_Bx1": ([0, 1], [9, 5], 1),
          "verify_Bx2": ([0, 1], [9, 5], 2)}


def _f32(config, impl):
    return dataclasses.replace(config, dtype=jnp.float32,
                               attn_impl="reference", paged_attn=impl)


def _llama(impl):
    return _f32(LlamaConfig.tiny(), impl)


def _window(impl):
    return dataclasses.replace(_llama(impl), window=6,
                               layer_types=(FULL, WINDOW))


MODULES = {
    "gpt2": lambda impl: CausalSelfAttention(
        _f32(GPT2Config.tiny(), impl)),
    "llama_full": lambda impl: LlamaAttention(_llama(impl), FULL),
    "llama_window": lambda impl: LlamaAttention(_window(impl), WINDOW),
    "latent": lambda impl: LatentAttention(_f32(JoyAIConfig.tiny(), impl)),
}


def _rows(shape):
    """``(sequences [B], positions [B, T])`` of a shape's rows."""
    seqs, starts, t = SHAPES[shape]
    return np.asarray(seqs), (np.asarray(starts)[:, None]
                              + np.arange(t)).astype(np.int32)


def _slots(seqs, positions):
    """Flat pool slots of ``positions`` [B, T] of ``seqs`` [B]."""
    return (TABLES[seqs[:, None], positions // PAGE] * PAGE
            + positions % PAGE).astype(np.int32)


@pytest.mark.parametrize("impl", ["interpret", "reference"])
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("name", list(MODULES))
def test_attention_step_is_the_dense_form(name, shape, impl):
    module = MODULES[name](impl)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(
        (SEQS, LENGTH, module.config.n_embd)), jnp.float32)
    params = module.init(jax.random.PRNGKey(0), x)["params"]
    # The dense form: every position's output and its pool rows (K and
    # V, or the one row of a latent layer).
    want, *held = module.apply({"params": params}, x, method="prefill")
    held = [np.asarray(h).reshape(SEQS, LENGTH, -1) for h in held]
    seqs, positions = _rows(shape)
    dests = _slots(seqs, positions)
    # Pools of garbage but for what the sequences hold before the step:
    # the rows of the positions left of its first.
    pools = []
    for rows in held:
        pool = rng.standard_normal(
            (1 + SEQS * 3, PAGE, rows.shape[-1])).astype(np.float32)
        for s, first in zip(seqs, positions[:, 0]):
            before = np.arange(first)
            pool.reshape(-1, rows.shape[-1])[
                _slots(np.asarray([s]), before[None])[0]] = rows[s, before]
        pools.append(jnp.asarray(pool))
    width = module.config.n_embd
    got, *pools = module.apply(
        {"params": params}, x[seqs[:, None], positions].reshape(-1, width),
        *pools, jnp.asarray(dests), jnp.asarray(TABLES[seqs]),
        jnp.asarray(positions), method="step")
    assert got.shape == (positions.size, width)
    np.testing.assert_allclose(
        got.reshape(*positions.shape, width),
        np.asarray(want)[seqs[:, None], positions], atol=3e-5)
    # The step wrote its rows where it was told, as the dense form has them.
    for pool, rows in zip(pools, held):
        np.testing.assert_allclose(
            np.asarray(pool).reshape(-1, rows.shape[-1])[dests],
            rows[seqs[:, None], positions], atol=1e-6)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_short_conv_step_is_the_dense_form(shape):
    """The convolution's rows behind the state at a sequence's seat, the
    last of them padding where the shape has room for one: the outputs of
    the live rows are the training forward's, and the state left is the
    convolution's input at the last two live positions."""
    config = dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, layer_types=("conv", FULL))
    module = ShortConv(config)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((SEQS, LENGTH, config.n_embd)),
                    jnp.float32)
    params = module.init(jax.random.PRNGKey(0), x)["params"]
    want = np.asarray(module.apply({"params": params}, x))
    inputs = np.asarray(
        module.apply({"params": params}, x, method="_gates")[0])
    seqs, positions = _rows(shape)
    t = positions.shape[1]
    # Sequence 0's last row is padding, but in a decode step of one row.
    live = np.ones(positions.shape, bool)
    live[0, -1] = t == 1
    seats = np.asarray([2, 1])[seqs]  # row 0 is the scratch seat
    taps = config.conv_taps - 1
    state = rng.standard_normal((4, taps, config.n_embd)).astype(np.float32)
    for seat, s, first in zip(seats, seqs, positions[:, 0]):
        state[seat] = inputs[s, first - taps:first]
    got, state = module.apply(
        {"params": params},
        x[seqs[:, None], positions].reshape(-1, config.n_embd),
        jnp.asarray(state), jnp.asarray(seats), jnp.asarray(live),
        jnp.asarray(positions[:, 0] == 0), method="step")
    assert got.shape == (positions.size, config.n_embd)
    np.testing.assert_allclose(
        np.asarray(got).reshape(*positions.shape, -1)[live],
        want[seqs[:, None], positions][live], atol=2e-5)
    for seat, s, first, n in zip(seats, seqs, positions[:, 0],
                                 live.sum(axis=1)):
        np.testing.assert_allclose(
            np.asarray(state)[seat], inputs[s, first + n - taps:first + n],
            atol=1e-6)
