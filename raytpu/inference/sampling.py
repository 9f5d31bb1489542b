"""Token sampling: greedy / temperature / top-k.

Sampling runs on the DEVICE, in the one :func:`sample` the engine jits
beside its three programs: a step's ``[batch, vocab]`` float32 logits
stay where the program left them and ``int32[batch]`` token ids come
back. Every row carries its own request's ``temperature``, ``top_k``
and ``seed``, and a stochastic row draws with the key
``fold_in(key(seed), position)``, where ``position`` is the position of
the row whose logits are sampled (``len(prompt) - 1`` for the first
token, one more for each token after it). A request's random stream
therefore depends only on its own seed and on how far it has come,
never on a shared key or on what else is in the batch: a request that
decodes alone and the same request inside a continuously batched group
produce identical tokens, and one resumed after a preemption draws what
it would have drawn. That is the property the engine's
greedy-matches-reference and batch-invariance tests pin down, and what
makes continuous batching an invisible optimization rather than a
behavior change.

:func:`sample_token` is the same mathematics on the host over one row
with a ``numpy`` generator: the tests' oracle for the distribution. The
engine does not call it.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling and stop configuration.

    ``temperature <= 0`` selects greedy decoding (``top_k`` ignored);
    ``top_k <= 0`` means no top-k truncation. ``seed`` keys the
    request's random stream (its low 32 bits). ``stop_token_ids`` end
    the sequence as soon as one is sampled (the stop token IS emitted,
    matching the reference serve semantics of streaming every token).
    """

    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    stop_token_ids: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        object.__setattr__(self, "stop_token_ids",
                           tuple(int(t) for t in self.stop_token_ids))


def sample(logits, temperature, top_k, seed, position):
    """One token id a row: ``(logits[B, V] f32, temperature[B] f32,
    top_k[B] i32, seed[B] u32, position[B] i32) -> ids[B] i32``, to be
    traced (``jax.numpy``).

    A row with ``temperature <= 0`` takes its first maximum, as
    ``np.argmax`` does. Any other row is scaled by its temperature,
    masked below its own ``top_k``-th value (ties with it kept; ``top_k
    <= 0`` or ``>= V``: no mask) and drawn from the softmax of what is
    left with ``fold_in(key(seed), position)``. Finding the rows'
    thresholds serves no greedy row, so it sits behind a ``cond`` on
    whether the batch holds a stochastic one."""
    import jax

    return _drawn(logits, temperature, top_k, lambda: jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.key(s), p))(
            seed, position))


def _drawn(logits, temperature, top_k, keys):
    """``sample``'s rule with the stochastic rows' keys from ``keys()``
    (called inside the ``cond``: a greedy batch makes none)."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    stochastic = temperature > 0.0

    def draw():
        scaled = _shaped(logits, temperature, top_k)
        drawn = jax.vmap(jax.random.categorical)(keys(), scaled)
        return jnp.where(stochastic, drawn.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(stochastic), draw, lambda: greedy)


def _shaped(logits, temperature, top_k):
    """``logits`` [B, V] as a stochastic row is drawn from: over its
    temperature, ``-inf`` below its own ``top_k``-th value."""
    import jax.numpy as jnp

    vocab = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    kept = jnp.where((top_k > 0) & (top_k < vocab), top_k, vocab)
    order = _ordered_bits(scaled)
    return jnp.where(order >= _kth_largest(order, kept)[:, None],
                     scaled, -jnp.inf)


# What a draw of speculative sampling is for. Its key is the request's
# seed, the position the token would fill, and one of these: the three
# draws at one position are independent, and none depends on the row,
# the step or what shares the batch. (``sample`` folds in no tag and the
# position of the row it reads, one before the one it fills.)
ACCEPT, DRAW, DRAFT = 1, 2, 3


def _keys(seed, position, tag: int):
    import jax

    return jax.vmap(lambda s, p: jax.random.fold_in(jax.random.fold_in(
        jax.random.key(s), p), tag))(seed, position)


def draft_token(logits, temperature, top_k, seed, position):
    """The draft for ``position`` [B] from the drafter's ``logits``
    [B, V]: their first maximum for a greedy row, else a draw from them
    as ``sample`` shapes them (temperature, ``top_k``), keyed ``DRAFT``."""
    return _drawn(logits, temperature, top_k,
                  lambda: _keys(seed, position, DRAFT))


def speculative(logits, draft_logits, draft, temperature, top_k, seed,
                position):
    """One draft a sequence verified (Leviathan et al. 2023; Chen et al.
    2023): ``(logits [B, 2, V] f32, draft_logits [B, V] f32, draft [B]
    i32, temperature, top_k, seed, position [B]) -> (ids [B, 2] i32, kept
    [B] i32)``. ``logits[:, 0]`` are the verifier's for the position the
    draft would fill, ``position``, and ``logits[:, 1]`` for the one after
    it, given the draft; ``draft`` was drawn from ``draft_logits`` by
    :func:`draft_token`. ``p`` and ``q`` are the softmaxes of the two as
    ``sample`` shapes them.

    A greedy row keeps the draft iff it is ``argmax p``, and its ids are
    the two argmaxes. Any other row keeps it with probability ``min(1,
    p(d) / q(d))`` (keyed ``ACCEPT``); kept, the ids are the draft and a
    draw from ``logits[:, 1]`` for ``position + 1``; not kept, a draw
    from ``max(0, p - q)`` renormalised (both keyed ``DRAW``), which
    makes the first id's distribution exactly ``p`` whatever ``q`` is.
    ``kept`` is 2 or 1, and ``ids[:, 1]`` is -1 where it is 1."""
    import jax
    import jax.numpy as jnp

    b, t, vocab = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    stochastic = temperature > 0.0

    def exact():
        return greedy[:, 0], greedy[:, 1], draft == greedy[:, 0]

    def drawn():
        shaped = _shaped(logits.reshape(b * t, vocab),
                         jnp.repeat(temperature, t),
                         jnp.repeat(top_k, t)).reshape(b, t, vocab)
        p = jax.nn.softmax(shaped[:, 0], axis=-1)
        q = jax.nn.softmax(_shaped(draft_logits, temperature, top_k),
                           axis=-1)
        at = draft[:, None]
        u = jax.vmap(jax.random.uniform)(_keys(seed, position, ACCEPT))
        # Strictly: a token the verifier gives no mass is never kept.
        ok = u * jnp.take_along_axis(q, at, 1)[:, 0] \
            < jnp.take_along_axis(p, at, 1)[:, 0]
        other = jax.vmap(jax.random.categorical)(
            _keys(seed, position, DRAW), jnp.log(jnp.maximum(p - q, 0.0)))
        after = jax.vmap(jax.random.categorical)(
            _keys(seed, position + 1, DRAW), shaped[:, 1])
        first = jnp.where(ok, draft, other.astype(jnp.int32))
        return (jnp.where(stochastic, first, greedy[:, 0]),
                jnp.where(stochastic, after.astype(jnp.int32), greedy[:, 1]),
                jnp.where(stochastic, ok, draft == greedy[:, 0]))

    first, second, ok = jax.lax.cond(jnp.any(stochastic), drawn, exact)
    return (jnp.stack([first, jnp.where(ok, second, -1)], axis=1),
            1 + ok.astype(jnp.int32))


def _ordered_bits(x):
    """float32 -> uint32 that compare as the floats do (a negative
    number's bits turned over, a positive one's sign bit set; ``-0.0``
    as ``0.0``)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0.0, 0.0, x).astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(order, k):
    """``(order[B, V] u32, k[B] i32 in 1..V) -> u32[B]``: each row's
    ``k``-th largest value, exactly, ``k`` a traced value of the row's
    own. Bit by bit from the top: a bit stays set while ``k`` values
    still reach the number built so far, 32 passes over the rows. (A
    sort of the rows gives the same value and takes the TPU's compiler
    half a minute a shape.)"""
    import jax
    import jax.numpy as jnp

    def with_bit(i, found):
        tried = found | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        reach = jnp.sum(order >= tried[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, tried, found)

    return jax.lax.fori_loop(0, 32, with_bit,
                             jnp.zeros(order.shape[0], jnp.uint32))


def sample_token(logits: np.ndarray, params: SamplingParams,
                 rng: np.random.Generator) -> int:
    """The tests' oracle: one token id from a ``[vocab]`` fp32 logits
    row, on the host, by the mathematics of :func:`sample`."""
    logits = np.asarray(logits, dtype=np.float64)
    if params.temperature <= 0.0:
        return int(np.argmax(logits))
    scaled = logits / max(params.temperature, 1e-6)
    if params.top_k > 0 and params.top_k < scaled.shape[0]:
        kth = np.partition(scaled, -params.top_k)[-params.top_k]
        scaled = np.where(scaled >= kth, scaled, -np.inf)
    scaled = scaled - np.max(scaled)
    probs = np.exp(scaled)
    probs /= probs.sum()
    return int(rng.choice(probs.shape[0], p=probs))
