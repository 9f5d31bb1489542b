"""The delta-rule recurrence of a KDA layer (Kimi Delta Attention,
arXiv:2510.26692) in the two forms a served model needs.

A head keeps a matrix state ``S`` [d_k, d_v], float32, zero before a
sequence's first position. A position brings ``q``, ``k`` [d_k], ``v``
[d_v], a log-decay a channel of the key ``g`` [d_k] (``<= 0``) and one
``beta`` in (0, 1):

    S' = Diag(exp g) S          (the decay, before the update)
    S  = S' + beta k (v - S'^T k)^T
    o  = S^T q

Every sum is float32. What a padding row brings is ``g = 0`` and ``beta =
0``: it decays nothing and writes nothing, so the state after a bucket's
last row is the state after its last live one.

**A prompt's rows** (:func:`kda_chunked`) go ``BLOCK`` positions at a
time, not one by one. With ``G_r`` the running sum of ``g`` inside a block
and ``u_r = beta_r (v_r - S'_r^T k_r)`` the row's *pseudo-value*, the
recurrence unrolls to ``S_r = Diag(e^{G_r}) S_0 + sum_{i<=r} (k_i e^{G_r -
G_i}) u_i^T``, so the ``u`` of a block solve one unit lower-triangular
system, ``(I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S_0)`` with
``A[r, i] = (k_r e^{G_r}) . (k_i e^{-G_i})`` for ``i < r``, and ``O = (Q
e^G) S_0 + B U`` with ``B[r, i] = (q_r e^{G_r}) . (k_i e^{-G_i})`` for ``i
<= r``. Everything that does not read the state (``A``, ``B``, the
system's inverse) is computed for all blocks at once; one ``lax.scan``
over the blocks carries the state through four small products. The
factorised products hold ``e^{-G_i}``, which is why a block is 16 rows: the
published gate is bounded below (``kda_lower_bound`` -5), so ``|G| <= 80``
inside a block and ``e^{80}`` is a float32 (``log`` of its largest is
88.7): nothing overflows. ``e^{-80}`` is a float32 too, but only three
decimal orders over the least normal one, and a small component of ``q``
or ``k`` times it is flushed to zero; so the two factors are taken about
the block's middle row ``M``, ``(k_r e^{G_r - M}) . (k_i e^{M - G_i})``,
and stay inside ``e^{+-40}``. A wider block would have to take the direct
``[C, C, d]`` form on its diagonal, and does not exist here.

**A decode row** (:func:`kda_decode`) is one pass over the state: each
sequence's ``[H, d_k, d_v]`` block is read once and written once (decay,
the contraction with ``k``, the rank-one update, the contraction with
``q``). On one TPU that is ``_kda_state_pallas`` (its trace events carry
the name): grid ``(sequence, block of heads)``, the sequences' seats as
scalar prefetch as :mod:`raytpu.ops.mla_attention` takes its pages, the
state array aliased in and out so that the seats no row names keep what
they hold. The vectors that scale the state's rows (``e^g``, ``k``, ``beta
k``, ``q``) come to it as they lie, a head a row, and are turned in the
kernel, 16 heads at a time, so that a head's vector is a lane slice of a
``[d_k, heads]`` tile (laid out ``[.., d_k, heads]`` in HBM the same
vectors took 0.12 ms a layer to write, on tiles an eighth full).
Elsewhere, and under the CPU tests, :func:`kda_decode_reference` is the
same arithmetic in ``jax.numpy`` over the gathered rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raytpu.ops.paged_attention import resolve_paged_impl

__all__ = ["BLOCK", "kda_chunked", "kda_decode", "kda_decode_reference"]

# Positions of one block of the chunked form (see the module docstring).
BLOCK = 16
# Heads of one grid step of the decode kernel: 16 x [128, 128] float32 is
# 1 MiB in and 1 MiB out, each in two buffers.
_HEAD_BLOCK = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(n):
    """``(I + n)^-1`` of strictly lower-triangular ``n`` [..., C, C], C a
    power of two: ``n`` is nilpotent, so the inverse is the finite series
    ``sum_j (-n)^j = (I - n)(I + n^2)(I + n^4) ...``, ``log2 C`` products."""
    c = n.shape[-1]
    inv = jnp.eye(c, dtype=n.dtype) - n
    power = -n
    for _ in range(c.bit_length() - 2):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = inv + jnp.matmul(inv, power, precision=_HIGHEST)
    return inv


def kda_chunked(q, k, v, g, beta, state, *, block: int = BLOCK):
    """``T`` consecutive positions a sequence behind ``state``.

    Args:
      q, k, g: ``[B, T, H, d_k]``; v: ``[B, T, H, d_v]``; beta: ``[B, T,
        H]``. ``g`` no lower than ``-88 / block`` a position. A padding
        row: ``g = 0``, ``beta = 0``.
      state: ``[B, H, d_k, d_v]`` float32, the state before the first row.

    Returns ``(o [B, T, H, d_v] float32, the state after the last row)``.
    """
    b, t, h, _ = q.shape
    f32 = jnp.float32
    pad = -t % block
    n = (t + pad) // block

    def blocks(x):  # [B, T, H, ...] -> [n, B, H, block, ...]
        x = jnp.pad(x.astype(f32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, block) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g = blocks(q), blocks(k), blocks(v), blocks(g)
    beta = blocks(beta)                                  # [n, B, H, C]
    run = jnp.cumsum(g, axis=-2)                         # G_r
    total = run[..., -1:, :]                             # G_C
    k_in = k * jnp.exp(run)                              # k_r e^{G_r}
    q_in = q * jnp.exp(run)
    k_end = k * jnp.exp(total - run)                     # k_i e^{G_C - G_i}
    # The factors of A and B, about the block's middle row.
    middle = run[..., block // 2 - 1:block // 2, :]
    k_out = k * jnp.exp(middle - run)                    # k_i e^{M - G_i}
    rows = jnp.arange(block)
    below = rows[:, None] > rows[None, :]
    a = jnp.einsum("...rc,...ic->...ri", k * jnp.exp(run - middle), k_out,
                   precision=_HIGHEST)
    within = jnp.einsum("...rc,...ic->...ri", q * jnp.exp(run - middle),
                        k_out, precision=_HIGHEST)
    within = jnp.where(below | (rows[:, None] == rows[None, :]), within, 0.0)
    solve = _unit_lower_inverse(
        jnp.where(below, a, 0.0) * beta[..., :, None]) * beta[..., None, :]

    def one(s, x):
        k_in, q_in, k_end, v, solve, within, decay = x
        u = jnp.matmul(solve, v - jnp.matmul(k_in, s, precision=_HIGHEST),
                       precision=_HIGHEST)
        o = jnp.matmul(q_in, s, precision=_HIGHEST) \
            + jnp.matmul(within, u, precision=_HIGHEST)
        s = s * jnp.swapaxes(decay, -1, -2) + jnp.einsum(
            "...ik,...iv->...kv", k_end, u, precision=_HIGHEST)
        return s, o

    state, o = jax.lax.scan(
        one, state.astype(f32),
        (k_in, q_in, k_end, v, solve, within, jnp.exp(total)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)        # [B, n, C, H, d_v]
    return o.reshape(b, n * block, h, -1)[:, :t], state


def kda_decode_reference(q, k, v, g, beta, state, seats, first):
    """One position a sequence against the state array, in ``jax.numpy``.

    Args:
      q, k, g: ``[B, H, d_k]``; v: ``[B, H, d_v]``; beta: ``[B, H]``.
      state: ``[seats + 1, H, d_k, d_v]`` float32; seats: int32 ``[B]``,
        each row's seat (0, the scratch row, for a padding row).
      first: bool ``[B]``: the row stands at position 0 and starts from
        zeros whatever its seat holds.

    Returns ``(o [B, H, d_v] float32, state with the rows' seats written)``.
    """
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    s = jnp.where(first[:, None, None, None], 0.0, state[seats])
    s = s * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    o = jnp.sum(s * q[..., None], axis=-2)
    return o, state.at[seats].set(s.astype(state.dtype))


def _kda_state_kernel(seats_ref, first_ref, vecs_ref, rows_ref, s_ref,
                      o_ref, s_out_ref):
    """One grid step: ``heads`` heads of one sequence. ``vecs_ref`` [1, 4,
    1, heads, d_k] holds ``e^g``, ``k``, ``beta k`` and ``q``, a head a
    row; each is turned once, so that a head's vector is a column that
    scales the rows of its ``[d_k, d_v]`` state. ``rows_ref`` [1, heads,
    d_v] holds ``beta v``."""
    del seats_ref  # the index maps read it
    keep = jnp.where(first_ref[pl.program_id(0)] != 0, 0.0, 1.0)
    decay, key, beta_key, query = (vecs_ref[0, j, 0].T for j in range(4))
    decay = decay * keep
    for h in range(s_ref.shape[1]):
        s = s_ref[0, h] * decay[:, h:h + 1]
        u = rows_ref[0, h:h + 1] - jnp.sum(s * beta_key[:, h:h + 1], axis=0,
                                           keepdims=True)
        s = s + key[:, h:h + 1] * u
        s_out_ref[0, h] = s
        o_ref[0, h:h + 1] = jnp.sum(s * query[:, h:h + 1], axis=0,
                                    keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_state_pallas(q, k, v, g, beta, state, seats, first, *, interpret):
    b, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    heads = _HEAD_BLOCK if h % _HEAD_BLOCK == 0 else h
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    vecs = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=1)
    vecs = vecs.reshape(b, 4, h // heads, heads, dk)
    rows = beta[..., None] * v

    def of_row(b_, j, seats_ref, first_ref):
        del seats_ref, first_ref
        return (b_, j, 0)

    def of_seat(b_, j, seats_ref, first_ref):
        del first_ref
        return (seats_ref[b_], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // heads),
        in_specs=[
            pl.BlockSpec((1, 4, 1, heads, dk),
                         lambda b_, j, *_: (b_, 0, j, 0, 0)),
            pl.BlockSpec((1, heads, dv), of_row),
            pl.BlockSpec((1, heads, dk, dv), of_seat),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, dv), of_row),
            pl.BlockSpec((1, heads, dk, dv), of_seat),
        ],
    )
    o, state = pl.pallas_call(
        _kda_state_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # Operands count the scalar prefetch: the state is the fifth.
        input_output_aliases={4: 1},
        interpret=interpret,
    )(seats.astype(jnp.int32), first.astype(jnp.int32), vecs, rows, state)
    return o, state


def kda_decode(q, k, v, g, beta, state, seats, first, *, force=None):
    """:func:`kda_decode_reference`'s arguments and result, through the
    kernel on a TPU (``force``: a model config's ``paged_attn``, as
    :func:`raytpu.ops.paged_attention.resolve_paged_impl` reads it)."""
    if state.dtype != jnp.float32:
        raise ValueError(
            f"kda_decode: the matrix state is float32, got {state.dtype} "
            f"(a state of another type is kda_decode_reference's to step, "
            f"and no kernel's)")
    impl = resolve_paged_impl(force)
    if impl == "reference":
        return kda_decode_reference(q, k, v, g, beta, state, seats, first)
    return _kda_state_pallas(q, k, v, g, beta, state, seats, first,
                             interpret=(impl == "interpret"))
