"""The KDA layer (Kimi Delta Attention: the Kimi Linear report,
arXiv:2510.26692; ``layer_types``: :data:`raytpu.models.llama.KDA`): a
linear-attention operator that keeps, of a whole sequence, one matrix a
head and the newest rows of three short convolutions' inputs, and no keys
or values.

For the normed input ``x`` of a block, per head of ``n_head`` (``d =
head_dim`` for keys and values alike):

- ``[q~ | k~ | v~] = [W_q | W_k | W_v] x`` (each the width to ``n_head *
  d``), through one depthwise causal convolution of ``conv_taps`` taps
  over the three side by side, then SiLU; ``q = l2norm(q~) d^-1/2``,
  ``k = l2norm(k~)``, ``v = v~``, heads apart. No rotary embedding.
- the decay, a value a channel of the key: ``g = kda_lower_bound *
  sigmoid(exp(A_log_h) (W_f x + dt_bias))``, in ``[kda_lower_bound, 0)``;
  ``beta = sigmoid(W_b x)``, a value a head.
- the recurrence of :mod:`raytpu.ops.kda` over the head's state.
- ``y = W_o (RMSNorm_d(o) * sigmoid(W_g x)_h)``, the gate a value a head.

What a served sequence keeps of such a layer is its *state*, two rows of
two arrays at the sequence's seat (:mod:`raytpu.inference.kv_cache`): the
matrices ``[n_head, d, d]`` in float32, and the ``conv_taps - 1`` newest
rows of ``[q~ | k~ | v~]`` in the model's dtype. The module has the walks
:class:`raytpu.models.short_conv.ShortConv` has: the training forward, a
whole prompt, and a ``step`` of ``[B, T]`` rows behind the state at their
seats (a prompt's chunk in blocks, ``ops.kda.kda_chunked``; a decode row
in one pass over the state, ``ops.kda.kda_decode``). The projections run
in ``config.dtype``; the convolution's sum, the norms, the gates and the
recurrence in float32. Under ``jax.named_scope("kda.in_proj" | "kda.conv"
| "kda.gate" | "kda.state" (a decode row) | "kda.chunk" (a prompt's rows)
| "kda.out")``.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from raytpu.models.llama import LlamaConfig
from raytpu.ops.kda import kda_chunked, kda_decode

_L2_EPS = 1e-6


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


class KimiDeltaAttention(nn.Module):
    """``config`` carries ``kda_lower_bound`` and ``kda_gate_init``
    (``(A's range, dt_bias's range)``: what the seeded gate is drawn from)
    beside ``n_head``, ``head_dim``, ``conv_taps`` and what every
    llama-family config has."""

    config: LlamaConfig

    def setup(self):
        c = self.config
        h, d = c.n_head, c.head_dim
        dense = functools.partial(nn.Dense, use_bias=False, dtype=c.dtype,
                                  param_dtype=c.param_dtype)
        self.q_proj, self.k_proj, self.v_proj = (dense(h * d)
                                                 for _ in range(3))
        self.f_proj = dense(h * d)
        self.b_proj = dense(h)
        self.g_proj = dense(h)
        self.o_proj = dense(c.n_embd)
        self.conv_kernel = self.param(
            "conv_kernel", nn.initializers.normal(c.conv_taps ** -0.5),
            (c.conv_taps, 3 * h * d), c.param_dtype)
        (a_lo, a_hi), (b_lo, b_hi) = c.kda_gate_init

        def between(lo, hi, of=lambda x: x):
            return lambda key, shape: of(jax.random.uniform(
                key, shape, jnp.float32, lo, hi))

        self.a_log = self.param("A_log", between(a_lo, a_hi, jnp.log), (h,))
        self.dt_bias = self.param("dt_bias", between(b_lo, b_hi), (h * d,))
        self.o_norm = self.param("o_norm", nn.initializers.ones, (d,))

    def _heads(self, x):
        return x.reshape(*x.shape[:-1], self.config.n_head, -1)

    def _projected(self, x):
        with jax.named_scope("kda.in_proj"):
            return jnp.concatenate(
                [self.q_proj(x), self.k_proj(x), self.v_proj(x)], axis=-1)

    def _mix(self, before, qkv):
        """The convolution of ``qkv`` [B, T, W] behind the ``taps - 1``
        rows ``before`` that precede it, then SiLU, float32 -> ``(mixed,
        the two joined [B, taps - 1 + T, W])``: row ``i`` of the joined
        rows onwards is the state after ``i`` rows."""
        t = qkv.shape[-2]
        rows = jnp.concatenate([before.astype(qkv.dtype), qkv], axis=-2)
        taps = self.conv_kernel.astype(jnp.float32)
        mixed = sum(
            taps[j] * jax.lax.slice_in_dim(rows, j, j + t, axis=-2)
            .astype(jnp.float32) for j in range(taps.shape[0]))
        return nn.silu(mixed), rows

    def _gates(self, x, live):
        """``(g [B, T, H, d], beta [B, T, H])`` of ``x`` [B, T, E],
        float32; a row that is no token (``live`` [B, T]) decays nothing
        and writes nothing."""
        c = self.config
        with jax.named_scope("kda.gate"):
            f = self._heads(self.f_proj(x).astype(jnp.float32)
                            + self.dt_bias)
            g = c.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(self.a_log)[:, None] * f)
            beta = jax.nn.sigmoid(self.b_proj(x).astype(jnp.float32))
            return (jnp.where(live[..., None, None], g, 0.0),
                    jnp.where(live[..., None], beta, 0.0))

    def _qkv(self, mixed):
        """The three heads-apart operands of the recurrence from the
        convolved ``mixed`` [..., 3 * H * d]."""
        q, k, v = (self._heads(a) for a in jnp.split(mixed, 3, axis=-1))
        return _l2norm(q) * self.config.head_dim ** -0.5, _l2norm(k), v

    def _out(self, x, o):
        """``o`` [..., H, d] float32 normed a head, gated a head, through
        ``o_proj``."""
        c = self.config
        with jax.named_scope("kda.out"):
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + c.norm_eps) \
                * self.o_norm
            o = o * jax.nn.sigmoid(
                self.g_proj(x).astype(jnp.float32))[..., None]
            return self.o_proj(o.astype(c.dtype).reshape(*o.shape[:-2], -1))

    def _zeros(self, b, dtype):
        c = self.config
        return (jnp.zeros((b, c.n_head, c.head_dim, c.head_dim), jnp.float32),
                jnp.zeros((b, c.conv_taps - 1, 3 * c.n_head * c.head_dim),
                          dtype))

    def __call__(self, x):
        """``x`` [B, T, E] from position 0: the training forward."""
        qkv = self._projected(x)
        s, before = self._zeros(x.shape[0], qkv.dtype)
        with jax.named_scope("kda.conv"):
            mixed, _ = self._mix(before, qkv)
        g, beta = self._gates(x, jnp.ones(x.shape[:2], bool))
        with jax.named_scope("kda.chunk"):
            o, _ = kda_chunked(*self._qkv(mixed), g, beta, s)
        return self._out(x, o)

    def _recurrence(self, q, k, v, g, beta, state, seats, first):
        """The rows' recurrence behind the matrices at ``seats`` of
        ``state`` (zeros where ``first``) -> ``(o [B, T, H, d] float32,
        state with the seats written)``: a decode row in one pass over the
        state, a prompt's rows in blocks."""
        if q.shape[1] == 1:
            with jax.named_scope("kda.state"):
                o, state = kda_decode(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                    seats, first, force=self.config.paged_attn)
                return o[:, None], state
        with jax.named_scope("kda.chunk"):
            s = jnp.where(first[:, None, None, None], 0.0,
                          state[seats].astype(jnp.float32))
            o, s = kda_chunked(q, k, v, g, beta, s)
            return o, state.at[seats].set(s.astype(state.dtype))

    def step(self, x, state, tails, seats, live, first):
        """``x`` [B * T, E] (or [B, T, E]): ``T`` consecutive rows a
        sequence (a prompt's chunk at ``B = 1``, a decode row at ``T =
        1``) behind the state at its seat in the two arrays, ``state``
        ``[seats + 1, H, d, d]`` and ``tails`` ``[seats + 1, taps - 1, 3 *
        H * d]`` (``seats`` [B]; padding rows name seat 0), or behind
        zeros where the sequence's rows start at position 0 (``first``
        [B], or one bool for all); ``live`` [B, T] marks the rows that
        are tokens, of each sequence the first so many. Returns ``(out,
        state, tails)``, ``out`` as ``x`` is shaped, with the state after
        each sequence's last live row written at its seat."""
        c = self.config
        b, t = live.shape
        first = jnp.broadcast_to(jnp.asarray(first), (b,))
        qkv = self._projected(x).reshape(b, t, -1)
        with jax.named_scope("kda.conv"):
            before = jnp.where(first[:, None, None], 0, tails[seats])
            mixed, rows = self._mix(before, qkv)
            if t == 1:  # a decode row: the tails move on by one, or stay
                after = jnp.where(live[:, :, None], rows[:, 1:],
                                  rows[:, :-1])
            else:
                after = jax.vmap(functools.partial(
                    jax.lax.dynamic_slice_in_dim,
                    slice_size=before.shape[-2]))(
                        rows, jnp.sum(live, axis=-1, dtype=jnp.int32))
            tails = tails.at[seats].set(after.astype(tails.dtype))
        g, beta = self._gates(x.reshape(b, t, -1), live)
        o, state = self._recurrence(*self._qkv(mixed), g, beta, state, seats,
                                    first)
        out = self._out(x.reshape(b * t, -1), o.reshape(b * t, c.n_head, -1))
        return out.reshape(x.shape), state, tails

    def prefill(self, x, state, tails, seats, live):
        """A whole prompt, ``x`` [1, T, E] from position 0: a step whose
        rows start there."""
        return self.step(x, state, tails, seats, live, True)
