"""The gated short convolution: LFM2's operator in the layers that are not
attention (``layer_types``: ``"conv"``; :data:`raytpu.models.llama.CONV`).

For the normed input ``n`` of a block: ``[B | C | u] = W_in n`` (once the
width to three times, in that order), ``v_t = B_t * u_t``, ``c_t = sum_j
w_j * v_{t - (L - 1) + j}`` over the ``L = conv_taps`` newest positions
(depthwise: a weight a channel a tap, causal, zeros left of position 0, no
bias), and the output is ``W_out (C_t * c_t)``. A position reads ``v`` of
its own and of the ``L - 1`` before it and nothing older, so what a served
sequence keeps of such a layer is those ``L - 1`` rows of ``v``, the same
few however long it is: its *state*, a row ``[L - 1, width]`` of the
layer's state array at the sequence's seat
(:mod:`raytpu.inference.kv_cache`), and no keys or values.

The module has the walks the attention modules have, over one parameter
set (``in_proj``, ``kernel`` [L, width], ``out_proj``): the training
forward, a whole prompt, and a ``step`` of ``[B, T]`` rows that reads the
state the rows before them left (a prompt's chunk, a decode row a
sequence). The last two are given the state array and the seats and
return the array written; the state left is that after the last *live*
row, and a padding row's seat is 0, the scratch row. The products run in
``config.dtype``, the convolution's sum in float32. Under
``jax.named_scope("conv.in_proj" | "conv.mix" | "conv.out_proj")``.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from raytpu.models.llama import LlamaConfig


class ShortConv(nn.Module):
    config: LlamaConfig

    def setup(self):
        c = self.config
        dense = functools.partial(nn.Dense, use_bias=False, dtype=c.dtype,
                                  param_dtype=c.param_dtype)
        self.in_proj = dense(3 * c.n_embd)
        self.out_proj = dense(c.n_embd)
        self.kernel = self.param(
            "kernel", nn.initializers.normal(c.conv_taps ** -0.5),
            (c.conv_taps, c.n_embd), c.param_dtype)

    def _gates(self, x):
        """``(v = B * u, C)`` of ``x`` [..., E]."""
        with jax.named_scope("conv.in_proj"):
            b, c, u = jnp.split(self.in_proj(x), 3, axis=-1)
            return b * u, c

    def _mix(self, before, v):
        """The convolution of ``v`` [..., T, E] behind the ``L - 1`` rows
        ``before`` [..., L - 1, E] that precede it -> ``(c [..., T, E],
        the two joined [..., L - 1 + T, E])``: row ``i`` of the joined
        rows onwards is the state after ``i`` rows of ``v``."""
        t = v.shape[-2]
        rows = jnp.concatenate([before.astype(v.dtype), v], axis=-2)
        taps = self.kernel.astype(jnp.float32)
        mixed = sum(
            taps[j] * jax.lax.slice_in_dim(rows, j, j + t, axis=-2)
            .astype(jnp.float32) for j in range(taps.shape[0]))
        return mixed.astype(v.dtype), rows

    def _out(self, gate, mixed):
        with jax.named_scope("conv.out_proj"):
            return self.out_proj(gate * mixed)

    def __call__(self, x):
        """``x`` [B, T, E] from position 0: the training forward."""
        v, gate = self._gates(x)
        with jax.named_scope("conv.mix"):
            mixed, _ = self._mix(jnp.zeros(
                (*v.shape[:-2], self.config.conv_taps - 1, v.shape[-1]),
                v.dtype), v)
        return self._out(gate, mixed)

    def step(self, x, state, seats, live, first):
        """``x`` [B * T, E] (or [B, T, E]): ``T`` consecutive rows a
        sequence (a prompt's chunk at ``B = 1``, a decode row at ``T =
        1``) behind the state at its seat (``seats`` [B]; padding rows
        name seat 0), or behind zeros where the sequence's rows start at
        position 0 (``first`` [B], or one bool for all: what the seat
        holds is then another sequence's); ``live`` [B, T] marks the rows
        that are tokens, of each sequence the first so many. Returns
        ``(out, state)``, ``out`` as ``x`` is shaped, with the state after
        each sequence's last live row written at its seat: fewer live
        rows than the state has carry the newest of the old ones on."""
        v, gate = self._gates(x)
        with jax.named_scope("conv.mix"):
            before = jnp.where(jnp.reshape(first, (-1, 1, 1)), 0,
                               state[seats])
            mixed, rows = self._mix(before, v.reshape(*live.shape, -1))
            after = jax.vmap(functools.partial(
                jax.lax.dynamic_slice_in_dim,
                slice_size=before.shape[-2]))(
                    rows, jnp.sum(live, axis=-1, dtype=jnp.int32))
            state = state.at[seats].set(after.astype(state.dtype))
        return self._out(gate, mixed.reshape(gate.shape)), state

    def prefill(self, x, state, seats, live):
        """A whole prompt, ``x`` [1, T, E] from position 0: a step whose
        rows start there."""
        return self.step(x, state, seats, live, True)
