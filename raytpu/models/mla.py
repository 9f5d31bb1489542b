"""Latent attention (MLA, DeepSeek-V2) with the three entry points of
:class:`raytpu.models.llama.LlamaAttention` over one parameter set.

Queries go through a low-rank bottleneck (``q_a_proj`` -> RMSNorm ->
``q_b_proj``), each head's ``qk_nope_dim + qk_rope_dim`` values split
into a part that is not roped and a part that is. Keys and values come
from one compressed latent a token: ``kv_a_proj`` gives ``kv_lora_rank``
values (RMSNorm'd) and ONE ``qk_rope_dim``-wide key shared by every
head (roped); ``kv_b_proj`` expands the latent to each head's
``qk_nope_dim`` key part and ``v_head_dim`` values. Scores are over
``sqrt(qk_nope_dim + qk_rope_dim)``. Where the config says so
(``mla_scale_q_lora``, ``mla_scale_kv_lora``: LongCat-Flash) the normed
query latent is multiplied by ``sqrt(n_embd / q_lora_rank)`` and the
normed key/value latent by ``sqrt(n_embd / kv_lora_rank)``, which give
the two bottlenecks' outputs the variance a full-width projection would
have; the roped shared key is not scaled. The pool's row holds the
scaled latent, so the absorbed form is the same with or without.

- ``prefill`` (and ``__call__``, training) is the *expanded* form: the
  latent goes through ``kv_b_proj`` and flash attention runs on heads of
  ``qk_nope_dim + qk_rope_dim`` (values zero-padded to that width).
- ``step`` (``[B, T]`` positions: a prompt's chunk, a decode step) is
  the *absorbed* form against the paged latent cache
  (:mod:`raytpu.ops.mla_attention`): a layer has
  ONE pool, a token's row ``[normed latent | roped key | zeros]``, read
  once as keys and as values; ``kv_b_proj`` is folded into the query
  (``W_uk``) and applied to the attended latent (``W_uv``).

``rope_interleave``: the roped values are read as adjacent pairs
``(2j, 2j + 1)`` and laid out ``[evens | odds]`` before the rotation by
halves that :func:`raytpu.models.llama.apply_rope` does; q and k are
permuted alike, so scores equal the pairwise rotation's.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax.numpy as jnp

from raytpu.models.llama import (RMSNorm, apply_rope, apply_rope_single,
                                 rope_tables)


def deinterleave(x):
    """``[..., D]`` read as D/2 adjacent pairs -> ``[evens | odds]``."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


class LatentAttention(nn.Module):
    """``config`` carries ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_dim``, ``qk_rope_dim``, ``v_head_dim``, ``rope_interleave``,
    ``mla_scale_q_lora`` and ``mla_scale_kv_lora`` beside what every
    llama-family config has."""

    config: object

    def setup(self):
        c = self.config
        dense = functools.partial(nn.Dense, use_bias=False, dtype=c.dtype,
                                  param_dtype=c.param_dtype)

        def gain(on: bool, rank: int) -> float:
            return (c.n_embd / rank) ** 0.5 if on else 1.0

        def expansion(on: bool, features: int):
            """The projection out of a latent. Where the latent is
            scaled it is seeded as a projection out of the hidden size
            would be, variance 1 / n_embd: the correction's premise is
            one standard deviation for every matrix, under which a
            latent's expansion comes out sqrt(rank / n_embd) of the roped
            key's size. (Seeded at its own fan-in *and* scaled, attention
            scores are 7 times as wide as either alone makes them and a
            seeded model is chaotic: PERF.md, PR 51.)"""
            if not on:
                return dense(features)
            return dense(features, kernel_init=nn.initializers.normal(
                c.n_embd ** -0.5))

        self.q_a_proj = dense(c.q_lora_rank)
        self.q_a_norm = RMSNorm(
            dtype=c.dtype, eps=c.norm_eps,
            gain=gain(c.mla_scale_q_lora, c.q_lora_rank))
        self.q_b_proj = expansion(
            c.mla_scale_q_lora, c.n_head * (c.qk_nope_dim + c.qk_rope_dim))
        self.kv_a_proj = dense(c.kv_lora_rank + c.qk_rope_dim)
        self.kv_a_norm = RMSNorm(
            dtype=c.dtype, eps=c.norm_eps,
            gain=gain(c.mla_scale_kv_lora, c.kv_lora_rank))
        self.kv_b_proj = expansion(
            c.mla_scale_kv_lora, c.n_head * (c.qk_nope_dim + c.v_head_dim))
        self.o_proj = dense(c.n_embd)

    @property
    def sm_scale(self) -> float:
        c = self.config
        return (c.qk_nope_dim + c.qk_rope_dim) ** -0.5

    def __call__(self, x):
        return self.prefill(x)[0]

    def _project(self, x):
        """``x`` [..., E] -> ``(q_nope [..., H, nope], q_pe [..., H, rope],
        c_kv [..., rank] normed, k_pe [..., rope])``, nothing roped yet
        (the roped parts de-interleaved, the latents scaled, where the
        config says so)."""
        c = self.config
        q = self.q_b_proj(self.q_a_norm(self.q_a_proj(x)))
        q = q.reshape(x.shape[:-1]
                      + (c.n_head, c.qk_nope_dim + c.qk_rope_dim))
        q_nope, q_pe = q[..., :c.qk_nope_dim], q[..., c.qk_nope_dim:]
        kv = self.kv_a_proj(x)
        c_kv = self.kv_a_norm(kv[..., :c.kv_lora_rank])
        k_pe = kv[..., c.kv_lora_rank:]
        if c.rope_interleave:
            q_pe, k_pe = deinterleave(q_pe), deinterleave(k_pe)
        return q_nope, q_pe, c_kv, k_pe

    def prefill(self, x):
        """Full-sequence attention over ``x`` [B, T, E], expanded; returns
        ``(out [B, T, E], rows [B, T, width])``, the rows what belongs in
        the latent pool for positions 0..T-1."""
        from raytpu.ops.flash_attention import flash_attention
        from raytpu.ops.mla_attention import latent_rows

        c = self.config
        b, t, _ = x.shape
        h, nope, vd = c.n_head, c.qk_nope_dim, c.v_head_dim
        q_nope, q_pe, c_kv, k_pe = self._project(x)
        cos, sin = rope_tables(c.qk_rope_dim, jnp.arange(t), c.rope_theta)
        q_pe = apply_rope(q_pe.transpose(0, 2, 1, 3), cos, sin)
        k_pe = apply_rope(k_pe[:, None], cos, sin)          # [B, 1, T, rope]
        kv = self.kv_b_proj(c_kv).reshape(b, t, h, nope + vd)
        kv = kv.transpose(0, 2, 1, 3)
        q = jnp.concatenate([q_nope.transpose(0, 2, 1, 3), q_pe], -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, h, t,
                                                     c.qk_rope_dim))], -1)
        # The flash kernels take one head size: the values ride on the
        # first ``vd`` lanes of a head as wide as the keys'.
        v = jnp.pad(kv[..., nope:],
                    ((0, 0),) * 3 + ((0, q.shape[-1] - vd),))
        y = flash_attention(q, k, v, causal=True, sm_scale=self.sm_scale,
                            force=c.attn_impl)[..., :vd]
        y = y.transpose(0, 2, 1, 3).reshape(b, t, h * vd)
        return self.o_proj(y), latent_rows(c_kv, k_pe[:, 0])

    def _absorbed(self, q_nope, q_pe, pages, block_tables, positions):
        """The absorbed form's attention: ``q_nope`` [B, T, H, nope] and
        the roped ``q_pe`` [B, T, H, rope] against the latent ``pages``
        -> [B, T, H * v_head_dim]."""
        from raytpu.ops.mla_attention import mla_paged_attention

        c = self.config
        h, nope, vd = c.n_head, c.qk_nope_dim, c.v_head_dim
        w = self.kv_b_proj.variables["params"]["kernel"].astype(c.dtype)
        w = w.reshape(c.kv_lora_rank, h, nope + vd)
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, w[..., :nope])
        u = mla_paged_attention(q_lat, q_pe, pages, block_tables, positions,
                                sm_scale=self.sm_scale, force=c.paged_attn)
        y = jnp.einsum("bthr,rhd->bthd", u, w[..., nope:])
        return y.reshape(y.shape[:2] + (h * vd,))

    def step(self, x, pages, dests, block_tables, positions):
        """``x`` [B * T, E], ``T`` consecutive positions a sequence at
        absolute ``positions`` [B, T], against the latent pages (see
        :meth:`LlamaAttention.step` for the arguments): the rows scatter
        into ``dests`` [B, T] first, then each attends every cached
        position ``<=`` its own. Returns ``(out [B * T, E], pages')``."""
        from raytpu.ops.mla_attention import latent_rows
        from raytpu.ops.paged_attention import scatter_kv_slots

        c = self.config
        b, t = positions.shape
        q_nope, q_pe, c_kv, k_pe = self._project(x)
        cos, sin = rope_tables(c.qk_rope_dim, positions.reshape(b * t),
                               c.rope_theta)
        q_pe = apply_rope_single(q_pe, cos, sin)
        k_pe = apply_rope_single(k_pe[:, None], cos, sin)[:, 0]
        pages = scatter_kv_slots(pages, dests.reshape(b * t),
                                 latent_rows(c_kv, k_pe))
        y = self._absorbed(q_nope.reshape(b, t, c.n_head, -1),
                           q_pe.reshape(b, t, c.n_head, -1), pages,
                           block_tables, positions)
        return self.o_proj(y.reshape(b * t, -1)), pages
