"""Latent attention (MLA, DeepSeek-V2) with the three entry points of
:class:`raytpu.models.llama.LlamaAttention` over one parameter set.

Queries go through a low-rank bottleneck (``q_a_proj`` -> RMSNorm ->
``q_b_proj``; with ``q_lora_rank`` ``None`` through one matrix,
``q_proj``), each head's ``qk_nope_dim + qk_rope_dim`` values split
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
scaled latent, so the absorbed form is the same with or without. With
``attn_head_gate`` each head's attended values are multiplied by
``sigmoid(W_g x)_h``, one value a head (``g_proj``: the width to
``n_head``), before ``o_proj``, in every form.

- ``prefill`` (and ``__call__``, training) is the *expanded* form: the
  latent goes through ``kv_b_proj`` and flash attention runs on heads of
  ``qk_nope_dim + qk_rope_dim`` (values zero-padded to that width).
- ``step`` (``[B, T]`` positions: a prompt's chunk, a decode step)
  attends the paged latent cache (:mod:`raytpu.ops.mla_attention`): a
  layer has ONE pool, a token's row ``[normed latent | roped key |
  zeros]``. A decode row, and any ``T`` under the break-even of
  :func:`raytpu.ops.mla_attention.expands`, is the *absorbed* form: the
  row read once as keys and as values, ``kv_b_proj`` folded into the
  query (``W_uk``) and applied to the attended latent (``W_uv``). A
  prompt's chunk (one sequence, ``T`` over the break-even) is the
  expanded form again, a third of the absorbed form's FLOPs: its own
  rows as ``prefill`` does them, the cached rows before them gathered
  and put through ``kv_b_proj`` a segment at a time under the flash
  kernel, the parts merged by their log-sum-exps in float32.

``rope_interleave``: the roped values are read as adjacent pairs
``(2j, 2j + 1)`` and laid out ``[evens | odds]`` before the rotation by
halves that :func:`raytpu.models.llama.apply_rope` does; q and k are
permuted alike, so scores equal the pairwise rotation's.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from raytpu.models.llama import (RMSNorm, apply_rope, apply_rope_single,
                                 rope_tables)


def deinterleave(x):
    """``[..., D]`` read as D/2 adjacent pairs -> ``[evens | odds]``."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


class LatentAttention(nn.Module):
    """``config`` carries ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_dim``, ``qk_rope_dim``, ``v_head_dim``, ``rope_interleave``,
    ``mla_scale_q_lora``, ``mla_scale_kv_lora`` and ``attn_head_gate``
    beside what every
    llama-family config has, and says by ``chunk_parts`` which ``T``
    attend expanded (:class:`raytpu.models.mixtral.LatentMoEConfig`)."""

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

        if c.q_lora_rank is None:
            self.q_proj = dense(c.n_head * (c.qk_nope_dim + c.qk_rope_dim))
        else:
            self.q_a_proj = dense(c.q_lora_rank)
            self.q_a_norm = RMSNorm(
                dtype=c.dtype, eps=c.norm_eps,
                gain=gain(c.mla_scale_q_lora, c.q_lora_rank))
            self.q_b_proj = expansion(
                c.mla_scale_q_lora,
                c.n_head * (c.qk_nope_dim + c.qk_rope_dim))
        if c.attn_head_gate:
            self.g_proj = dense(c.n_head)
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

    def _project(self, x, c_q=None):
        """``x`` [..., E] -> ``(q_nope [..., H, nope], q_pe [..., H, rope],
        c_kv [..., rank] normed, k_pe [..., rope])``, nothing roped yet
        (the roped parts de-interleaved, the latents scaled, where the
        config says so). ``c_q``: the normed query latent, where the
        caller has it already."""
        c = self.config
        if c.q_lora_rank is None:
            q = self.q_proj(x)
        else:
            if c_q is None:
                c_q = self.q_a_norm(self.q_a_proj(x))
            q = self.q_b_proj(c_q)
        q = q.reshape(x.shape[:-1]
                      + (c.n_head, c.qk_nope_dim + c.qk_rope_dim))
        q_nope, q_pe = q[..., :c.qk_nope_dim], q[..., c.qk_nope_dim:]
        kv = self.kv_a_proj(x)
        c_kv = self.kv_a_norm(kv[..., :c.kv_lora_rank])
        k_pe = kv[..., c.kv_lora_rank:]
        if c.rope_interleave:
            q_pe, k_pe = deinterleave(q_pe), deinterleave(k_pe)
        return q_nope, q_pe, c_kv, k_pe

    def _gated(self, x, y):
        """``y`` [..., H * v_head_dim], each head's values times the
        head's gate of ``x`` [..., E] where the config has one, through
        ``o_proj``."""
        c = self.config
        if c.attn_head_gate:
            gate = jax.nn.sigmoid(self.g_proj(x).astype(jnp.float32))
            y = (y.reshape(*y.shape[:-1], c.n_head, -1)
                 * gate[..., None].astype(y.dtype)).reshape(y.shape)
        return self.o_proj(y)

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
        q = jnp.concatenate([q_nope.transpose(0, 2, 1, 3), q_pe], -1)
        k, v = self._expand(c_kv, k_pe)
        y = flash_attention(q, k, v, causal=True, sm_scale=self.sm_scale,
                            force=c.attn_impl)[..., :vd]
        y = y.transpose(0, 2, 1, 3).reshape(b, t, h * vd)
        return self._gated(x, y), latent_rows(c_kv, k_pe[:, 0])

    @nn.nowrap  # no scope of its own: the caller's operations, as written
    def _expand(self, c_kv, k_pe):
        """The expanded form's keys and values of ``c_kv`` [B, S, rank]
        and the roped ``k_pe`` [B, 1, S, rope] -> ``(k, v)`` [B, H, S,
        nope + rope]: ``kv_b_proj``'s key part beside the shared roped
        key, and its values zero-padded to the same head."""
        c = self.config
        b, s, _ = c_kv.shape
        h, nope, vd = c.n_head, c.qk_nope_dim, c.v_head_dim
        kv = self.kv_b_proj(c_kv).reshape(b, s, h, nope + vd)
        kv = kv.transpose(0, 2, 1, 3)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, h, s,
                                                     c.qk_rope_dim))], -1)
        # The flash kernels take one head size: the values ride on the
        # first ``vd`` lanes of a head as wide as the keys'.
        v = jnp.pad(kv[..., nope:],
                    ((0, 0),) * 3 + ((0, k.shape[-1] - vd),))
        return k, v

    def _expanded(self, q_nope, q_pe, c_kv, k_pe, pages, block_tables,
                  start, segment, parts):
        """The expanded form's attention of one sequence's chunk:
        ``q_nope`` [T, H, nope] and the roped ``q_pe`` [T, H, rope] of
        the tokens from ``start`` on, whose ``c_kv`` [T, rank] and roped
        ``k_pe`` [T, rope] are in ``pages`` already -> [T, H *
        v_head_dim]. One softmax a query over its own rows up to itself
        and every cached row before ``start``, in ``parts`` parts
        (:func:`raytpu.ops.mla_attention.expanded_parts`): its own rows,
        then the cached ones ``segment`` at a time, only a live segment
        gathered and expanded, and the last one's rows from ``start`` on
        no keys."""
        from raytpu.ops.flash_attention import (flash_attention_part,
                                                merge_parts)
        from raytpu.ops.mla_attention import gather_segment

        c = self.config
        t, h, vd = q_nope.shape[0], c.n_head, c.v_head_dim
        attend = functools.partial(flash_attention_part,
                                   sm_scale=self.sm_scale, force=c.attn_impl)
        q = jnp.concatenate([q_nope, q_pe], -1).transpose(1, 0, 2)[None]
        own = attend(q, *self._expand(c_kv[None], k_pe[None, None]),
                     causal=True)

        def past(i, merged):
            rows = gather_segment(pages, block_tables[0], i, segment)
            rows = rows[None].astype(c.dtype)
            k_pe = rows[..., c.kv_lora_rank:c.kv_lora_rank + c.qk_rope_dim]
            k, v = self._expand(rows[..., :c.kv_lora_rank], k_pe[:, None])
            o, lse = attend(q, k, v, causal=False,
                            kv_len=jnp.minimum(start - i * segment, segment))
            return merge_parts([merged, (o[..., :vd], lse)])

        y, _ = jax.lax.fori_loop(
            0, parts - 1, past, merge_parts([(own[0][..., :vd], own[1])]))
        return y[0].astype(c.dtype).transpose(1, 0, 2).reshape(t, h * vd)

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
        position ``<=`` its own: absorbed, or, where one sequence brings
        enough queries that ``config.chunk_parts`` says so (a prompt's
        chunk), expanded. Returns ``(out [B * T, E],
        pages')``."""
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
        start = positions[0, 0]
        parts = c.chunk_parts(t, start, pages.shape[1]) if b == 1 else None
        if parts is not None:
            y = self._expanded(q_nope, q_pe, c_kv, k_pe, pages, block_tables,
                               start, *parts)
        else:
            y = self._absorbed(q_nope.reshape(b, t, c.n_head, -1),
                               q_pe.reshape(b, t, c.n_head, -1), pages,
                               block_tables, positions)
        return self._gated(x, y.reshape(b * t, -1)), pages


def _as_pool(x):
    """``x`` [B, T, W], a row a position from 0, as a pool of pages and
    the block tables under which sequence ``b``'s position ``p`` is row
    ``p`` of its table: ``([B * n, page, W], int32 [B, n])``."""
    b, t, w = x.shape
    page = 128 if t % 128 == 0 else t
    n = t // page
    return (x.reshape(b * n, page, w),
            jnp.arange(b * n, dtype=jnp.int32).reshape(b, n))


class SparseLatentAttention(LatentAttention):
    """Latent attention that reads only the cached positions a learned
    *indexer* chooses (DeepSeek sparse attention; GLM-5's
    ``glm_moe_dsa``). Beside ``LatentAttention``'s, ``config`` carries
    ``index_topk``, ``index_n_head``, ``index_head_dim`` and
    ``index_rope_interleave``.

    The indexer takes the query latent the attention computes anyway:
    ``q_I = W_Iqb c_q`` (``index_n_head`` heads of ``index_head_dim``),
    one key a token ``k_I = LayerNorm(W_Ik x)`` (scale and bias), the
    first ``qk_rope_dim`` values of each roped with the attention's own
    tables, and head weights ``w = (W_Iw x) / sqrt(index_n_head *
    index_head_dim)``. A query at ``t`` scores ``I[t, s] = sum_h w[t, h]
    relu(q_I[t, h] . k_I[s])`` for ``s <= t`` and attends the
    ``index_topk`` best-scored positions (:mod:`raytpu.ops.dsa_attention`;
    a context no longer than that is attended whole, and the result is
    ``LatentAttention``'s). The choice is made from float32 scores and
    passes no gradient.

    A layer keeps **two** pools under one block table: the latent rows
    and the index keys, ``index_head_dim`` wide. ``step`` takes and
    returns both (the serving walk's K and V places); ``prefill`` returns
    both kinds of row. A whole prompt no longer than ``index_topk`` is
    ``LatentAttention.prefill`` (flash attention, expanded); a longer one
    is not causal-dense attention, and goes through the absorbed form
    over its own rows as a pool."""

    def setup(self):
        super().setup()
        c = self.config
        dense = functools.partial(nn.Dense, use_bias=False, dtype=c.dtype,
                                  param_dtype=c.param_dtype)
        self.index_q_proj = dense(c.index_n_head * c.index_head_dim)
        self.index_k_proj = dense(c.index_head_dim)
        self.index_k_norm = nn.LayerNorm(epsilon=c.index_norm_eps,
                                         dtype=c.dtype)
        self.index_w_proj = dense(c.index_n_head)

    def _index_roped(self, v, cos, sin):
        """``v`` [N, heads, index_head_dim] with its first ``qk_rope_dim``
        values rotated by the attention's own tables."""
        c = self.config
        head, tail = v[..., :c.qk_rope_dim], v[..., c.qk_rope_dim:]
        if c.index_rope_interleave:
            head = deinterleave(head)
        return jnp.concatenate([apply_rope_single(head, cos, sin), tail], -1)

    def _index_keys(self, x, cos, sin):
        """One roped index key a row of ``x`` [N, E] -> [N, D]."""
        k = self.index_k_norm(self.index_k_proj(x))
        return self._index_roped(k[:, None], cos, sin)[:, 0]

    def _index_queries(self, x, c_q, cos, sin):
        """``(q_I [N, Hi, D] roped, w [N, Hi] float32)`` of the rows
        ``x`` [N, E] whose normed query latent is ``c_q``."""
        c = self.config
        q = self.index_q_proj(c_q).reshape(
            x.shape[0], c.index_n_head, c.index_head_dim)
        w = self.index_w_proj(x).astype(jnp.float32) \
            * (c.index_n_head * c.index_head_dim) ** -0.5
        return self._index_roped(q, cos, sin), w

    def _rows(self, x, positions):
        """Everything of the rows ``x`` [N, E] at ``positions`` [N] that
        the absorbed form takes: ``(q_nope, q_pe roped, latent rows,
        q_I, k_I, w)``."""
        from raytpu.ops.mla_attention import latent_rows

        c = self.config
        c_q = self.q_a_norm(self.q_a_proj(x))
        q_nope, q_pe, c_kv, k_pe = self._project(x, c_q)
        cos, sin = rope_tables(c.qk_rope_dim, positions, c.rope_theta)
        q_pe = apply_rope_single(q_pe, cos, sin)
        k_pe = apply_rope_single(k_pe[:, None], cos, sin)[:, 0]
        q_idx, w_idx = self._index_queries(x, c_q, cos, sin)
        return (q_nope, q_pe, latent_rows(c_kv, k_pe), q_idx,
                self._index_keys(x, cos, sin), w_idx)

    def _chosen(self, q_nope, q_pe, q_idx, w_idx, pages, index_pages,
                block_tables, positions):
        """The absorbed form over the rows the indexer chooses ->
        [B * T, H * v_head_dim]; ``positions`` [B, T], the rest a row a
        position."""
        from raytpu.ops.dsa_attention import dsa_paged_attention

        c = self.config
        b, t = positions.shape
        h, nope, vd = c.n_head, c.qk_nope_dim, c.v_head_dim
        w = self.kv_b_proj.variables["params"]["kernel"].astype(c.dtype)
        w = w.reshape(c.kv_lora_rank, h, nope + vd)
        q_lat = jnp.einsum("nhd,rhd->nhr", q_nope, w[..., :nope])
        u = dsa_paged_attention(
            q_lat.reshape(b, t, h, -1), q_pe.reshape(b, t, h, -1),
            q_idx.reshape((b, t) + q_idx.shape[1:]),
            w_idx.reshape(b, t, -1), pages, index_pages, block_tables,
            positions, index_topk=c.index_topk, sm_scale=self.sm_scale,
            force=c.paged_attn)
        y = jnp.einsum("nhr,rhd->nhd", u.reshape(b * t, h, -1),
                       w[..., nope:])
        return y.reshape(b * t, h * vd)

    def prefill(self, x):
        """``LatentAttention.prefill`` with a third value, the index
        keys [B, T, index_head_dim] for the second pool."""
        c = self.config
        b, t, e = x.shape
        positions = jnp.tile(jnp.arange(t), b)
        # Every position is chosen: dense. (So are the parameters made:
        # the dense form touches every one but the index queries'.)
        if t <= c.index_topk or self.is_initializing():
            out, rows = super().prefill(x)
            flat = x.reshape(b * t, e)
            cos, sin = rope_tables(c.qk_rope_dim, positions, c.rope_theta)
            if self.is_initializing():  # the queries' side has parameters
                self._index_queries(flat, self.q_a_proj(flat), cos, sin)
            keys = self._index_keys(flat, cos, sin)
            return out, rows, keys.reshape(b, t, -1)
        q_nope, q_pe, rows, q_idx, keys, w_idx = self._rows(
            x.reshape(b * t, e), positions)
        pages, tables = _as_pool(rows.reshape(b, t, -1))
        index_pages, _ = _as_pool(keys.reshape(b, t, -1))
        y = self._chosen(q_nope, q_pe, q_idx, w_idx, pages, index_pages,
                         tables, positions.reshape(b, t))
        return (self._gated(x.reshape(b * t, e), y).reshape(b, t, e),
                rows.reshape(b, t, -1),
                keys.reshape(b, t, -1))

    def step(self, x, pages, index_pages, dests, block_tables, positions):
        """``LatentAttention.step`` over both pools: the rows' latent
        rows and index keys scatter into ``dests`` first, then each
        attends the positions ``<=`` its own that its indexer chooses.
        Returns ``(out [B * T, E], pages', index_pages')``."""
        from raytpu.ops.paged_attention import scatter_kv_slots

        b, t = positions.shape
        q_nope, q_pe, rows, q_idx, keys, w_idx = self._rows(
            x, positions.reshape(b * t))
        pages = scatter_kv_slots(pages, dests.reshape(b * t), rows)
        index_pages = scatter_kv_slots(index_pages, dests.reshape(b * t),
                                       keys)
        y = self._chosen(q_nope, q_pe, q_idx, w_idx, pages, index_pages,
                         block_tables, positions)
        return self._gated(x, y), pages, index_pages
