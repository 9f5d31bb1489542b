"""Absorbed latent attention over the paged latent cache (MLA decode).

A latent-attention layer (DeepSeek-V2's MLA) caches one row a token: the
normed compressed latent ``c_kv`` (``rank`` values) and one roped key
``k_pe`` shared by all heads. Keys and values are both linear in
``c_kv``, so the per-head expansion can be moved onto the query and the
output (the *absorbed* form): with ``q_lat[n] = q_nope[n] W_uk[n]^T``,

    score[n, j] = (q_lat[n] . c_kv[j] + q_pe[n] . k_pe[j]) * sm_scale
    u[n]        = sum_j softmax_j(score[n, :]) c_kv[j]

and the caller multiplies ``u[n]`` by ``W_uv[n]``. Every head reads the
same row, and the values are the row's first ``rank`` columns: a page is
copied once and serves as K and as V.

**The row as held.** A pool is ``[num_pages, page_size, width]`` with
``width`` = ``rank`` + the roped key's size rounded up to whole 128-lane
tiles (512 + 64 -> 640): ``[c_kv | k_pe | zeros]``. The TPU holds a
576-lane row on 640 lanes whatever the array says, so the padding is
written out, costs what it would cost anyway, and every copy and every
slice in the kernel is tile-aligned. The query row is laid out the same
way, ``[q_lat | q_pe | zeros]``, so one dense product over ``width``
lanes is the whole score.

**The kernel** (``_mla_paged_pallas``; its trace events carry that name)
is :mod:`raytpu.ops.paged_attention`'s walk with one pool: grid
``(sequence, query block)``, block tables and query-start positions as
scalar prefetch, the pool whole in HBM, a sequence's live pages copied
``pages_per_block`` at a time by one DMA each into half of a double
buffer while the other half is computed on, the copy of the next grid
step's first block started by the step before. A grid step's rows are
``(token, head)``: all ``H`` heads of ``block_q`` tokens against one
shared block of rows, ``[rows, width] x [slots, width]^T``, the position
mask, the online softmax in float32, ``p x block[:, :rank]``. Decode is
one token a sequence (32 rows at 32 heads); ``T`` > 1 tokens a sequence
run the same kernel with ``block_q`` tokens a step.

**Which ``T`` takes which form.** The absorbed form pays ``2 (width +
rank)`` FLOPs a (query, head, key), the expanded one ``4 (nope + rope)``
and the key's expansion once: :func:`expands` is the break-even, a
number of queries of one sequence (171 at the widths above). Under it
(a decode row, a verify step's two) ``LatentAttention.step`` calls this
kernel; over it (a prompt's chunk) it attends expanded, through
:mod:`raytpu.ops.flash_attention`, the cached rows :func:`expanded_parts`
segments at a time, and this kernel is not in the chunk program.

``mla_paged_attention_reference`` is the dense float32 form over the
gathered pages: the numerics ground truth, and the CPU default.
Implementation choice is :func:`raytpu.ops.paged_attention.
resolve_paged_impl`'s, by the same ``force``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raytpu.ops.paged_attention import (_LANES, _NEG_INF, _fit_q_block,
                                        _whole_lane_tiles, gather_kv_pages,
                                        resolve_paged_impl)

__all__ = [
    "expanded_parts",
    "expands",
    "gather_segment",
    "latent_row_width",
    "latent_rows",
    "mla_paged_attention",
    "mla_paged_attention_reference",
]

# Slots of one block of pages (a pass of the kernel's loop), and the query
# rows ((token, head) pairs) of one grid step of a chunk.
_BLOCK_SLOTS = 512
_CHUNK_ROWS = 256


def latent_row_width(rank: int, rope_dim: int) -> int:
    """Lanes of a pool row as held: the latent and the roped key, rounded
    up to whole 128-lane tiles."""
    return _whole_lane_tiles(rank + rope_dim)


def expands(t: int, *, rank: int, nope_dim: int, rope_dim: int,
            v_dim: int) -> bool:
    """Whether ``t`` query tokens of one sequence attend its cache
    cheaper *expanded* than absorbed, by the products' FLOPs alone. A
    (query, head, key) costs the absorbed form a score over the row as
    held and values over the latent, ``2 (width + rank)``; the expanded
    form scores and weighs heads of ``nope_dim + rope_dim`` (the values
    ride a head as wide as the keys'), and pays ``2 rank (nope_dim +
    v_dim)`` a (key, head) once to put the key through ``kv_b_proj``.
    The keys cancel: the break-even is a number of queries (171 at a
    latent of 512, a roped key of 64 and heads of 128: a decode row and a
    verify step's two stay absorbed, a prompt's chunk expands)."""
    absorbed = 2 * (latent_row_width(rank, rope_dim) + rank)
    expanded = 2 * 2 * (nope_dim + rope_dim)
    return t * (absorbed - expanded) > 2 * rank * (nope_dim + v_dim)


def expanded_parts(t: int, start: int, page_size: int):
    """How a chunk of ``t`` query tokens whose first stands at ``start``
    attends expanded: ``(rows of a segment, parts)``. The rows before
    ``start`` go through ``kv_b_proj`` a segment at a time, whole pages
    and no more rows than the chunk has; ``parts`` counts those segments
    and one for the chunk's own rows (``start`` may be traced)."""
    segment = max(1, t // page_size) * page_size
    return segment, 1 + (start + segment - 1) // segment


def gather_segment(pages, block_table, i, segment: int) -> jax.Array:
    """Rows ``[i * segment, (i + 1) * segment)`` (whole pages; ``i`` may
    be traced) of the sequence behind ``block_table`` [P], gathered out
    of ``pages`` -> ``[segment, width]``. Columns past the table's end
    name page 0: like a dead column's, their rows are for the caller to
    leave out."""
    per = segment // pages.shape[1]
    table = jnp.pad(block_table, (0, -block_table.shape[0] % per))
    table = jax.lax.dynamic_slice(table, (i * per,), (per,))
    return gather_kv_pages(pages, table[None], pages.shape[2])[0, :, 0]


def latent_rows(c_kv: jax.Array, k_pe: jax.Array) -> jax.Array:
    """``[..., width]`` pool rows from ``c_kv`` ``[..., rank]`` and the
    roped ``k_pe`` ``[..., rope_dim]``: side by side, zeros after."""
    rank, rope_dim = c_kv.shape[-1], k_pe.shape[-1]
    pad = latent_row_width(rank, rope_dim) - rank - rope_dim
    return jnp.concatenate(
        [c_kv, k_pe, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)], -1)


def mla_paged_attention_reference(q, pages, block_tables, positions, *,
                                  rank, sm_scale):
    """Dense float32 absorbed attention over the gathered pages. ``q``
    ``[B, T, H, width]`` (``[q_lat | q_pe | zeros]``), ``pages``
    ``[num_pages, page_size, width]``; a query at position p sees slots
    ``0..p``. Returns ``[B, T, H, rank]``."""
    rows = gather_kv_pages(pages, block_tables, pages.shape[2])[:, :, 0]
    rows = rows.astype(jnp.float32)                       # [B, L, width]
    s = jnp.einsum("bthw,blw->bhtl", q.astype(jnp.float32), rows) * sm_scale
    slots = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None, :]
    visible = slots <= positions[:, :, None]
    s = jnp.where(visible[:, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhtl,blr->bthr", p, rows[..., :rank])
    return o.astype(q.dtype)


def _mla_kernel(bt_ref, qs_ref, q_ref, kv_hbm, o_ref, kv_buf, sems,
                half_ref, m_scr, l_scr, acc_scr, *, sm_scale, bq_t, heads,
                n_pg, n_qb):
    """One grid step: all heads of query-token block iq of sequence b
    against that sequence's live pages, ``ppb`` pages a pass. See
    ``paged_attention._paged_kernel`` for the walk; here there is one
    pool, and a block's first ``rank`` lanes are its values."""
    b, iq = pl.program_id(0), pl.program_id(1)
    step = b * n_qb + iq
    n_steps = pl.num_programs(0) * n_qb
    rows, rank = acc_scr.shape
    _, ppb, page_size, width = kv_buf.shape
    slots = ppb * page_size

    def last_page(b_, iq_):
        last = (qs_ref[b_] + iq_ * bq_t + bq_t - 1) // page_size
        return jnp.clip(last, 0, n_pg - 1)

    def live_pages(last_, i_):
        return jnp.minimum(ppb, last_ + 1 - i_ * ppb)

    def page_copy(page, half, p):
        return pltpu.make_async_copy(kv_hbm.at[page], kv_buf.at[half, p],
                                     sems.at[half])

    def start_block(b_, iq_, i_, half):
        @pl.loop(0, live_pages(last_page(b_, iq_), i_))
        def _start_page(p):
            page_copy(bt_ref[b_, i_ * ppb + p], half, p).start()

    @pl.when(step == 0)
    def _first_block():
        half_ref[0] = 0
        start_block(b, iq, 0, 0)

    m_scr[...] = jnp.full((rows, _LANES), _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros((rows, _LANES), jnp.float32)
    acc_scr[...] = jnp.zeros((rows, rank), jnp.float32)

    q_start = qs_ref[b]
    last = last_page(b, iq)
    n_blk = last // ppb + 1

    def block(i, half):
        ends = i + 1 == n_blk
        nxt = step + 1

        @pl.when(jnp.logical_or(~ends, nxt < n_steps))
        def _next_block():
            start_block(jnp.where(ends, nxt // n_qb, b),
                        jnp.where(ends, nxt % n_qb, iq),
                        jnp.where(ends, 0, i + 1), 1 - half)

        live = live_pages(last, i)

        @pl.loop(0, live)
        def _wait_page(p):
            page_copy(0, half, p).wait()

        # Pages past the live ones were not fetched. Their slots are
        # masked, but they are values too, and 0 x a stale NaN is NaN.
        @pl.loop(live, ppb)
        def _zero_page(p):
            kv_buf[half, p] = jnp.zeros((page_size, width), kv_buf.dtype)

        q = q_ref[0]  # [rows, width]
        kb = kv_buf[half].reshape(slots, width).astype(q.dtype)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        # Rows run (token, head): row r is query token iq*bq_t + r // H.
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, slots), 0)
        tok = iq * bq_t + jax.lax.div(row, heads)
        slot = i * slots + jax.lax.broadcasted_iota(
            jnp.int32, (rows, slots), 1)
        s = jnp.where(slot <= q_start + tok, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True),
            (rows, _LANES))
        m_scr[...] = jnp.broadcast_to(m_new, (rows, _LANES))
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(q.dtype), kb[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return 1 - half

    half_ref[0] = jax.lax.fori_loop(0, n_blk, block, half_ref[0])
    l = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("rank", "sm_scale", "interpret"))
def _mla_paged_pallas(q, pages, block_tables, positions, *, rank, sm_scale,
                      interpret):
    b, t, h, width = q.shape
    _, page_size, held = pages.shape
    if held != width or rank % _LANES or rank > width:
        raise ValueError(
            f"query rows of {width} lanes and a latent of {rank} do not "
            f"match a pool whose rows hold {held}")
    n_pg = block_tables.shape[1]
    bq_t = _fit_q_block(t, max(1, _CHUNK_ROWS // h))
    n_qb = t // bq_t
    live_rows = bq_t * h
    sublanes = 32 // q.dtype.itemsize
    rows = -(-live_rows // sublanes) * sublanes
    ppb = max(1, _BLOCK_SLOTS // page_size)

    qg = q.reshape(b, n_qb, live_rows, width)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - live_rows), (0, 0)))
    qg = qg.reshape(b, n_qb * rows, width)
    q_start = positions[:, 0].astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def q_index(b_, iq, bt_ref, qs_ref):
        del bt_ref, qs_ref
        return (b_, iq, 0)

    kernel = functools.partial(
        _mla_kernel, sm_scale=sm_scale, bq_t=bq_t, heads=h, n_pg=n_pg,
        n_qb=n_qb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_qb),
        in_specs=[
            pl.BlockSpec((1, rows, width), q_index),
            pl.BlockSpec(memory_space=pl.ANY),  # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, rows, rank), q_index),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, width), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),  # one a buffer half
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, rank), jnp.float32),
        ],
    )
    kwargs = {}
    if not interpret:
        # The buffer half and the copies in flight are carried from one
        # grid step to the next: both dimensions run in order.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.GridDimensionSemantics.ARBITRARY,) * 2)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_qb * rows, rank), q.dtype),
        interpret=interpret,
        **kwargs,
    )(block_tables, q_start, qg, pages)
    out = out.reshape(b, n_qb, rows, rank)[:, :, :live_rows]
    return out.reshape(b, t, h, rank)


def mla_paged_attention(q_lat, q_pe, pages, block_tables, positions, *,
                        sm_scale, force=None):
    """Absorbed latent attention of queries against the paged latent
    cache.

    Args:
      q_lat: ``[B, T, H, rank]``, each head's ``q_nope W_uk^T``.
      q_pe: ``[B, T, H, rope_dim]`` roped.
      pages: ``[num_pages, page_size, width]``, rows as
        :func:`latent_rows` lays them out (may be bf16).
      block_tables: ``[B, P]`` page ids a sequence (dead columns: any
        valid page, scratch by convention).
      positions: ``[B, T]`` absolute positions; the kernel takes a
        sequence's first and counts on from it (decode: T = 1; a chunk:
        B = 1, consecutive). A query at p sees slots ``0..p``.
      sm_scale: the softmax scale (the *expanded* head's: 1 / sqrt(nope +
        rope), which no shape here gives away).
      force: as ``paged_attention``'s.

    Returns ``[B, T, H, rank]`` in ``q_lat``'s dtype: each head's
    attention-weighted latent, for the caller's ``W_uv``.
    """
    rank = q_lat.shape[-1]
    q = latent_rows(q_lat, q_pe.astype(q_lat.dtype))
    positions = positions.astype(jnp.int32)
    impl = resolve_paged_impl(force)
    if impl == "reference":
        return mla_paged_attention_reference(
            q, pages, block_tables, positions, rank=rank, sm_scale=sm_scale)
    return _mla_paged_pallas(q, pages, block_tables, positions, rank=rank,
                             sm_scale=float(sm_scale),
                             interpret=(impl == "interpret"))
