"""Sparse latent attention over two paged pools (DeepSeek sparse
attention, as GLM-5's ``glm_moe_dsa`` has it).

A latent-attention layer with an *indexer* keeps two rows a token under
one block table: the latent row of :mod:`raytpu.ops.mla_attention`
(``[c_kv | k_pe | zeros]``) and one small roped *index key*. A query does
not attend every cached position. Its indexer scores them all,

    I[t, s] = sum_h w[t, h] * relu(q_I[t, h] . k_I[s])        (s <= t)

the ``index_topk`` largest are kept (ties to the lower position; all
``t + 1`` of them while the context is no longer than that), and the
absorbed latent attention runs over the kept rows alone. Three steps,
each with a dense float32 reference form, chosen by
:func:`raytpu.ops.paged_attention.resolve_paged_impl`'s one rule:

- :func:`index_scores`: the scores of every cached position, float32,
  ``-1e30`` at a future position and in a dead table column. The kernel
  (``_dsa_index_pallas``; its trace events carry that name) is
  ``mla_attention``'s walk over the index pool: grid ``(sequence, query
  block)``, a sequence's live pages copied ``pages_per_block`` at a time
  into half of a double buffer, a block's ``[rows, D] x [slots, D]^T``
  with rows ``(token, head)``, ReLU, the heads' weights, the sum over a
  token's heads.
- :func:`select_rows`: the exact top-k of the float32 scores (no
  approximation, no sort: the k-th largest found from the bits, equal
  scores to the lower position, the chosen slots counted off) ->
  ``(positions [B, T, k], how many of them count [B, T])``. A position
  that does not count is 0, which every query may see: no future
  position, dead column or scratch slot is ever named.
- :func:`sparse_latent_attention`: the chosen rows gathered from the
  latent pool by ``(page, offset)`` and the absorbed form over them. The
  kernel (``_dsa_attend_pallas``) takes the gathered rows a query at a
  time: ``[H, width] x [k, width]^T``, the count's mask, a float32
  softmax, ``p x rows[:, :rank]``. The gather itself is XLA's: a row of a
  bf16 pool is half a sublane of its tile, which no DMA of a kernel's own
  copies alone. (It costs a TPU 11 to 30 ns a row whatever the row holds:
  the choice above fetches nothing by a gather for that reason.)

:func:`dsa_paged_attention` is the three in order under
``jax.named_scope("attn.dsa.index" / ".select" / ".attend")``. A
sequence with more than ``QUERY_BLOCK`` queries (a prompt's chunk) is
taken that many queries at a time, one after another (``jax.lax.map``):
a chunk's 4,096 queries each choose and read 2,048 rows of their own,
10.7 GB of gathered rows at GLM-5's widths if taken at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raytpu.ops.mla_attention import latent_rows
from raytpu.ops.paged_attention import (_LANES, _NEG_INF, _fit_q_block,
                                        gather_kv_pages, resolve_paged_impl)

__all__ = [
    "dsa_paged_attention",
    "index_scores",
    "index_scores_reference",
    "select_rows",
    "sparse_latent_attention",
    "sparse_latent_attention_reference",
]

# Queries of one sequence taken at a time (scores, choice, gathered rows
# and attention all live for that many at once).
QUERY_BLOCK = 128
# Slots of one block of index-key pages (a pass of the index kernel's
# loop), and the query tokens of one of its grid steps.
_BLOCK_SLOTS = 512
_INDEX_TOKENS = 8


# ---- the indexer's scores ---------------------------------------------------


def index_scores_reference(q, w, pages, block_tables, positions):
    """Dense float32 index scores over the gathered pages. ``q``
    ``[B, T, Hi, D]`` roped, ``w`` ``[B, T, Hi]`` float32, ``pages``
    ``[num_pages, page_size, D]``; returns ``[B, T, P * page_size]``
    float32, ``-1e30`` where the slot is past the query's position."""
    keys = gather_kv_pages(pages, block_tables, pages.shape[2])[:, :, 0]
    s = jnp.einsum("bthd,bld->bthl", q.astype(jnp.float32),
                   keys.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.sum(jax.nn.relu(s) * w.astype(jnp.float32)[..., None], axis=2)
    slots = jnp.arange(keys.shape[1], dtype=jnp.int32)
    return jnp.where(slots <= positions[:, :, None], s, _NEG_INF)


def _index_kernel(bt_ref, qs_ref, q_ref, w_ref, k_hbm, o_ref, k_buf, sems,
                  half_ref, *, bq_t, heads, n_pg, n_qb):
    """One grid step: the index heads of query-token block iq of sequence
    b against that sequence's live index-key pages, ``ppb`` pages a pass
    (``mla_attention._mla_kernel``'s walk; here a block's product is
    weighed and summed over a token's heads and written out, block
    ``i`` of the output)."""
    b, iq = pl.program_id(0), pl.program_id(1)
    step = b * n_qb + iq
    n_steps = pl.num_programs(0) * n_qb
    _, ppb, page_size, width = k_buf.shape
    slots = ppb * page_size

    def last_page(b_, iq_):
        last = (qs_ref[b_] + iq_ * bq_t + bq_t - 1) // page_size
        return jnp.clip(last, 0, n_pg - 1)

    def live_pages(last_, i_):
        return jnp.minimum(ppb, last_ + 1 - i_ * ppb)

    def page_copy(page, half, p):
        return pltpu.make_async_copy(k_hbm.at[page], k_buf.at[half, p],
                                     sems.at[half])

    def start_block(b_, iq_, i_, half):
        @pl.loop(0, live_pages(last_page(b_, iq_), i_))
        def _start_page(p):
            page_copy(bt_ref[b_, i_ * ppb + p], half, p).start()

    @pl.when(step == 0)
    def _first_block():
        half_ref[0] = 0
        start_block(b, iq, 0, 0)

    # Blocks past the last live one are no position's: nothing is read.
    o_ref[0] = jnp.full(o_ref.shape[1:], _NEG_INF, jnp.float32)

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

        # Pages past the live ones were not fetched: stale bits, which
        # the position mask below discards whatever they are.
        q = q_ref[0]  # [rows, width], rows run (token, head)
        kb = k_buf[half].reshape(slots, width).astype(q.dtype)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[0][:, :1]
        per_token = jnp.concatenate(
            [jnp.sum(s[j * heads:(j + 1) * heads], axis=0, keepdims=True)
             for j in range(bq_t)], axis=0)               # [bq_t, slots]
        tok = iq * bq_t + jax.lax.broadcasted_iota(
            jnp.int32, (bq_t, slots), 0)
        slot = i * slots + jax.lax.broadcasted_iota(
            jnp.int32, (bq_t, slots), 1)
        o_ref[0, i] = jnp.where(slot <= q_start + tok, per_token, _NEG_INF)
        return 1 - half

    half_ref[0] = jax.lax.fori_loop(0, n_blk, block, half_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dsa_index_pallas(q, w, pages, block_tables, positions, *, interpret):
    b, t, h, width = q.shape
    _, page_size, held = pages.shape
    if held != width:
        raise ValueError(f"index queries of {width} values against a pool "
                         f"whose rows hold {held}")
    n_pg = block_tables.shape[1]
    bq_t = _fit_q_block(t, _INDEX_TOKENS)
    n_qb = t // bq_t
    live_rows = bq_t * h
    sublanes = 32 // q.dtype.itemsize
    rows = -(-live_rows // sublanes) * sublanes
    ppb = max(1, _BLOCK_SLOTS // page_size)
    slots = ppb * page_size
    n_blk = -(-n_pg // ppb)

    def by_block(x):
        """``[B, T, H, F]`` -> ``[B, n_qb * rows, F]``, a block's rows
        (token, head) and zeros up to whole sublanes."""
        x = x.reshape(b, n_qb, live_rows, x.shape[-1])
        x = jnp.pad(x, ((0, 0), (0, 0), (0, rows - live_rows), (0, 0)))
        return x.reshape(b, n_qb * rows, x.shape[-1])

    qg = by_block(q)
    wg = by_block(jnp.broadcast_to(
        w.astype(jnp.float32)[..., None], (b, t, h, _LANES)))
    q_start = positions[:, 0].astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def q_index(b_, iq, bt_ref, qs_ref):
        del bt_ref, qs_ref
        return (b_, iq, 0)

    def o_index(b_, iq, bt_ref, qs_ref):
        del bt_ref, qs_ref
        return (b_, 0, iq, 0)

    kernel = functools.partial(_index_kernel, bq_t=bq_t, heads=h, n_pg=n_pg,
                               n_qb=n_qb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_qb),
        in_specs=[
            pl.BlockSpec((1, rows, width), q_index),
            pl.BlockSpec((1, rows, _LANES), q_index),
            pl.BlockSpec(memory_space=pl.ANY),  # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, n_blk, bq_t, slots), o_index),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, width), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),  # one a buffer half
            pltpu.SMEM((1,), jnp.int32),
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
        out_shape=jax.ShapeDtypeStruct((b, n_blk, t, slots), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(block_tables, q_start, qg, wg, pages)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, n_blk * slots)
    return out[:, :, :n_pg * page_size]


def index_scores(q, w, pages, block_tables, positions, *, force=None):
    """The indexer's score of every cached position for every query.

    Args:
      q: ``[B, T, Hi, D]`` index queries, roped.
      w: ``[B, T, Hi]`` the heads' weights (scaled), float32.
      pages: ``[num_pages, page_size, D]`` the pool of index keys.
      block_tables: ``[B, P]``; positions: ``[B, T]`` absolute,
        consecutive along a sequence (the kernel counts on from the
        first).

    Returns ``[B, T, P * page_size]`` float32: ``I[t, s]`` at slot ``s``
    of the sequence's table, ``-1e30`` where ``s`` is past the query.
    """
    positions = positions.astype(jnp.int32)
    impl = resolve_paged_impl(force)
    if impl == "reference":
        return index_scores_reference(q, w, pages, block_tables, positions)
    return _dsa_index_pallas(q.astype(pages.dtype), w, pages, block_tables,
                             positions, interpret=(impl == "interpret"))


# ---- the choice -----------------------------------------------------------------

# Slots of one group of the choice's two-level count (a lane tile).
_GROUP = 128


def _ordered(x):
    """float32 -> uint32 in the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(keys, count):
    """The ``count``-th largest of ``keys`` uint32 ``[..., L]`` (``count``
    ``[...]``, at least 1): the largest value that ``count`` entries
    reach, found two bits a pass over the keys."""
    digits = jnp.arange(1, 4, dtype=jnp.uint32)

    def two_bits(i, found):
        shift = (30 - 2 * i).astype(jnp.uint32)
        trials = found[..., None] | (digits << shift)          # [..., 3]
        reach = jnp.sum(keys[..., None, :] >= trials[..., None], axis=-1,
                        dtype=jnp.int32)
        digit = jnp.sum(reach >= count[..., None], axis=-1)
        return found | (digit.astype(jnp.uint32) << shift)

    return jax.lax.fori_loop(0, 16, two_bits,
                             jnp.zeros(keys.shape[:-1], jnp.uint32))


def _running_sum(x):
    """Inclusive running sum along the last axis of ``x``, whole numbers
    of at most 256 each, as one product with a triangle of ones (exact:
    bf16 holds the terms, float32 the sums)."""
    n = x.shape[-1]
    ones = jnp.triu(jnp.ones((n, n), jnp.bfloat16))
    return jnp.einsum("...i,ij->...j", x.astype(jnp.bfloat16), ones,
                      preferred_element_type=jnp.float32).astype(jnp.int32)


def _numbered(mask):
    """``mask`` bool ``[B, T, G, 128]`` -> ``(inside, before, totals)``:
    each set slot's number from 1 inside its group (0 where unset), the
    set slots before each group ``[B, T, G]``, and those up to its end."""
    inside = _running_sum(mask)
    totals = _running_sum(inside[..., -1])
    return (jnp.where(mask, inside, 0), totals - inside[..., -1], totals)


def select_rows(scores, positions, index_topk: int):
    """The ``index_topk`` best-scored positions of each query, exactly.

    ``scores`` ``[B, T, L]`` float32 as :func:`index_scores` gives them;
    ``positions`` ``[B, T]``. Returns ``(chosen [B, T, k] int32 positions,
    count [B, T] int32)`` with ``k = min(index_topk, L)``: the first
    ``count = min(k, position + 1)`` entries of ``chosen`` are the choice,
    by rising position; the rest are 0.

    No sort: the ``count``-th largest score is found from the float32
    bits, 16 counting passes; every score above it is chosen, and of the
    scores equal to it the lowest positions, as many as are still
    wanted. The chosen slots are then numbered by a running count in two
    levels (inside a group of 128 slots, and over the groups), and entry
    ``j`` of the result is the slot numbered ``j + 1``. (A sort of all
    ``L`` scores, which is what ``jax.lax.top_k`` is on a TPU at this
    ``k``, is several times the rest of a chunk's step.)"""
    b, t, length = scores.shape
    k = min(index_topk, length)
    count = jnp.minimum(k, positions.astype(jnp.int32) + 1)
    pad = -length % _GROUP
    keys = _ordered(jnp.pad(scores, ((0, 0), (0, 0), (0, pad)),
                            constant_values=_NEG_INF))
    kth = _kth_largest(keys, count)[..., None]
    above = (keys > kth).reshape(b, t, -1, _GROUP)
    equal = (keys == kth).reshape(b, t, -1, _GROUP)
    wanted = count - jnp.sum(above, axis=(-2, -1), dtype=jnp.int32)

    def lowest_ties():
        """Of the scores equal to the k-th, the lowest positions: all of
        them unless two scores are the same float, so they are numbered
        only then."""
        inside, before, _ = _numbered(equal)
        return (inside > 0) & (inside + before[..., None]
                               <= wanted[..., None, None])

    chosen = above | jax.lax.cond(
        jnp.all(jnp.sum(equal, axis=(-2, -1), dtype=jnp.int32) == wanted),
        lambda: equal, lowest_ties)
    inside, before, totals = _numbered(chosen)
    # Entry j is the slot numbered j + 1: in the first group whose running
    # total passes j, the slot of that number inside it. A group's row is
    # fetched by a product with the group's one-hot row, not by a gather
    # (a TPU gathers a row in about the time it multiplies a thousand):
    # every term is a whole number bf16 holds.
    j = jnp.arange(k, dtype=jnp.int32)
    group = jnp.sum(totals[..., None] <= j, axis=-2, dtype=jnp.int32)
    group = jnp.minimum(group, inside.shape[2] - 1)          # [B, T, k]
    one_hot = (group[..., None] == jnp.arange(inside.shape[2])).astype(
        jnp.bfloat16)                                        # [B, T, k, G]
    rows = jnp.einsum("btkg,btgo->btko", one_hot,
                      inside.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    # (``before`` in three digits base 64: any count under 2 ** 18.)
    digits = jnp.stack([before >> 12, before >> 6 & 63, before & 63],
                       -1).astype(jnp.bfloat16)
    top, mid, low = jnp.moveaxis(jnp.einsum(
        "btkg,btgc->btkc", one_hot, digits,
        preferred_element_type=jnp.float32).astype(jnp.int32), -1, 0)
    rank = (j + 1 - (top * 4096 + mid * 64 + low)).astype(jnp.float32)
    offset = jnp.einsum(
        "...o,o->...", (rows == rank[..., None]).astype(jnp.bfloat16),
        jnp.arange(_GROUP, dtype=jnp.bfloat16),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    slot = group * _GROUP + offset
    return jnp.where(j < count[..., None], slot, 0), count


# ---- attention over the chosen rows -----------------------------------------


def sparse_latent_attention_reference(q, rows, count, *, rank, sm_scale):
    """Dense float32 absorbed attention of ``q`` ``[N, H, width]`` over
    its own gathered ``rows`` ``[N, k, width]``, of which the first
    ``count`` ``[N]`` are there. Returns ``[N, H, rank]``."""
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("nhw,nkw->nhk", q.astype(jnp.float32), rows) * sm_scale
    there = jnp.arange(rows.shape[1], dtype=jnp.int32) < count[:, None]
    s = jnp.where(there[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nhk,nkr->nhr", p, rows[..., :rank]).astype(q.dtype)


def _attend_kernel(count_ref, q_ref, rows_ref, o_ref, *, sm_scale, rank):
    """One query: all its heads against its own chosen rows."""
    q = q_ref[0]                                          # [heads, width]
    rows = rows_ref[0].astype(q.dtype)                    # [k, width]
    s = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < count_ref[pl.program_id(0)], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    acc = jax.lax.dot_general(
        p.astype(q.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (acc / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("rank", "sm_scale", "interpret"))
def _dsa_attend_pallas(q, rows, count, *, rank, sm_scale, interpret):
    n, h, width = q.shape
    k = rows.shape[1]
    if rank % _LANES or rank > width or rows.shape[2] != width:
        raise ValueError(
            f"query rows of {width} lanes and a latent of {rank} do not "
            f"match gathered rows of {rows.shape[2]}")
    sublanes = 32 // q.dtype.itemsize
    heads = -(-h // sublanes) * sublanes
    qp = jnp.pad(q, ((0, 0), (0, heads - h), (0, 0)))

    def index(i, count_ref):
        del count_ref
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, heads, width), index),
                  pl.BlockSpec((1, k, width), index)],
        out_specs=pl.BlockSpec((1, heads, rank), index),
    )
    out = pl.pallas_call(
        functools.partial(_attend_kernel, sm_scale=sm_scale, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, heads, rank), q.dtype),
        interpret=interpret,
    )(count.astype(jnp.int32), qp, rows)
    return out[:, :h]


def _attend_rows(q, rows, count, *, rank, sm_scale, force):
    """``q`` ``[N, H, width]`` over its gathered ``rows`` ``[N, k, width]``
    of which ``count`` ``[N]`` count, by ``force``'s implementation."""
    impl = resolve_paged_impl(force)
    if impl == "reference":
        return sparse_latent_attention_reference(
            q, rows, count, rank=rank, sm_scale=sm_scale)
    return _dsa_attend_pallas(q, rows, count, rank=rank,
                              sm_scale=float(sm_scale),
                              interpret=(impl == "interpret"))


def sparse_latent_attention(q, pages, chosen_pages, chosen_offsets, count,
                            *, rank, sm_scale, force=None):
    """Absorbed latent attention of each query over its chosen rows.

    Args:
      q: ``[B, T, H, width]`` (``[q_lat | q_pe | zeros]``).
      pages: ``[num_pages, page_size, width]`` the latent pool.
      chosen_pages, chosen_offsets: ``[B, T, k]`` int32, where each
        chosen row lies in the pool; count: ``[B, T]`` how many count.

    Returns ``[B, T, H, rank]`` in ``q``'s dtype.
    """
    b, t, h, width = q.shape
    k = chosen_pages.shape[-1]
    # By (page, offset) on the pool as it is: a reshape to flat slots
    # copies the pool (``paged_attention.scatter_kv_slots``).
    rows = pages[chosen_pages, chosen_offsets].reshape(b * t, k, width)
    out = _attend_rows(q.reshape(b * t, h, width), rows,
                       count.reshape(b * t), rank=rank, sm_scale=sm_scale,
                       force=force)
    return out.reshape(b, t, h, rank)


# ---- the three in order ------------------------------------------------------


def _dsa_block(q, q_idx, w_idx, pages, index_pages, block_tables, positions,
               *, rank, index_topk, sm_scale, force, context=None):
    """The three steps for queries ``[B, T]``. ``context`` (``[L, width]``,
    with ``B`` = 1): the sequence's latent rows by position, gathered
    page-wise once for all its blocks; the chosen rows are then taken
    from it by position, which costs a TPU three fifths of taking them
    from the pool by (page, offset)."""
    page_size = pages.shape[1]
    with jax.named_scope("attn.dsa.index"):
        scores = index_scores(q_idx, w_idx, index_pages, block_tables,
                              positions, force=force)
    with jax.named_scope("attn.dsa.select"):
        chosen, count = select_rows(scores, positions, index_topk)
        if context is None:
            chosen_pages = jnp.take_along_axis(
                block_tables.astype(jnp.int32)[:, None, :],
                chosen // page_size, axis=-1)
    with jax.named_scope("attn.dsa.attend"):
        if context is None:
            return sparse_latent_attention(
                q, pages, chosen_pages, chosen % page_size, count,
                rank=rank, sm_scale=sm_scale, force=force)
        return _attend_rows(q[0], context[chosen[0]], count[0], rank=rank,
                            sm_scale=sm_scale, force=force)[None]


def dsa_paged_attention(q_lat, q_pe, q_idx, w_idx, pages, index_pages,
                        block_tables, positions, *, index_topk, sm_scale,
                        force=None):
    """Indexer-chosen absorbed latent attention against the two pools.

    Args:
      q_lat: ``[B, T, H, rank]``, each head's ``q_nope W_uk^T``; q_pe:
        ``[B, T, H, rope_dim]`` roped (``mla_paged_attention``'s).
      q_idx: ``[B, T, Hi, D]`` the indexer's queries, roped; w_idx:
        ``[B, T, Hi]`` its heads' weights, float32.
      pages: ``[num_pages, page_size, width]`` latent rows; index_pages:
        ``[num_pages, page_size, D]`` index keys, under the same
        ``block_tables`` ``[B, P]``.
      positions: ``[B, T]`` absolute, consecutive along a sequence.
      index_topk: rows a query keeps; sm_scale, force: as
        ``mla_paged_attention``'s.

    Returns ``[B, T, H, rank]`` in ``q_lat``'s dtype.
    """
    b, t, h, rank = q_lat.shape
    q = latent_rows(q_lat, q_pe.astype(q_lat.dtype))
    positions = positions.astype(jnp.int32)
    one = functools.partial(
        _dsa_block, pages=pages, index_pages=index_pages, rank=rank,
        index_topk=index_topk, sm_scale=sm_scale, force=force)
    block = _fit_q_block(t, QUERY_BLOCK)
    if block == t:
        return one(q, q_idx, w_idx, block_tables=block_tables,
                   positions=positions)
    # A block of one sequence's queries at a time, each under its
    # sequence's table and over its sequence's rows by position.
    n = t // block
    with jax.named_scope("attn.dsa.attend"):
        contexts = gather_kv_pages(pages, block_tables, pages.shape[2])[
            :, :, 0]                                      # [B, L, width]

    def blocks(x):
        return x.reshape((b * n, 1, block) + x.shape[2:])

    def one_block(xs):
        q_, q_idx_, w_idx_, table, positions_, seq = xs
        return one(q_, q_idx_, w_idx_, block_tables=table,
                   positions=positions_,
                   context=contexts[0] if b == 1 else
                   jax.lax.dynamic_index_in_dim(contexts, seq, 0, False))

    out = jax.lax.map(one_block, (
        blocks(q), blocks(q_idx), blocks(w_idx),
        jnp.repeat(block_tables, n, axis=0)[:, None], blocks(positions),
        jnp.repeat(jnp.arange(b), n)))
    return out.reshape(b, t, h, rank)
