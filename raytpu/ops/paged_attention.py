"""Paged flash-decode attention: fused block-table attention over the
inference KV page pool.

The decode hot path used to materialize the whole padded page pool with
``k_pages[block_tables]`` — O(B * P_max * page_size * kv_heads *
head_dim) HBM traffic per generated token — then run dense fp32
attention over mostly padding.  This module replaces that with a Pallas
kernel that reads KV pages **in place**, vLLM-PagedAttention style:

- grid ``(batch, kv_head_group, q_blocks)``, run in order; a grid step
  is one sequence's query rows against all of that sequence's live
  pages, taken ``pages_per_block`` at a time by a loop inside the kernel
  whose trip count comes from the prefetched position. Online-softmax
  state (m / l / acc) lives in VMEM scratch across the loop.
- the pools enter whole and stay in HBM (``memory_space=pl.ANY``), as
  they are held. The block table and the per-sequence query-start
  positions are scalar-prefetch operands: the kernel looks a block's
  live pages up in the table and copies each by a DMA of its own, K and
  V, all started together, into one half of a double buffer
  ``[2, pages_per_block, page_size, lanes]`` a pool. While a block is
  computed the next one's copies are in flight; the pass that finishes
  a sequence starts the next grid step's first block, so the copies'
  latency is exposed once a call and not once a sequence.
- a block is computed on whole: ``q [rows, lanes] x k [slots, lanes]^T``
  with ``slots = pages_per_block * page_size`` (at least 128, so the
  scores fill a lane tile), the position mask, one update of m, l and
  the accumulator, ``p x v``. ``_pages_per_block`` sizes it from what
  the call can see.
- only live pages are read, so the bytes moved are the live pages' K
  and V rows and nothing grows with the table's width: a dead column
  costs no copy, no flops and no pass of the loop (the kernel this
  replaced paid a quarter of a microsecond of grid step for every
  column of the table, live or dead: 7.4 ms of GPT-2 XL's 17.3 ms
  decode period; PERF.md, PR 31). A block's slots past the live pages
  are masked, and their rows of the V buffer zeroed (0 x a stale NaN is
  NaN).
- a pool is ``[num_pages, page_size, kv_heads * head_dim]``, a token's
  heads side by side in one row, and is blocked as it is held: a block
  takes kv heads that fill whole 128-lane tiles (two or more at
  ``head_dim`` 64, one or more at 128: as many as keep the block's
  query rows inside one pass of the matrix unit, so all of them in a
  decode step and the fewest in a prompt's chunk), because Mosaic has
  no block of one head out of a ``[kv_heads, head_dim]`` minor pair
  (which is also why no pool is held 4-D). The heads of a group share
  the lane axis: each query row is zero outside its own head's lanes,
  one dense product scores every head, and the wrapper keeps each
  row's own lanes of the result. The MXU does ``group`` times the
  needed work; the pool bytes read, which bound decode, do not change.
- GQA folds query heads onto their kv head (row = t*rep + r, matching
  ``jnp.repeat``), so one grid step attends all query heads sharing the
  group's kv heads.
- pages may be bf16; scores and accumulators are fp32.
- under a mesh (``jax.set_mesh``) the call runs per shard, the pools
  split on their last dimension, in which a head's features are
  contiguous (``per_shard``).

Like :mod:`raytpu.ops.flash_attention` this ships a sanctioned dense
reference (`paged_attention_reference`, the ONE place a materializing
gather is allowed — lint rule RTP011 bans it from models/ and
inference/), an ``interpret=True`` path so CPU tier-1 tests execute the
real kernel, and a ``force=`` override.

Implementation selection (``resolve_paged_impl``), from the platform and
the model config's ``paged_attn`` field and nothing else:

- ``None`` / ``auto``: kernel on TPU, reference elsewhere.
- ``kernel`` (``tpu``), ``interpret``, ``reference``: that one — tests
  and the benchmark's plain references pin it.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raytpu.ops.flash_attention import _on_tpu, per_shard

_NEG_INF = -1e30
# Online-softmax running max/denominator are (rows, LANES) f32 scratch:
# TPU vector scratch wants the 128-wide lane dimension even though only
# column 0 is meaningful.
_LANES = 128

__all__ = [
    "paged_attention",
    "paged_attention_reference",
    "gather_kv_pages",
    "resolve_paged_impl",
]


# Query tokens of one grid step (a decode step has one or two a
# sequence, so this only matters for a prompt's chunk).
BLOCK_Q = 256

_VALID_PAGED = {"auto": "auto", "kernel": "tpu", "tpu": "tpu",
                "interpret": "interpret", "reference": "reference"}


def resolve_paged_impl(selector=None) -> str:
    """Resolve the paged-attention implementation to run.

    ``selector`` is the model config's ``paged_attn`` field; ``None`` is
    ``"auto"``, the kernel on a TPU and the reference elsewhere. Returns
    one of ``"tpu"`` / ``"interpret"`` / ``"reference"``.
    """
    raw = "auto" if selector is None else str(selector).strip().lower()
    mode = _VALID_PAGED.get(raw)
    if mode is None:
        warnings.warn(
            f"config paged_attn={raw!r} not recognized (use 'auto', "
            f"'kernel', 'interpret', or 'reference'); using 'auto'",
            RuntimeWarning, stacklevel=2)
        mode = "auto"
    if mode == "auto":
        return "tpu" if _on_tpu() else "reference"
    return mode


# ---------------------------------------------------------------------------
# Writing new tokens' K/V into a pool.
# ---------------------------------------------------------------------------


def scatter_kv_slots(pages: jax.Array, dests: jax.Array,
                     rows: jax.Array) -> jax.Array:
    """``pages`` ``[num_pages, page_size, kv_heads * head_dim]`` with
    ``rows`` ``[N, kv_heads * head_dim]`` written at the flat slots
    ``dests`` ``[N]`` (``page * page_size + offset``, as
    ``PagedKVCache.slot`` gives them; padding rows name page 0). The
    one way a pool is written: the prefill, the chunked prefill and
    the decode step all come here, with the rows as the K and V
    projections produce them.

    Indexed by (page, offset) on the pool as it is, not through a
    reshape to ``[num_pages * page_size, ...]`` and back: on the TPU a
    pool lives in a layout in which that reshape is a copy of the whole
    pool into a padded form, and a decode program that made it kept all
    its layers' padded copies to its end (4.5 GB of scratch for GPT-2
    XL's 1.9 GB of pools; PERF.md, PR 25). Inside a program that was
    given ``pages`` donated (the engine's three are) the rows are
    written into the buffer that came in.
    """
    page_size = pages.shape[1]
    return pages.at[dests // page_size, dests % page_size].set(
        rows.astype(pages.dtype))


# ---------------------------------------------------------------------------
# Sanctioned dense reference.
# ---------------------------------------------------------------------------


def gather_kv_pages(pages: jax.Array, block_tables: jax.Array,
                    head_dim: int) -> jax.Array:
    """Materialize ``[B, P*page_size, kv_heads, head_dim]`` from the
    page pool ``[num_pages, page_size, kv_heads * head_dim]``; the heads
    are split after the gather, on the copy.  This is the ONE sanctioned
    home of the ``pages[block_tables]`` gather; RTP011 bans the pattern
    from ``raytpu/models/`` and ``raytpu/inference/``.
    """
    b = block_tables.shape[0]
    return pages[block_tables].reshape(b, -1, pages.shape[2] // head_dim,
                                       head_dim)


def paged_attention_reference(q, k_pages, v_pages, block_tables, positions,
                              *, sm_scale, window=None):
    """Dense fp32 attention over the gathered pages — numerics ground
    truth for the kernel, and the CPU default. Reproduces the op order
    of the pre-kernel model code (gather, repeat, fp32 einsums,
    additive-free masking via where, jax.nn.softmax) exactly so
    fallback greedy generation is unchanged. With a ``window`` a query at
    position p sees slots ``p - window < l <= p`` only."""
    b, t, h, d = q.shape
    ks = gather_kv_pages(k_pages, block_tables, d)
    vs = gather_kv_pages(v_pages, block_tables, d)
    kv = ks.shape[2]
    if kv != h:
        rep = h // kv
        ks = jnp.repeat(ks, rep, axis=2)
        vs = jnp.repeat(vs, rep, axis=2)
    s = jnp.einsum("bthd,blhd->bhtl", q.astype(jnp.float32),
                   ks.astype(jnp.float32)) * sm_scale
    # Slot l holds token l of the sequence; query token at absolute
    # position p sees slots 0..p.
    slots = jnp.arange(ks.shape[1], dtype=jnp.int32)[None, None, :]
    visible = slots <= positions[:, :, None]
    if window is not None:
        # A window layer: the ``window`` newest slots, the token's own
        # among them.
        visible &= slots > positions[:, :, None] - window
    s = jnp.where(visible[:, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhtl,blhd->bthd", p, vs.astype(jnp.float32))
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel.
# ---------------------------------------------------------------------------


def _paged_kernel(bt_ref, qs_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, half_ref, m_scr, l_scr, acc_scr,
                  *, sm_scale, bq_t, rep, n_pg, n_grp, n_qb, span, window):
    """One grid step: the query heads of one kv-head group and query-token
    block iq of sequence b, attending that sequence's live pages a block
    of ``ppb`` at a time. A block's pages are copied by one DMA each into
    half of a double buffer while the other half is computed on; the step
    that finishes a sequence starts the next step's first block, so the
    copies' latency is exposed once a call. ``half_ref`` carries which
    half is current from step to step.

    A group's heads sit side by side on the lane axis. Each query row
    is zero outside its own head's lanes, so one dense
    ``[rows, lanes] x [slots, lanes]^T`` product yields every head's
    scores, and row r of ``p @ v`` is right on the lanes of r's head
    (the wrapper keeps those and drops the rest).

    With a ``window`` (a window layer's pool) a query at position p sees
    slots ``p - window < l <= p``: the loop starts at the page that holds
    the first slot the block's first row may see, not at page 0, so the
    table's columns left of it are never looked up (the cache has given
    those pages back) and the pages read do not grow with the context."""
    b, j, iq = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    step = (b * n_grp + j) * n_qb + iq
    n_steps = pl.num_programs(0) * n_grp * n_qb
    rows, lanes = acc_scr.shape
    _, ppb, page_size, _ = k_buf.shape
    slots = ppb * page_size

    def last_page(b_, iq_):
        # The last page any row of q block iq_ may see.
        last = (qs_ref[b_] + iq_ * bq_t + bq_t - 1) // page_size
        return jnp.clip(last, 0, n_pg - 1)

    def first_page(b_, iq_):
        # The first page any row of q block iq_ may see: page 0, or the
        # page of the oldest slot inside its first row's window.
        if window is None:
            return None
        oldest = qs_ref[b_] + iq_ * bq_t - (window - 1)
        return jnp.clip(oldest, 0, n_pg * page_size - 1) // page_size

    def block_base(first_, i_):
        # The first page of the sequence's block i_.
        return i_ * ppb if first_ is None else first_ + i_ * ppb

    def live_pages(last_, first_, i_):
        # Of block i_'s pages, those up to the sequence's last.
        return jnp.minimum(ppb, last_ + 1 - block_base(first_, i_))

    def page_copies(page, j_, half, p):
        # A page of the group's heads: ``span`` lanes from the group's
        # first, out of each of the page's rows.
        first = pl.multiple_of(j_ * lanes, _LANES) if n_grp > 1 else 0
        return [pltpu.make_async_copy(
            hbm.at[page, :, pl.ds(first, span)],
            buf.at[half, p, :, pl.ds(0, span)], sems.at[i, half])
            for i, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))]

    def start_block(b_, j_, iq_, i_, half):
        first_ = first_page(b_, iq_)

        @pl.loop(0, live_pages(last_page(b_, iq_), first_, i_))
        def _start_page(p):
            for copy in page_copies(bt_ref[b_, block_base(first_, i_) + p],
                                    j_, half, p):
                copy.start()

    @pl.when(step == 0)
    def _first_block():
        half_ref[0] = 0
        start_block(b, j, iq, 0, 0)

    m_scr[...] = jnp.full((rows, _LANES), _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros((rows, _LANES), jnp.float32)
    acc_scr[...] = jnp.zeros((rows, lanes), jnp.float32)

    q_start = qs_ref[b]  # absolute position of query token 0
    last = last_page(b, iq)
    first = first_page(b, iq)
    n_blk = (last if first is None else last - first) // ppb + 1

    def block(i, half):
        # The block after this one: the sequence's next, or the first
        # of the next grid step's.
        ends = i + 1 == n_blk
        nxt = step + 1

        @pl.when(jnp.logical_or(~ends, nxt < n_steps))
        def _next_block():
            start_block(jnp.where(ends, nxt // (n_grp * n_qb), b),
                        jnp.where(ends, nxt // n_qb % n_grp, j),
                        jnp.where(ends, nxt % n_qb, iq),
                        jnp.where(ends, 0, i + 1), 1 - half)

        live = live_pages(last, first, i)

        @pl.loop(0, live)
        def _wait_page(p):
            for copy in page_copies(0, j, half, p):
                copy.wait()

        # Pages of the block past the live ones were not fetched: their
        # slots are masked below, but 0 x a stale NaN of V is NaN.
        @pl.loop(live, ppb)
        def _zero_page(p):
            v_buf[half, p] = jnp.zeros((page_size, lanes), v_buf.dtype)

        q = q_ref[0, 0]  # [rows, lanes]
        kb = k_buf[half].reshape(slots, lanes).astype(q.dtype)
        vb = v_buf[half].reshape(slots, lanes).astype(q.dtype)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        # Rows run (head in group, token, query head of the kv head):
        # row r holds query token iq*bq_t + (r mod bq_t*rep) // rep;
        # column c is slot i*slots + c. Rows past the live ones are
        # padding.
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, slots), 0)
        tok = iq * bq_t + jax.lax.div(
            jax.lax.rem(row, bq_t * rep), rep)
        slot = i * slots + jax.lax.broadcasted_iota(
            jnp.int32, (rows, slots), 1)
        if first is None:
            seen = slot <= q_start + tok
        else:
            slot = first * page_size + slot
            seen = jnp.logical_and(slot <= q_start + tok,
                                   slot > q_start + tok - window)
        s = jnp.where(seen, s, _NEG_INF)

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
            p.astype(q.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return 1 - half

    half_ref[0] = jax.lax.fori_loop(0, n_blk, block, half_ref[0])
    l = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _fit_q_block(t: int, want: int) -> int:
    """Largest divisor of t that is <= want (grid blocks must tile the
    query axis exactly)."""
    want = min(want, t)
    while t % want:
        want -= 1
    return want


# Rows of one matrix-unit pass: a block of this many query rows or fewer
# costs the MXU the same whatever heads share it.
_MXU_ROWS = 128


def _kv_heads_per_block(kv: int, d: int, rows_per_head: int = _MXU_ROWS
                        ) -> int:
    """How many kv heads one block takes. Mosaic wants the minor
    dimension of a block to be a multiple of 128 or the array's whole
    minor dimension, and a pool row flattened to ``[kv * d]`` offers
    both: any count whose features fill whole 128-lane tiles, or all the
    heads. Every grid step costs about a quarter of a microsecond
    whatever it moves, so of those counts the largest is taken whose
    query rows (``rows_per_head`` each: tokens of the block times query
    heads of a kv head) still fit one pass of the matrix unit; a decode
    step, one token a sequence, then reads a page of all 16 heads of
    128 in one step and not in 16 (33 ms of an 80 ms step of
    OLMoE-1B-7B at batch 16; PERF.md, PR 26). A chunk of a prompt has
    hundreds of rows a head and takes the fewest heads, as before."""
    fits = [g for g in range(1, kv)
            if kv % g == 0 and (g * d) % _LANES == 0] + [kv]
    few = [g for g in fits if g * rows_per_head <= _MXU_ROWS]
    return max(few) if few else fits[0]


def _whole_lane_tiles(lanes: int) -> int:
    """``lanes`` rounded up to whole 128-lane tiles: what a row of that
    many features occupies in HBM and in VMEM."""
    return -(-lanes // _LANES) * _LANES


# VMEM the two pools' double buffers may take, and a block's float32
# scores.
_KV_BUFFER_BYTES = 4 << 20
_SCORE_BYTES = 1 << 20


def _pages_per_block(page_size: int, rows: int, lanes: int,
                     itemsize: int) -> int:
    """How many pages of a sequence one pass of the kernel's loop takes.
    At least as many as give the scores a whole 128-lane tile (8 pages
    of 16 slots), and twice that where the four buffers (K and V, two
    halves each, rows padded to whole lane tiles) and the
    ``[rows, slots]`` scores stay inside their budgets: the copies in
    flight are one block, and a block of 16 pages keeps the HBM busier
    than one of 8 (84 against 78 % of the byte roofline at GPT-2 XL's
    decode shape and 1,000 tokens of context; PERF.md, PR 31). No more
    than that: a sequence's last block is computed on whole, half of it
    dead slots on average, and nothing measured says a wider one pays
    for them."""
    least = -(-_LANES // page_size)
    row = _whole_lane_tiles(lanes) * itemsize
    fits = min(_KV_BUFFER_BYTES // (4 * page_size * row),
               _SCORE_BYTES // (rows * page_size * 4))
    return max(least, min(2 * least, fits // least * least))


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "interpret", "window"))
def _paged_pallas(q, k_pages, v_pages, block_tables, positions,
                  *, sm_scale, interpret, window=None):
    b, t, h, d = q.shape
    # The pools as they are held: [num_pages, page_size, kv * d].
    _, page_size, width = k_pages.shape
    kv = width // d
    if kv * d != width or h % kv:
        raise ValueError(
            f"a pool row of {width} features does not hold kv heads of "
            f"{d} that divide the {h} query heads")
    rep = h // kv
    n_pg = block_tables.shape[1]
    bq_t = _fit_q_block(t, BLOCK_Q)
    n_qb = t // bq_t
    g = _kv_heads_per_block(kv, d, bq_t * rep)
    n_grp = kv // g
    lanes = g * d
    live_rows = g * bq_t * rep
    # Whole sublane tiles: 8 rows of 32-bit, 16 of 16-bit (decode is
    # one row per query head otherwise).
    sublanes = 32 // q.dtype.itemsize
    rows = -(-live_rows // sublanes) * sublanes

    # Query head (j*g + i)*rep + r of token iq*bq_t + tt becomes row
    # (i, tt, r) of group j's block iq, on lanes [i*d, (i+1)*d) and
    # zero elsewhere. r runs inside tt as jnp.repeat(axis=2) would in
    # the reference.
    qg = q.reshape(b, n_qb, bq_t, n_grp, g, rep, d)
    qg = qg.transpose(0, 3, 1, 4, 2, 5, 6)
    own = jnp.eye(g, dtype=q.dtype)[:, None, None, :, None]
    qg = (qg[..., None, :] * own).reshape(b, n_grp, n_qb, live_rows, lanes)
    qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, rows - live_rows), (0, 0)))
    qg = qg.reshape(b, n_grp, n_qb * rows, lanes)
    q_start = positions[:, 0].astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    ppb = _pages_per_block(page_size, rows, lanes, k_pages.dtype.itemsize)

    def q_index(b_, j, iq, bt_ref, qs_ref):
        del bt_ref, qs_ref
        return (b_, j, iq, 0)

    # A copy moves whole 128-lane tiles. Mosaic holds a pool whose rows
    # are no multiple of 128 lanes wide (GPT-2 XL's 1600) padded to
    # one, pool and buffer alike, and refuses a DMA of a slice that
    # ends inside the last tile ("Slice shape along dimension 2 must be
    # aligned to tiling"); one that takes the padding along compiles,
    # and nothing reads the padding. The interpreter holds none.
    span = lanes if interpret else _whole_lane_tiles(lanes)
    kernel = functools.partial(
        _paged_kernel, sm_scale=sm_scale, bq_t=bq_t, rep=rep, n_pg=n_pg,
        n_grp=n_grp, n_qb=n_qb, span=span, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_grp, n_qb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, lanes), q_index),
            # The pools stay in HBM, as they are held; the kernel copies
            # the live pages itself.
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, lanes), q_index),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, lanes), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_size, lanes), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (K or V, buffer half)
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, lanes), jnp.float32),
        ],
    )
    kwargs = {}
    if not interpret:
        # The buffer half and the copies in flight are carried from one
        # grid step to the next: every dimension runs in order.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.GridDimensionSemantics.ARBITRARY,) * 3)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
        **kwargs,
    )(block_tables, q_start, qg, k_pages, v_pages)
    # Keep each row's own head's lanes (the diagonal of the two
    # head-in-group axes) and undo the fold.
    out = out.reshape(b, n_grp, n_qb, rows, lanes)[:, :, :, :live_rows]
    out = out.reshape(b, n_grp, n_qb, g, bq_t, rep, g, d)
    out = jnp.einsum("bjqitrid->bjqitrd", out)
    return out.transpose(0, 2, 4, 1, 3, 5, 6).reshape(b, t, h, d)


def paged_attention(q, k_pages, v_pages, block_tables, positions, *,
                    sm_scale=None, force=None, window=None):
    """Attention of queries ``q`` against the paged KV cache.

    Args:
      q: ``[B, T, H, D]`` queries (decode: T=1; chunked prefill: B=1).
      k_pages / v_pages: ``[num_pages, page_size, kv_heads * head_dim]``
        page pools (may be bf16), as ``PagedKVCache`` holds them;
        ``kv_heads`` is the row's width over ``q``'s ``D``.
      block_tables: ``[B, P]`` int page ids per sequence; dead columns
        may hold any valid page id (page 0 scratch by convention).
      positions: ``[B, T]`` absolute position of each query token; a
        token at position p attends slots 0..p.
      sm_scale: softmax scale (default ``head_dim ** -0.5``).
      force: implementation selector (the model config's ``paged_attn``
        field; :func:`resolve_paged_impl`).
      window: a window layer's width in tokens: a token at position p
        attends slots ``p - window < l <= p`` (``window`` of them, its
        own among them). The table's columns left of the page that holds
        slot ``p - window + 1`` of the call's first query are not read
        and may hold anything (the cache names scratch there).

    Returns ``[B, T, H, D]`` in q's dtype.  Rows whose position is
    padding produce well-defined garbage (they attend real slots of
    whatever pages the table names); callers discard them.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    impl = resolve_paged_impl(force)
    positions = positions.astype(jnp.int32)
    if impl == "reference":
        return paged_attention_reference(
            q, k_pages, v_pages, block_tables, positions,
            sm_scale=sm_scale, window=window)
    # Per shard under a mesh: q and the result [B, T, H, D], the pools
    # [pages, page_size, KV * D] (whole heads to a shard), tables and
    # positions [B, ...].
    return per_shard(
        functools.partial(_paged_pallas, sm_scale=sm_scale,
                          interpret=(impl == "interpret"), window=window),
        (q, k_pages, v_pages, block_tables, positions),
        ("b.h.", "..h", "..h", "b.", "b."), "b.h.",
        heads=(k_pages.shape[2] // q.shape[3],))
