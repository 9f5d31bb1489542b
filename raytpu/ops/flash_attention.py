"""Flash attention as three Pallas TPU kernels: forward, dq and dk/dv.

Blockwise-stable softmax with O(T) memory; the backward recomputes the
scores from the residuals (q, k, v, o, lse), so the [T, T] matrix never
reaches HBM. All three work on one kind of tile: **keys down the sublanes,
queries along the lanes** (the scores' transpose). In that orientation a
query's statistics (running max and sum, lse, delta) are a lane-major row
that broadcasts down the sublanes for nothing, the max and the sum over
keys are plain vector maxima and additions with one sublane reduction per
128 queries at the end, the accumulators ``[D, Bq]`` fill their lanes at
``head_dim`` 64, and lse and delta travel as ``f32[bh, 1, T]``: 4 bytes a
row. With queries down the sublanes (as this file had it until PR 33) a
tile of 512 x 512 paid 64 cross-lane reductions for each of the max and
the sum, and they, not the products, were most of the forward
(PERF.md §6, PR 33: 2.00 ms a call at [256, 1024, 64], 1.26 with the max
and sum left out, 0.66 now).

A grid step holds a block of one side (queries in the forward and in dq,
keys in dk/dv) and walks the other in sub-blocks, in rolled loops whose
bounds are the causal limit's (and the window's, forward): a sub-block no
query sees is not visited, one every query sees whole takes a body with
no mask, and only those the diagonal or the window's edge crosses are
masked. Where the diagonal runs from corner to corner of the held block
(equal lengths, no window: every training call and every whole-prompt
prefill) the tiles it crosses are walked unrolled, in chunks of keys that
each take the queries from their own first on, so the corner no query
sees is not computed (`_diagonal_chunk`). The walked side arrives a major
block at a time along the innermost grid dimension (the whole of it up to
`_MAJOR_ROWS`), double-buffered by the pipeline; a major block no query
sees is neither fetched nor visited. A grid axis of one step hands the
kernel a Python 0 for its index, so a call of one block (a short prefill)
knows its walk when it is traced and is one tile with no loop beside it:
a serving program traces and lowers the forward once a layer, and
set-up pays for every line of its body 48 times a program in GPT-2 XL
(PERF.md §6, PR 33). ``sm_scale`` multiplies q (k in
dk/dv) once a block where that is exact, a power of two, and the float32
scores otherwise; dq and dk take it on their ``[D, B]`` result. Products
feed the MXU in the operand type with float32 accumulation; ``exp`` and
the statistics are float32.

The tile's sizes are `_TILES`: what the chip chose (benchmarks/
sweep_attn.py, PERF.md §6), fitted to the lengths by `_fit_block`. Off a
TPU the reference einsum formulation runs instead (tests compare the
kernels in interpret mode against it).

Cross-length causal (t_q != t_kv) uses a bottom-aligned diagonal
(``tril(k=t_kv-t_q)``, matching the reference path). For t_q > t_kv the
leading rows attend nothing; the kernels output 0 for those rows while
the einsum path degenerates to uniform attention — both are artifacts of
an ill-defined case (a softmax over zero elements).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec


# Rows of q and of k in a tile of scores where a test or
# benchmarks/sweep_attn.py overrides the table below; None, the table.
DEFAULT_BLOCK_Q: Optional[int] = None
DEFAULT_BLOCK_K: Optional[int] = None

# kernel: (rows of q, rows of k, rows of keys in a chunk on the diagonal),
# as the chip chose them at [256, 1024, 64], [100, 1024, 64] and the
# prefills' [25, 512, 64], [16, 256, 128], [16, 1280, 128] (PERF.md, PR 33:
# every kernel was fastest holding 1,024 rows, which at T = 1,024 leaves
# no tile off the diagonal, and each at its own chunk). One row: no length
# or head size measured wanted another.
_TILES = {"fwd": (1024, 512, 512), "dq": (1024, 512, 256),
          "dkv": (512, 1024, 128)}


def _tile(kernel: str, block_q=None, block_k=None):
    """``(rows of q, rows of k, rows of a chunk on the diagonal)`` of a
    tile of scores in ``kernel`` ("fwd", "dq" or "dkv"), before
    :func:`_fit_block` fits them to the lengths. In the forward and in dq
    the rows of q are the block a grid step holds and the rows of k the
    sub-block it walks the keys in; in dk/dv the other way round. The
    lengths and the head size are no arguments: `_TILES` has one row."""
    want_q, want_k, chunk = _TILES[kernel]
    return block_q or want_q, block_k or want_k, chunk


def _fit_block(t: int, want: int, interpret: bool) -> int:
    """Largest block <= ``want`` that exactly tiles ``t`` (trace-time).

    Keeps arbitrary sequence lengths working under large default tiles
    (e.g. t=768 with 512 defaults tiles at 384). On hardware the block
    must also be 8-row sublane-aligned — Mosaic mis-handles odd block
    heights — so a ``t`` with no aligned divisor (e.g. t=300, t=50, or
    any prime t > 8) raises; an explicit block override < 64 lowers the
    economic floor to 8, and interpret mode (CPU tests) accepts any
    divisor. Callers hitting the error should use force='reference' or
    pad the sequence.
    """
    floor = 64 if want >= 64 else 8  # honor explicit small overrides
    want = min(want, t)
    if interpret:
        def ok(b):  # single-block, or any non-degenerate divisor
            return b == t or b >= 8
    else:
        def ok(b):  # sublane-aligned; full-sequence block also allowed
            return b % 8 == 0 and (b >= floor or b == t)
    while want > 1 and (t % want or not ok(want)):
        want -= 1
    if not ok(want) or t % want:
        raise ValueError(
            f"no sublane-aligned pallas block (>= {floor}, %8 == 0) tiles "
            f"sequence length {t}; use force='reference' or pad the "
            f"sequence")
    return want


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def per_shard(fn, args, in_layouts, out_layouts, *, heads=()):
    """Call ``fn(*args)``, a function built on Mosaic kernels, from a
    program sharded over the mesh set with ``jax.set_mesh``.

    XLA cannot partition a Mosaic kernel, so under a mesh the call runs
    once per shard (``shard_map``) over the axes this repository shards
    activations on (``raytpu.parallel.sharding``): the batch over
    ``dp``/``fsdp`` and the heads over ``tp``, wherever those divide the
    sizes. Every other dimension, and every other mesh axis, is
    replicated. A layout is one character per dimension of an array:
    ``b`` batch, ``h`` heads, ``.`` neither (``"bh.."`` is
    ``[B, H, T, D]``). ``out_layouts`` mirrors ``fn``'s result. With no
    mesh set, or inside a ``shard_map`` already, ``fn`` is called as is.
    ``heads`` are head counts that ``tp`` must divide as well: of an
    array whose ``h`` dimension holds each head's features side by side
    (a KV pool's rows), so that a shard takes whole heads.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return fn(*args)
    size = dict(mesh.shape)
    axes = {"b": tuple(a for a in ("dp", "fsdp") if size.get(a, 1) > 1),
            "h": ("tp",) if size.get("tp", 1) > 1 else (), ".": ()}
    for x, layout in zip(args, in_layouts):
        for dim, kind in enumerate(layout):
            if x.shape[dim] % math.prod(size[a] for a in axes[kind]):
                axes[kind] = ()
    if any(n % math.prod(size[a] for a in axes["h"]) for n in heads):
        axes["h"] = ()

    def spec(layout):
        return PartitionSpec(*(axes[kind] or None for kind in layout))

    return jax.shard_map(
        fn, in_specs=tuple(spec(l) for l in in_layouts),
        out_specs=jax.tree.map(spec, out_layouts), check_vma=False)(*args)


# -- reference path (also the backward) --------------------------------------


def _causal_mask(t_q: int, t_k: int, window=None):
    """Bottom-aligned causal mask [t_q, t_k]; with a ``window`` a query
    sees the ``window`` newest keys up to its own, and no older one."""
    mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
    if window is not None:
        mask &= ~jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q - window)
    return mask


def _attn_fwd_reference(q, k, v, causal: bool, sm_scale: float,
                        window=None, kv_len=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = _causal_mask(q.shape[2], k.shape[2], window)
        s = jnp.where(mask[None, None], s, -1e30)
    if kv_len is not None:
        s = jnp.where(jnp.arange(k.shape[2]) < kv_len, s, -1e30)
    lse = jax.scipy.special.logsumexp(s, axis=-1, keepdims=True)
    p = jnp.exp(s - lse)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


def _attn_bwd_reference(q, k, v, o, lse, g, causal: bool, sm_scale: float,
                        window=None):
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    gf = g.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * sm_scale
    if causal:
        mask = _causal_mask(q.shape[2], k.shape[2], window)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jnp.exp(s - lse)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
    delta = jnp.sum(gf * o.astype(jnp.float32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# -- pallas kernels -----------------------------------------------------------
#
# The module docstring says what a grid step holds and walks. Every tile
# below is [keys, queries]: keys down the sublanes, queries along the lanes.

_LANES = 128
_MASKED = -1e30
# Rows of the walked side that a grid step holds in VMEM (twice over, the
# pipeline's two buffers): 2 MB of K and V at head_dim 128.
_MAJOR_ROWS = 2048


def _walk_blocks(t: int, want: int, interpret: bool):
    """``(sub, major)``: the sub-block the kernels walk ``t`` rows in, and
    how many rows of it a grid step holds. A sub-block that is no whole
    number of 128-lane tiles cannot be sliced out of a longer block (its
    row statistics lie along the lanes), so it is a major block alone."""
    sub = _fit_block(t, want, interpret)
    n = t // sub
    if sub % _LANES:
        return sub, sub
    count = max(c for c in range(1, n + 1)
                if n % c == 0 and (c == 1 or sub * c <= _MAJOR_ROWS))
    return sub, sub * count


def _stat_lanes(block: int, n_blocks: int) -> int:
    """Lanes one block's row statistics take in ``f32[bh, 1, ·]``: the
    block's rows, padded to whole tiles where Mosaic could not address a
    block of them otherwise."""
    if n_blocks == 1 or block % _LANES == 0:
        return block
    return -(-block // _LANES) * _LANES


def _stat_rows(x, block: int):
    """``[bh, T]`` row statistics as the kernels take them: one lane-major
    row a head, ``[bh, 1, ·]``, a slot of :func:`_stat_lanes` a block."""
    bh, t = x.shape
    n = t // block
    lanes = _stat_lanes(block, n)
    if lanes != block:
        x = jnp.pad(x.reshape(bh, n, block),
                    ((0, 0), (0, 0), (0, lanes - block)))
    return x.reshape(bh, 1, n * lanes)


def _clip(x, lo, hi):
    """``x`` held to ``[lo, hi]``: a Python int where all three are (a grid
    of one step knows its bounds when it is traced), else traced."""
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _seen_keys(q_start, block_q: int, sub: int, first, count: int, off: int,
               causal: bool, window):
    """Of the ``count`` sub-blocks of keys from ``first``, which a block of
    query rows walks: some row sees a key of ``[lo, hi)``, and every row
    sees all of ``[whole_lo, whole_hi)``. The diagonal is bottom-aligned
    (``off = t_kv - t_q``, the reference's ``tril(k=off)``)."""
    last = first + count
    if not causal:
        return first, first, last, last
    top = q_start + off  # the newest key the first row sees
    bot = top + block_q - 1  # and the last row
    hi = _clip(bot // sub + 1, first, last)
    if window is None:
        lo = whole_lo = first
    else:
        lo = _clip((top - window + 1) // sub, first, hi)
        whole_lo = _clip((bot - window) // sub + 1, lo, hi)
    whole_hi = _clip((top + 1) // sub, whole_lo, hi)
    return lo, whole_lo, whole_hi, hi


def _seeing_queries(k_start, block_k: int, sub: int, first, count: int,
                    off: int, causal: bool):
    """The mirror of :func:`_seen_keys` for a block of keys: of the
    sub-blocks of query rows from ``first``, those from ``lo`` on hold a
    row that sees a key of the block, and from ``whole_lo`` on every row
    sees them all."""
    last = first + count
    if not causal:
        return first, first, last
    lo = _clip((k_start - off) // sub, first, last)
    whole_lo = _clip((k_start + block_k - 2 - off) // sub + 1, lo, last)
    return lo, whole_lo, last


def _diagonal_chunk(want: int, block: int, sub: int, off: int,
                    one_major: bool, causal: bool, window=None) -> int:
    """Rows of a chunk the tiles on the diagonal are cut into, or 0. Where
    the diagonal runs from corner to corner of every held block (equal
    lengths, whole sub-blocks to a block, the walked side one major block,
    no window), the tiles it crosses lie the same in every block, so they
    are walked unrolled, in chunks of keys of which each takes the queries
    from its own first on: the corner no query sees is not computed."""
    chunk = min(want, sub)
    if (not causal or window is not None or not one_major or off
            or block % sub or chunk % _LANES or sub % chunk
            or block // chunk > 8):
        return 0
    return chunk


def _grid_index(axis: int, steps: int):
    """This grid step's index along ``axis``: a Python 0 where the axis has
    one step, so that a call of one block (a short prefill; T = 1,024 under
    the table) knows its walk when it is traced and holds no loop that
    would not run and no slice at a computed row."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    return 0 if steps == 1 else pl.program_id(axis)


def _when(cond):
    """``pl.when``, decided at trace time where ``cond`` is a Python bool."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    if isinstance(cond, bool):
        return lambda body: body() if cond else None
    return pl.when(cond)


def _loop(lo, hi, body):
    """A rolled loop over ``[lo, hi)``; none where it is empty at trace
    time."""
    if isinstance(lo, int) and isinstance(hi, int) and hi <= lo:
        return
    lax.fori_loop(lo, hi, body, 0)


def _rows(start, size: int, multiple: Optional[int] = None):
    """``size`` rows from ``start``, a multiple of ``multiple`` (of
    ``size`` if none is given)."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    if isinstance(start, int):
        return pl.ds(start, size)
    return pl.ds(pl.multiple_of(start, multiple or size), size)


def _sub_rows(i, sub: int, n_sub: int):
    """The ``i``-th sub-block of a major block's rows."""
    return _rows(0 if n_sub == 1 else i * sub, sub)


def _key_less_query(keys: int, queries: int):
    """A tile's key row less its query column, ``[keys, queries]``."""
    return (lax.broadcasted_iota(jnp.int32, (keys, queries), 0)
            - lax.broadcasted_iota(jnp.int32, (keys, queries), 1))


def _mask(s, rel, limit, window):
    """Scores a query does not see, to -1e30, in a tile of keys down the
    sublanes and queries along the lanes. ``rel`` is
    :func:`_key_less_query` and ``limit`` the largest a query sees: its
    place in the tile, plus where the tile's queries start on the
    (bottom-aligned) diagonal, less where its keys start."""
    seen = rel <= limit
    if window is not None:
        seen = jnp.logical_and(seen, rel > limit - window)
    return jnp.where(seen, s, _MASKED)


def _dot(a, b, contract):
    return lax.dot_general(a, b, ((contract, ((), ()))),
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _walk_keys(tile, q_start, block_q: int, sub: int, n_sub: int, first,
               chunk: int, off: int, causal: bool, window=None, kv_len=None):
    """The forward's and dq's walk: ``tile(rows, limit, masked, q0)`` over
    the sub-blocks of keys a held block of queries sees in this major
    block. Rolled loops, one body masked and one not; the diagonal's
    tiles unrolled in chunks where :func:`_diagonal_chunk` allows.
    ``kv_len`` (not causal: a traced scalar): the keys from it on are
    none. A sub-block past it is not visited, the one it crosses is
    masked, and ``limit`` is then the last live key's row in the tile."""
    lo, whole_lo, whole_hi, hi = _seen_keys(
        q_start, block_q, sub, first, n_sub, off, causal, window)
    if kv_len is not None:
        whole_hi = jnp.clip(kv_len // sub, first, hi)
        hi = jnp.clip((kv_len + sub - 1) // sub, first, hi)

    def step(j, carry, masked):
        limit = q_start + off if kv_len is None else kv_len - 1
        tile(_sub_rows(j - first, sub, n_sub), limit - j * sub, masked, 0)
        return carry

    if window is not None:
        _loop(lo, whole_lo, functools.partial(step, masked=True))
    _loop(whole_lo, whole_hi, functools.partial(step, masked=False))
    if chunk:
        for q0 in range(0, block_q, chunk):
            tile(_rows(q_start + off + q0, chunk), 0, True, q0)
    elif causal or kv_len is not None:
        _loop(whole_hi, hi, functools.partial(step, masked=True))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, causal: bool, sm_scale: float, scale_q: bool, sub: int,
                  chunk: int, off: int, steps, window=None, kv_len=None):
    """``steps``: the grid's steps along its last two axes (blocks of
    queries, major blocks of keys), in all three kernels. ``kv_len``:
    as :func:`_walk_keys` takes it."""
    block_q, d = q_ref.shape[1:]
    n_sub = k_ref.shape[1] // sub
    iq = _grid_index(1, steps[0])
    ik = _grid_index(2, steps[1])

    @_when(ik == 0)
    def _init():
        m_scr[...] = jnp.full((1, block_q), _MASKED, jnp.float32)
        l_scr[...] = jnp.zeros((1, block_q), jnp.float32)
        acc_scr[...] = jnp.zeros((d, block_q), jnp.float32)

    # The MXU is fed in the residual dtype (bf16 in, fp32 accumulate).
    mxu = q_ref.dtype
    q = q_ref[0].astype(mxu)  # [Bq, D]
    if scale_q:
        q = q * sm_scale
    q_start = iq * block_q
    if kv_len is not None:  # a key's row alone decides: no diagonal
        rel = lax.broadcasted_iota(jnp.int32, (sub, block_q), 0)
    else:
        rel = _key_less_query(sub, block_q) if causal else None

    def tile(rows, limit, masked, q0):
        """The keys in ``rows`` of the major block against the queries
        from ``q0`` on; ``limit`` as :func:`_mask` takes it."""
        lanes = slice(q0, block_q)
        kb = k_ref[0, rows, :].astype(mxu)  # [Sk, D]
        vb = v_ref[0, rows, :].astype(mxu)
        s = _dot(kb, q[q0:], _NT)  # [Sk, Bq - q0]
        if not scale_q:
            s = s * sm_scale
        if masked:
            s = _mask(s, rel[:s.shape[0], :s.shape[1]], limit, window)
        m_prev = m_scr[:, lanes]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        acc_scr[:, lanes] = (acc_scr[:, lanes] * corr
                             + _dot(vb, p.astype(mxu), _TN))
        l_scr[:, lanes] = (l_scr[:, lanes] * corr
                           + jnp.sum(p, axis=0, keepdims=True))
        m_scr[:, lanes] = m_new

    _walk_keys(tile, q_start, block_q, sub, n_sub, ik * n_sub, chunk, off,
               causal, window, kv_len)

    @_when(ik == steps[1] - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).T.astype(o_ref.dtype)
        lse_ref[0, :, :block_q] = m_scr[...] + jnp.log(l)


def _flash_kernel_to(len_ref, *refs, **kw):
    """:func:`_flash_kernel` with a key limit, the scalar prefetched."""
    _flash_kernel(*refs, kv_len=len_ref[0], **kw)


def _walked_index(block: int, major: int, n_major: int, off: int,
                  causal: bool, window=None):
    """Index map of the walked side's major blocks under a grid of
    ``(bh, blocks of the held side, major blocks)``, for a held block of
    ``block`` query rows. A major block no row sees is not fetched: its
    grid step re-references the nearest one that is seen, which the
    pipeline already holds."""
    def index(ib, iq, ik):
        if causal:
            last = (iq * block + block - 1 + off) // major
            ik = jnp.minimum(ik, jnp.clip(last, 0, n_major - 1))
        if window is not None:
            first = (iq * block + off - window + 1) // major
            ik = jnp.maximum(ik, jnp.clip(first, 0, n_major - 1))
        return (ib, ik, 0)
    return index


def _compiler(interpret: bool):
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=(
        pltpu.GridDimensionSemantics.PARALLEL,
        pltpu.GridDimensionSemantics.PARALLEL,
        pltpu.GridDimensionSemantics.ARBITRARY))}


def _scale_on_operand(sm_scale: float) -> bool:
    """A power of two scales q (or k) exactly in any float type; any
    other scale stays a float32 multiply of the scores."""
    return math.frexp(sm_scale)[0] == 0.5


def _flash_forward_pallas(q, k, v, causal: bool, sm_scale: float,
                          block_q, block_k, interpret: bool, window=None,
                          kv_len=None):
    """``(o [B, H, T, D], lse [B, H, T])``. ``kv_len`` (an int32 scalar,
    not causal): only the first ``kv_len`` keys are keys; it reaches the
    kernel as a prefetched scalar, and without it the call is the one it
    was."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    bh = b * h
    want_q, want_k, chunk = _tile("fwd", block_q, block_k)
    block_q = _fit_block(t_q, want_q, interpret)
    sub, major = _walk_blocks(t_kv, want_k, interpret)
    n_qb = t_q // block_q
    n_major = t_kv // major
    lanes = _stat_lanes(block_q, n_qb)
    off = t_kv - t_q  # bottom-aligned diagonal (reference tril k=off)

    def held(ib, iq, ik, *_):
        return (ib, iq, 0)

    walked = _walked_index(block_q, major, n_major, off, causal, window)
    if kv_len is not None:
        every = walked

        def walked(ib, iq, ik, len_ref):  # a major block past it: not fetched
            last = jnp.maximum(len_ref[0] - 1, 0) // major
            return every(ib, iq, jnp.minimum(ik, last))

    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale,
        scale_q=_scale_on_operand(sm_scale), sub=sub,
        chunk=_diagonal_chunk(chunk, block_q, sub, off, n_major == 1,
                              causal, window),
        off=off, steps=(n_qb, n_major), window=window)
    grid = dict(
        grid=(bh, n_qb, n_major),
        in_specs=[
            pl.BlockSpec((1, block_q, d), held),
            pl.BlockSpec((1, major, d), walked),
            pl.BlockSpec((1, major, d), walked),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), held),
            pl.BlockSpec((1, 1, lanes), lambda ib, iq, ik, *_: (ib, 0, iq)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((d, block_q), jnp.float32),
        ])
    operands = (q.reshape(bh, t_q, d), k.reshape(bh, t_kv, d),
                v.reshape(bh, t_kv, d))
    if kv_len is not None:
        kernel = functools.partial(_flash_kernel_to, **kernel.keywords)
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **grid))
        operands = (jnp.asarray(kv_len, jnp.int32).reshape(1),) + operands
    o3, lse3 = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, n_qb * lanes), jnp.float32),
        ],
        interpret=interpret,
        **grid,
        **_compiler(interpret),
    )(*operands)
    if lanes != block_q:
        lse3 = lse3.reshape(bh, n_qb, lanes)[:, :, :block_q]
    return o3.reshape(b, h, t_q, d), lse3.reshape(b, h, t_q)


# -- pallas backward kernels --------------------------------------------------
#
# Flash-style recompute from (q, k, v, lse) and delta = sum(g * o): no
# running max. lse and delta arrive as lane-major rows, which is how the
# [keys, queries] tile takes them; dq accumulates transposed, [D, Bq], and
# is turned over once a block.


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal: bool, sm_scale: float,
                         scale_q: bool, sub: int, chunk: int, off: int,
                         steps):
    block_q, d = q_ref.shape[1:]
    n_sub = k_ref.shape[1] // sub
    iq = _grid_index(1, steps[0])
    ik = _grid_index(2, steps[1])

    @_when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros((d, block_q), jnp.float32)

    mxu = q_ref.dtype
    q = q_ref[0].astype(mxu)
    if scale_q:
        q = q * sm_scale
    g = g_ref[0].astype(mxu)
    q_start = iq * block_q
    rel = _key_less_query(sub, block_q) if causal else None

    def tile(rows, limit, masked, q0):
        lanes = slice(q0, block_q)
        kb = k_ref[0, rows, :].astype(mxu)
        vb = v_ref[0, rows, :].astype(mxu)
        s = _dot(kb, q[q0:], _NT)  # [Sk, Bq - q0]
        if not scale_q:
            s = s * sm_scale
        if masked:
            s = _mask(s, rel[:s.shape[0], :s.shape[1]], limit, None)
        p = jnp.exp(s - lse_ref[0, :, lanes])  # a row, [1, Bq - q0]
        ds = p * (_dot(vb, g[q0:], _NT) - delta_ref[0, :, lanes])
        dq_scr[:, lanes] += _dot(kb, ds.astype(mxu), _TN)  # [D, Bq - q0]

    _walk_keys(tile, q_start, block_q, sub, n_sub, ik * n_sub, chunk, off,
               causal)

    @_when(ik == steps[1] - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * sm_scale).T.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                          sm_scale: float, scale_k: bool, sub: int, chunk: int,
                          off: int, steps):
    block_k, d = k_ref.shape[1:]
    n_sub = q_ref.shape[1] // sub
    ik = _grid_index(1, steps[0])
    iq = _grid_index(2, steps[1])

    @_when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros((block_k, d), jnp.float32)
        dv_scr[...] = jnp.zeros((block_k, d), jnp.float32)

    mxu = q_ref.dtype
    kb = k_ref[0].astype(mxu)  # [Bk, D]
    if scale_k:
        kb = kb * sm_scale
    vb = v_ref[0].astype(mxu)
    k_start = ik * block_k
    first = iq * n_sub
    lo, whole_lo, last = _seeing_queries(
        k_start, block_k, sub, first, n_sub, off, causal)
    rel = _key_less_query(block_k, max(sub, block_k)) if causal else None

    def tile(rows, limit, masked, k0=0, k1=block_k):
        """The keys ``k0:k1`` of the block against the queries in ``rows``
        of the major block."""
        q = q_ref[0, rows, :].astype(mxu)  # [Sq, D]
        g = g_ref[0, rows, :].astype(mxu)
        s = _dot(kb[k0:k1], q, _NT)  # [k1 - k0, Sq]: the scores' transpose
        if not scale_k:
            s = s * sm_scale
        if masked:
            s = _mask(s, rel[:s.shape[0], :s.shape[1]], limit, None)
        p = jnp.exp(s - lse_ref[0, :, rows])
        dv_scr[k0:k1] += _dot(p.astype(mxu), g, _NN)
        ds = p * (_dot(vb[k0:k1], g, _NT) - delta_ref[0, :, rows])
        dk_scr[k0:k1] += _dot(ds.astype(mxu), q, _NN)

    def step(i, carry, masked):
        tile(_sub_rows(i - first, sub, n_sub), i * sub + off - k_start,
             masked)
        return carry

    if chunk:
        for k0 in range(0, block_k, chunk):
            tile(_rows(k_start - off + k0, block_k - k0, chunk), 0, True,
                 k0, k0 + chunk)
    elif causal:
        _loop(lo, whole_lo, functools.partial(step, masked=True))
    _loop(whole_lo, last, functools.partial(step, masked=False))

    @_when(iq == steps[1] - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward_pallas(q, k, v, o, lse, g, causal: bool, sm_scale: float,
                           block_q, block_k, interpret: bool):
    """``lse`` is ``[B, H, T]``, as the forward returns it."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    bh = b * h
    off = t_kv - t_q  # bottom-aligned diagonal (reference tril k=off)
    q3 = q.reshape(bh, t_q, d)
    k3 = k.reshape(bh, t_kv, d)
    v3 = v.reshape(bh, t_kv, d)
    g3 = g.reshape(bh, t_q, d)
    lse2 = lse.reshape(bh, t_q).astype(jnp.float32)
    delta2 = jnp.sum(g3.astype(jnp.float32) * o.reshape(bh, t_q, d).astype(
        jnp.float32), axis=-1)
    scale_on_operand = _scale_on_operand(sm_scale)

    # dq: a block of query rows walks the keys, as the forward does.
    want_q, want_k, chunk = _tile("dq", block_q, block_k)
    held_q = _fit_block(t_q, want_q, interpret)
    sub, major = _walk_blocks(t_kv, want_k, interpret)
    n_held = t_q // held_q
    lanes = _stat_lanes(held_q, n_held)

    def held(ib, iq, ik):
        return (ib, iq, 0)

    walked = _walked_index(held_q, major, t_kv // major, off, causal)
    held_spec = pl.BlockSpec((1, held_q, d), held)
    walked_spec = pl.BlockSpec((1, major, d), walked)
    stat_spec = pl.BlockSpec((1, 1, lanes), lambda ib, iq, ik: (ib, 0, iq))
    dq3 = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
            scale_q=scale_on_operand, sub=sub,
            chunk=_diagonal_chunk(chunk, held_q, sub, off, t_kv == major,
                                  causal),
            off=off, steps=(n_held, t_kv // major)),
        grid=(bh, n_held, t_kv // major),
        in_specs=[held_spec, walked_spec, walked_spec, held_spec,
                  stat_spec, stat_spec],
        out_specs=held_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((d, held_q), jnp.float32)],
        interpret=interpret,
        **_compiler(interpret),
    )(q3, k3, v3, g3, _stat_rows(lse2, held_q), _stat_rows(delta2, held_q))

    # dk/dv: a block of keys walks the query rows that see it.
    want_q, want_k, chunk = _tile("dkv", block_q, block_k)
    held_k = _fit_block(t_kv, want_k, interpret)
    sub, major = _walk_blocks(t_q, want_q, interpret)
    n_major = t_q // major

    def walked(ib, ik, iq):
        if causal:  # as _walked_index: rows above the block see none of it
            first = (ik * held_k - off) // major
            iq = jnp.maximum(iq, jnp.clip(first, 0, n_major - 1))
        return (ib, iq, 0)

    def held(ib, ik, iq):
        return (ib, ik, 0)

    held_spec = pl.BlockSpec((1, held_k, d), held)
    walked_spec = pl.BlockSpec((1, major, d), walked)
    stat_spec = pl.BlockSpec(
        (1, 1, _stat_lanes(major, n_major)),
        lambda ib, ik, iq: (ib, 0, walked(ib, ik, iq)[1]))
    dk3, dv3 = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, causal=causal, sm_scale=sm_scale,
            scale_k=scale_on_operand, sub=sub,
            chunk=_diagonal_chunk(chunk, held_k, sub, off, n_major == 1,
                                  causal),
            off=off, steps=(t_kv // held_k, n_major)),
        grid=(bh, t_kv // held_k, n_major),
        in_specs=[walked_spec, held_spec, held_spec, walked_spec,
                  stat_spec, stat_spec],
        out_specs=[held_spec, held_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((held_k, d), jnp.float32),
            pltpu.VMEM((held_k, d), jnp.float32),
        ],
        interpret=interpret,
        **_compiler(interpret),
    )(q3, k3, v3, g3, _stat_rows(lse2, major), _stat_rows(delta2, major))

    return (dq3.reshape(b, h, t_q, d),
            dk3.reshape(b, h, t_kv, d),
            dv3.reshape(b, h, t_kv, d))


# -- public op with custom vjp ------------------------------------------------

_BHTD = "bh.."  # per_shard layout of q/k/v/o/g
_BHT = "bh."  # and of lse [B, H, T]


# What a block under ``jax.checkpoint`` keeps of this op
# (``raytpu.models.gpt2.remat_block`` saves these names and nothing
# else): the five arrays ``_flash_bwd`` takes. With them saved the
# backward of a layer runs neither the forward kernel nor the projection
# and transposes that made q, k and v a second time.
RESIDUAL_NAMES = ("flash_q", "flash_k", "flash_v", "flash_o", "flash_lse")


def _flash_run(q, k, v, causal, sm_scale, use_pallas, window, kv_len=None,
               blocks=None):
    """``(o, lse [B, H, T])`` by the implementation ``use_pallas``.
    ``blocks``: ``(DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)`` as a caller read
    them that is traced once for many calls; None: as they stand now."""
    if use_pallas in ("tpu", "interpret"):
        block_q, block_k = blocks or (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
        fwd = functools.partial(
            _flash_forward_pallas, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k,
            interpret=(use_pallas == "interpret"), window=window)
        if kv_len is None:
            return per_shard(fwd, (q, k, v), (_BHTD,) * 3, (_BHTD, _BHT))
        return per_shard(
            lambda q, k, v, n: fwd(q, k, v, kv_len=n), (q, k, v, kv_len),
            (_BHTD,) * 3 + ("",), (_BHTD, _BHT))
    o, lse = _attn_fwd_reference(q, k, v, causal, sm_scale, window, kv_len)
    return o, lse[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, use_pallas, window=None):
    return _flash_run(q, k, v, causal, sm_scale, use_pallas, window)[0]


def _flash_fwd(q, k, v, causal, sm_scale, use_pallas, window=None):
    o, lse = _flash_run(q, k, v, causal, sm_scale, use_pallas, window)
    # Named here, inside the rule and after ``per_shard`` returned the
    # global arrays: a name on the layer's output alone would leave lse
    # unsaved and the kernel would run again. Outside a checkpoint a
    # name is the identity.
    q, k, v, o, lse = (checkpoint_name(x, name) for x, name
                       in zip((q, k, v, o, lse), RESIDUAL_NAMES))
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, use_pallas, window, res, g):
    q, k, v, o, lse = res
    if window is None and use_pallas in ("tpu", "interpret"):
        return per_shard(
            functools.partial(
                _flash_backward_pallas, causal=causal, sm_scale=sm_scale,
                block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                interpret=(use_pallas == "interpret")),
            (q, k, v, o, lse, g), (_BHTD,) * 4 + (_BHT, _BHTD), (_BHTD,) * 3)
    # The two backward kernels know no window yet: a windowed backward
    # takes the masked reference whatever ran forward (the saved
    # log-sum-exp is the same quantity on either path).
    return _attn_bwd_reference(q, k, v, o, lse[..., None], g, causal,
                               sm_scale, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def resolve_flash_impl(force: Optional[str] = None) -> str:
    """The implementation ``flash_attention(force=...)`` runs: ``"tpu"``
    (the compiled Pallas kernels), ``"interpret"`` (the same kernels in
    the Pallas interpreter — tests) or ``"reference"``. ``None`` is the
    kernels on a TPU and the reference elsewhere."""
    if force is None:
        return "tpu" if _on_tpu() else "reference"
    return {"tpu": "tpu", "interpret": "interpret",
            "reference": "reference"}[force]


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    force: Optional[str] = None,
                    window: Optional[int] = None):
    """Flash attention on [B, H, T, D]; ``force`` as in
    :func:`resolve_flash_impl`. ``window`` (causal only): a query sees the
    ``window`` newest keys up to its own. The forward kernel skips and
    masks by it; the backward kernels do not know it, so a windowed
    backward runs the masked reference (O(T^2) scores: no windowed model
    is trained at a length where that matters yet)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("a window is a causal layer's: causal=False")
    return _flash(q, k, v, causal, sm_scale, resolve_flash_impl(force),
                  window)


def flash_attention_part(q, k, v, *, causal: bool, sm_scale: float,
                         force: Optional[str] = None, kv_len=None):
    """The forward alone, for a caller that attends its keys a part at a
    time: ``(o [B, H, T, D], lse float32 [B, H, T])``, the part's
    attention and the log of its softmax's sum, by which
    :func:`merge_parts` weighs it against the others. ``kv_len`` (an
    int32 scalar, traced or not; not causal): of ``k`` and ``v`` only the
    first ``kv_len`` rows are keys; the rest enter no softmax, and a
    part with none has an ``lse`` of -1e30, at which the merge gives it
    no weight. No gradient is defined."""
    if kv_len is not None and causal:
        raise ValueError("a key limit is a part's before the diagonal: "
                         "causal=False")
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len, jnp.int32)
    return _flash_part(q, k, v, kv_len, causal=causal,
                       sm_scale=float(sm_scale),
                       impl=resolve_flash_impl(force),
                       blocks=(DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K))


# Jitted: a program that attends in parts calls this once a part a layer,
# and traces and lowers the kernel once a signature, not once a call
# (`setup_s`: a chunk program of 12 layers holds 24 calls of two kinds).
@functools.partial(jax.jit,
                   static_argnames=("causal", "sm_scale", "impl", "blocks"))
def _flash_part(q, k, v, kv_len, *, causal, sm_scale, impl, blocks):
    return _flash_run(q, k, v, causal, sm_scale, impl, None, kv_len, blocks)


def merge_parts(parts):
    """One softmax over the keys of all ``parts``, each ``(o [..., T, D],
    lse [..., T])`` as :func:`flash_attention_part` gives them: ``(o
    float32, lse)``, the parts weighed by their sums in float32."""
    (o, lse), *more = parts
    o = o.astype(jnp.float32)
    for o_part, lse_part in more:
        both = jnp.logaddexp(lse, lse_part)
        o = (o * jnp.exp(lse - both)[..., None]
             + o_part.astype(jnp.float32)
             * jnp.exp(lse_part - both)[..., None])
        lse = both
    return o, lse
