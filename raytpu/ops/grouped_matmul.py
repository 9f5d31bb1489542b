"""Grouped matrix products for the routed-expert layer, as a Pallas TPU
kernel beside ``jax.lax.ragged_dot``.

Rows ``[M, K]`` sorted by expert, matrices ``[E, K, N]`` and the int32
``tokens[E]`` rows each expert received give ``[M, N]``: group ``i``'s
rows times matrix ``i``. Rows past the last group (padding, another
chip's experts) belong to none and come out zero. Operands multiply as
given (bf16 in serving), every sum is float32, and the result is rounded
once: ``ragged_dot``'s arithmetic. :func:`grouped_swiglu` is the layer's
first two products in one pass over the rows, ``silu(rows @ wg) *
(rows @ wi)`` from the float32 sums.

Why a kernel: a decode step multiplies a few rows an expert (256 rows
over 64 experts in Mellum2), so each product is worth its matrix's bytes
and nothing else, and the TPU compiler's ``ragged_dot`` read Mellum2's
``[2304, 896]`` experts at a fifth of the HBM's rate (PERF.md, PR 32-35).
Here the row axis is cut at every tile and every group boundary into
*visits*, one (row tile, expert) pair each in row order (the walk of
``jax.experimental.pallas.ops.tpu.megablox``); the visit's expert is
scalar-prefetched into the matrices' index map, so the pipeline streams
an expert's matrix once and an expert no row chose is never fetched. A
group that straddles two row tiles makes two consecutive visits of one
block, which the pipeline does not fetch again; the dead rows are a last
group of their own, stored as zeros and multiplied by nothing.

An expert whose matrices fit the buffers is streamed whole and
contiguous. One that does not (K-EXAONE's ``[6144, 2048]``) is cut along
N into blocks of whole lanes, the widest that divide N and fit
(:func:`_block_width`): the grid is (blocks, visits) with the visits
innermost, so each block of each touched expert is still streamed once,
a tile's consecutive visits still find its output block where they left
it, and the rows are read once a block. Gate and up are cut at the same
columns and no sum crosses a block, so the arithmetic is the whole
expert's. There are no blocks along K: they would need an accumulator
across grid steps, and no served width needs them once N is cut.

Which products take the kernel, and in what blocks, is decided when a
program is traced, from what the operands show (:func:`takes_kernel`):
``(rows, k, n)``, the matrices' item size, and how many of them the pass
streams. The rows must fill whole tiles, and a block of whole lanes must
fit VMEM; the kernel's own guard reads the same numbers. On the v5e it
was the faster at every width measured (PERF.md, PR 40 and PR 48): 2-8
rows an expert (a decode step, bound by its matrices' bytes: the chip
does 240 FLOPs in the time it moves a byte, perfbench/peaks.py, and a
group of r rows does r a byte), 256 (a chunk) and 1,024 to 4,096 (a
training batch's), so no count of rows sends a product back. It lowers
for the TPU only (``jax.lax.platform_dependent``); every other backend, a
program sharded over a mesh and every other shape multiply by
``ragged_dot``, which is also the backward pass of both.
:func:`kernel_calls` tells a program's builder how many of its products
went which way.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "grouped_swiglu", "takes_kernel",
           "kernel_calls"]

# Rows of one visit: one pass of the 128 x 128 matrix unit. A visit
# multiplies the whole tile by its expert whatever share of it is the
# group's, so a wider tile only pads (at 256 the padded work would show
# beside the copies).
_ROW_TILE = 128
# VMEM for the expert blocks in flight (each matrix twice: the one
# multiplied and the one arriving) and what the kernel may use in all.
# The compiler's own scope is 16 MB; the v5e has 128 MiB.
_EXPERT_BUFFER_BYTES = 40 << 20
_VMEM_LIMIT_BYTES = 64 << 20


def _row_tile(m: int) -> int:
    return min(_ROW_TILE, m)


def _fits(k: int, tn: int, itemsize: int, matrices: int) -> bool:
    """Whether ``matrices`` blocks ``[k, tn]`` of ``itemsize`` bytes an
    element fit the experts' buffers, each twice."""
    return 2 * matrices * k * tn * itemsize <= _EXPERT_BUFFER_BYTES


def _block_width(k: int, n: int, itemsize: int, matrices: int) -> int:
    """The columns of an expert's ``[k, n]`` matrices one visit takes:
    ``n`` where they fit their buffers whole, else the most lanes (a
    multiple of 128) that divide ``n`` and fit, 0 where none does."""
    if _fits(k, n, itemsize, matrices):
        return n
    return max((tn for tn in range(128, n, 128)
                if n % tn == 0 and _fits(k, tn, itemsize, matrices)),
               default=0)


def takes_kernel(rows: int, k: int, n: int, itemsize: int,
                 matrices: int) -> bool:
    """Whether a product of ``rows`` sorted rows over ``matrices``
    experts' matrices ``[k, n]`` of ``itemsize`` bytes an element (two
    for :func:`grouped_swiglu`, one for :func:`grouped_matmul`) goes
    through the kernel on a TPU: where the rows fill whole tiles of 16
    (a bf16 sublane tile) and of ``_ROW_TILE`` and the matrices fit VMEM
    whole or in blocks of whole lanes along ``n``."""
    tile = _row_tile(rows)
    return (rows > 0 and rows % tile == 0 and tile % 16 == 0
            and _block_width(k, n, itemsize, matrices) > 0)


def _visits(tokens, m: int, tm: int):
    """The walk over ``m`` sorted rows in tiles of ``tm``: the row axis
    cut wherever a tile or a group starts, the dead rows after the last
    group being one more group. Returns int32 ``starts[V + 1]`` (visit
    ``v`` holds rows ``starts[v]:starts[v + 1]``; ``m`` past the last),
    ``experts[V]`` (the matrix it multiplies by; a dead visit names the
    last live expert, whose block is there already), the number of
    visits and of live rows, for ``V = m // tm + E`` visits at most."""
    e = tokens.shape[0]
    ends = jnp.cumsum(tokens.astype(jnp.int32))
    row = jnp.arange(m, dtype=jnp.int32)
    group = jnp.sum(row[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    first = (row % tm == 0) | (group != jnp.roll(group, 1))
    visit = jnp.cumsum(first.astype(jnp.int32)) - 1
    v = jnp.arange(m // tm + e + 1, dtype=jnp.int32)
    starts = jnp.sum(visit[None, :] < v[:, None], axis=1, dtype=jnp.int32)
    last_live = jnp.sum(ends < ends[-1], dtype=jnp.int32)
    experts = jnp.minimum(
        jnp.sum(ends[None, :] <= starts[:-1, None], axis=1, dtype=jnp.int32),
        last_live)
    return starts, experts, visit[-1] + 1, ends[-1:]


def _grouped_kernel(starts_ref, experts_ref, live_ref, rows_ref, *refs,
                    tm: int):
    """One visit of one block of columns: the row tile times the block
    of the visit's expert in float32; the visit's own rows of the tile
    are stored, the others left as they are."""
    del experts_ref
    *w_refs, out_ref = refs
    v = pl.program_id(1)
    lo, hi = starts_ref[v], starts_ref[v + 1]
    n_live = live_ref[0]
    at = lo // tm * tm + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    mine = (at >= lo) & (at < hi)

    @pl.when(lo < n_live)
    def _multiply():
        x = rows_ref[...]
        y = jnp.dot(x, w_refs[-1][...], preferred_element_type=jnp.float32)
        if len(w_refs) == 2:
            y = jax.nn.silu(jnp.dot(
                x, w_refs[0][...], preferred_element_type=jnp.float32)) * y
        out_ref[...] = jnp.where(mine, y.astype(out_ref.dtype), out_ref[...])

    @pl.when(lo >= n_live)
    def _dead():
        out_ref[...] = jnp.where(mine, jnp.zeros_like(out_ref), out_ref[...])


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def _moe_grouped_pallas(rows, ws: Tuple[jax.Array, ...], tokens, *,
                        tn: Optional[int] = None, interpret: bool = False):
    """``tn``: the block of columns, ``_block_width``'s unless a test or
    ``benchmarks/moe_products.py`` asks for a narrower one."""
    m, k = rows.shape
    n = ws[0].shape[2]
    tm = _row_tile(m)
    itemsize, matrices = ws[0].dtype.itemsize, len(ws)
    tn = tn or _block_width(k, n, itemsize, matrices)
    if (m % tm or not tn or n % tn or (tn < n and tn % 128)
            or not _fits(k, tn, itemsize, matrices)):
        raise ValueError(
            f"{m} rows in tiles of {tm} over {matrices} matrices [{k}, {n}]"
            f" of {itemsize} bytes in blocks of {tn} columns: not a shape "
            f"of the grouped kernel (takes_kernel)")
    starts, experts, n_visits, n_live = _visits(tokens, m, tm)

    # The visits are the inner axis: a tile's follow one another, so its
    # output block stays where it is between them, and a touched
    # expert's block of columns is streamed once.
    def row_index(j, v, starts_ref, experts_ref, live_ref):
        return starts_ref[v] // tm, 0

    def expert_index(j, v, starts_ref, experts_ref, live_ref):
        return experts_ref[v], 0, j

    def out_index(j, v, starts_ref, experts_ref, live_ref):
        return starts_ref[v] // tm, j

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.ARBITRARY,) * 2,
            vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    return pl.pallas_call(
        functools.partial(_grouped_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, k), row_index)]
            + [pl.BlockSpec((None, k, tn), expert_index)] * len(ws),
            out_specs=pl.BlockSpec((tm, tn), out_index),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        interpret=interpret,
        **kwargs,
    )(starts, experts, n_live, rows, *ws)


def _ragged(rows, ws: Sequence[jax.Array], tokens):
    y = jax.lax.ragged_dot(rows, ws[-1], tokens)
    if len(ws) == 2:
        y = jax.nn.silu(jax.lax.ragged_dot(rows, ws[0], tokens)) * y
    return y


@jax.custom_vjp
def _kernel_products(rows, ws, tokens):
    return jax.lax.platform_dependent(
        rows, ws, tokens, tpu=_moe_grouped_pallas, default=_ragged)


def _kernel_products_fwd(rows, ws, tokens):
    return _kernel_products(rows, ws, tokens), (rows, ws, tokens)


def _kernel_products_bwd(saved, g):
    rows, ws, tokens = saved
    _, pull = jax.vjp(lambda r, w: _ragged(r, w, tokens), rows, ws)
    return (*pull(g), None)


_kernel_products.defvjp(_kernel_products_fwd, _kernel_products_bwd)


class _Notes(threading.local):
    """The open notes of ``kernel_calls``, each thread's own: a program
    is traced by the thread that calls it."""

    def __init__(self):
        self.open: List[Tuple[List[int], bool]] = []


_notes = _Notes()


@contextlib.contextmanager
def kernel_calls(platform: str) -> Iterator[List[int]]:
    """``with kernel_calls(platform) as calls``: ``calls[0]`` is how many
    of the products traced inside it go through the kernel in a program
    lowered for ``platform``: what a program's builder reads once, when
    it traces the program, to know it of every later call."""
    calls = [0]
    _notes.open.append((calls, platform == "tpu"))
    try:
        yield calls
    finally:
        _notes.open.pop()


def _products(rows, ws: Tuple[jax.Array, ...], tokens):
    """The products by ``takes_kernel`` of their own shapes, outside a
    mesh: XLA partitions ``ragged_dot`` over one and cannot a Mosaic
    kernel."""
    mesh = jax.sharding.get_abstract_mesh()
    if not ((mesh.empty or mesh.size == 1)
            and takes_kernel(rows.shape[0], *ws[0].shape[1:],
                             ws[0].dtype.itemsize, len(ws))):
        return _ragged(rows, ws, tokens)
    for calls, lowers in _notes.open:
        calls[0] += lowers
    return _kernel_products(rows, ws, tokens)


def grouped_matmul(rows, w, tokens):
    """``rows`` ``[M, K]`` sorted by group times group ``i``'s matrix of
    ``w`` ``[E, K, N]``, ``tokens`` ``[E]`` rows a group -> ``[M, N]``;
    rows past the last group come out zero."""
    return _products(rows, (w,), tokens)


def grouped_swiglu(rows, wg, wi, tokens):
    """``silu(grouped_matmul(rows, wg)) * grouped_matmul(rows, wi)`` in
    one pass over the rows."""
    return _products(rows, (wg, wi), tokens)
