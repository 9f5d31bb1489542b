"""Flash attention as a Pallas TPU kernel.

Blockwise-stable softmax with O(T) memory. The grid is
``(batch*heads, q_blocks, kv_blocks)`` with the K/V walk as the
*innermost grid dimension*, so the Mosaic pipeline double-buffers the
K/V block DMAs from HBM into VMEM while running max / denominator /
output accumulator persist in VMEM scratch across kv iterations (the
canonical TPU flash pattern — scratch carries state because TPU grids
execute sequentially over the arbitrary dimension). Matmuls hit the MXU
in fp32 accumulation (``preferred_element_type``); causally fully-masked
K/V blocks are skipped with `pl.when` predication.

Backward is flash-style recompute: residuals are just (q, k, v, o, lse).
On TPU two Pallas kernels produce the gradients without ever
materializing the [T, T] score matrix in HBM — a dq kernel (grid walks
K/V innermost, dq accumulates in VMEM scratch) and a dk/dv kernel (grid
walks Q innermost, dk/dv accumulate in scratch); `p = exp(s - lse)`
reuses the saved log-sum-exp so no running max is needed. Elsewhere the
reference einsum formulation runs instead (tests compare the kernels in
interpret mode against it).

Cross-length causal (t_q != t_kv) uses a bottom-aligned diagonal
(``tril(k=t_kv-t_q)``, matching the reference path). For t_q > t_kv the
leading rows attend nothing; the kernels output 0 for those rows while
the einsum path degenerates to uniform attention — both are artifacts of
an ill-defined case (a softmax over zero elements).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

def _env_block(name: str, default: int) -> int:
    """Malformed/empty/non-positive overrides fall back silently — a bad
    env var must not break every import of raytpu.ops."""
    try:
        v = int(os.environ.get(name) or default)
    except ValueError:
        return default
    return v if v > 0 else default


# Tile shape of the pallas kernel's grid. Env-overridable so
# benchmarks/sweep_attn.py can A/B block shapes per process without code
# edits (_fit_block shrinks them to tile the actual sequence length).
# 512x512 has not been measured against other shapes on a chip.
DEFAULT_BLOCK_Q = _env_block("RAYTPU_FLASH_BLOCK_Q", 512)
DEFAULT_BLOCK_K = _env_block("RAYTPU_FLASH_BLOCK_K", 512)


def _env_dot_mode() -> str:
    """"input" | "f32", with synonyms; unknown values warn and fall back
    (a bad env var must not break every import of raytpu.ops)."""
    raw = (os.environ.get("RAYTPU_FLASH_DOT") or "input").lower()
    mode = {"input": "input", "bf16": "input",
            "f32": "f32", "fp32": "f32", "float32": "f32"}.get(raw)
    if mode is None:
        import warnings
        warnings.warn(f"RAYTPU_FLASH_DOT={raw!r} not recognized "
                      f"(use 'input' or 'f32'); using 'input'",
                      RuntimeWarning, stacklevel=2)
        mode = "input"
    return mode


# MXU operand dtype inside the kernels. "input" feeds q/k/v (and p/ds,
# cast back down) to the MXU in their input dtype with fp32 accumulation
# via preferred_element_type — the official TPU flash pattern; "f32"
# upcasts every operand first (r4-and-earlier behavior, ~roundoff-free
# but slower when inputs are bf16). Env-overridable for the sweep A/B.
DEFAULT_DOT_MODE = _env_dot_mode()


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
            f"sequence length {t}; use force='reference', pad the "
            f"sequence, or raise RAYTPU_FLASH_BLOCK_Q/K")
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
                        window=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = _causal_mask(q.shape[2], k.shape[2], window)
        s = jnp.where(mask[None, None], s, -1e30)
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


# -- pallas kernel ------------------------------------------------------------


_LANES = 128  # VMEM scratch lane width; m/l broadcast across lanes.


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr, *, causal: bool,
                  sm_scale: float, block_q: int, block_k: int, n_kb: int,
                  off: int, dot_mode: str, window=None):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    d = q_ref.shape[2]
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full((block_q, _LANES), -1e30, jnp.float32)
        l_scr[...] = jnp.zeros((block_q, _LANES), jnp.float32)
        acc_scr[...] = jnp.zeros((block_q, d), jnp.float32)

    q_start = iq * block_q
    k_start = ik * block_k
    # Causally fully-masked K/V blocks contribute nothing. The diagonal is
    # bottom-aligned for t_q != t_kv (off = t_kv - t_q), matching the
    # reference path's tril(k=t_kv-t_q).
    live = (k_start <= q_start + block_q - 1 + off) if causal else True
    if window is not None:
        # Blocks wholly left of the first row's window contribute nothing
        # either: the block's last key is older than the oldest it sees.
        live = jnp.logical_and(
            live, k_start + block_k - 1 > q_start + off - window)

    @pl.when(live)
    def _compute():
        # "input" mode feeds the MXU in the residual dtype (bf16 in, fp32
        # accumulate) — native MXU speed; "f32" upcasts operands first.
        mxu = jnp.float32 if dot_mode == "f32" else q_ref.dtype
        q = q_ref[0].astype(mxu)  # [Bq, D]
        kb = k_ref[0].astype(mxu)  # [Bk, D]
        vb = v_ref[0].astype(mxu)  # [Bk, D]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            seen = kpos <= qpos + off
            if window is not None:
                seen = jnp.logical_and(seen, kpos > qpos + off - window)
            s = jnp.where(seen, s, -1e30)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_new, (block_q, _LANES))
        l_scr[...] = jnp.broadcast_to(l_new, (block_q, _LANES))
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(mxu), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kb - 1)
    def _finalize():
        m = m_scr[:, :1]
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            (m + jnp.log(l)), (block_q, _LANES)).astype(jnp.float32)


def _flash_forward_pallas(q, k, v, causal: bool, sm_scale: float,
                          block_q: int, block_k: int, interpret: bool,
                          window=None):
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, t_q, d)
    k3 = k.reshape(bh, t_kv, d)
    v3 = v.reshape(bh, t_kv, d)
    block_q = _fit_block(t_q, block_q, interpret)
    block_k = _fit_block(t_kv, block_k, interpret)
    n_kb = t_kv // block_k

    off = t_kv - t_q  # bottom-aligned diagonal (reference tril k=off)
    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, n_kb=n_kb, off=off,
        dot_mode=DEFAULT_DOT_MODE, window=window)

    if causal and window is not None:
        # As below, from both sides: iterations left of the window
        # re-reference its first block, those past the diagonal its last.
        def kv_index(ib, iq, ik):
            last = (iq * block_q + block_q - 1 + off) // block_k
            last = jnp.clip(last, 0, n_kb - 1)
            first = (iq * block_q + off - window + 1) // block_k
            first = jnp.clip(first, 0, n_kb - 1)
            return (ib, jnp.clip(ik, first, last), 0)
    elif causal:
        # Clamp the K/V walk to the last causally-live block: iterations
        # past the diagonal re-reference an already-fetched block, so the
        # pipeline never DMAs fully-masked K/V from HBM (`pl.when` skips
        # their compute; this skips their bandwidth too).
        def kv_index(ib, iq, ik):
            last = (iq * block_q + block_q - 1 + off) // block_k
            last = jnp.clip(last, 0, n_kb - 1)
            return (ib, jnp.minimum(ik, last), 0)
    else:
        def kv_index(ib, iq, ik):
            return (ib, ik, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda ib, iq, ik: (ib, iq, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    out_specs = [
        pl.BlockSpec((1, block_q, d), lambda ib, iq, ik: (ib, iq, 0)),
        pl.BlockSpec((1, block_q, _LANES), lambda ib, iq, ik: (ib, iq, 0)),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.GridDimensionSemantics.PARALLEL,
                pltpu.GridDimensionSemantics.PARALLEL,
                pltpu.GridDimensionSemantics.ARBITRARY,
            ))

    o3, lse3 = pl.pallas_call(
        kernel,
        grid=(bh, t_q // block_q, n_kb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t_q, _LANES), jnp.float32),
        ],
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        **kwargs,
    )(q3, k3, v3)
    return (o3.reshape(b, h, t_q, d),
            lse3[:, :, :1].reshape(b, h, t_q, 1))


# -- pallas backward kernels --------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal: bool, sm_scale: float,
                         block_q: int, block_k: int, n_kb: int, off: int,
                         dot_mode: str):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    d = q_ref.shape[2]
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros((block_q, d), jnp.float32)

    q_start = iq * block_q
    k_start = ik * block_k
    live = (k_start <= q_start + block_q - 1 + off) if causal else True

    @pl.when(live)
    def _compute():
        mxu = jnp.float32 if dot_mode == "f32" else q_ref.dtype
        q = q_ref[0].astype(mxu)
        kb = k_ref[0].astype(mxu)
        vb = v_ref[0].astype(mxu)
        g = g_ref[0].astype(mxu)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos + off, s, -1e30)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            g, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(mxu), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                          sm_scale: float, block_q: int, block_k: int,
                          n_qb: int, off: int, dot_mode: str):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    d = q_ref.shape[2]
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros((block_k, d), jnp.float32)
        dv_scr[...] = jnp.zeros((block_k, d), jnp.float32)

    q_start = iq * block_q
    k_start = ik * block_k
    live = (q_start + block_q - 1 + off >= k_start) if causal else True

    @pl.when(live)
    def _compute():
        mxu = jnp.float32 if dot_mode == "f32" else q_ref.dtype
        q = q_ref[0].astype(mxu)
        kb = k_ref[0].astype(mxu)
        vb = v_ref[0].astype(mxu)
        g = g_ref[0].astype(mxu)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos + off, s, -1e30)
        p = jnp.exp(s - lse)  # [Bq, Bk]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(mxu), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            g, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(mxu), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward_pallas(q, k, v, o, lse, g, causal: bool, sm_scale: float,
                           block_q: int, block_k: int, interpret: bool):
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    bh = b * h
    block_q = _fit_block(t_q, block_q, interpret)
    block_k = _fit_block(t_kv, block_k, interpret)
    n_qb = t_q // block_q
    n_kb = t_kv // block_k

    q3 = q.reshape(bh, t_q, d)
    k3 = k.reshape(bh, t_kv, d)
    v3 = v.reshape(bh, t_kv, d)
    g3 = g.reshape(bh, t_q, d)
    # lse/delta enter lane-broadcast so the kernel reads [Bq, 1] columns
    # without an in-kernel transpose (Mosaic-friendly layout).
    lse3 = jnp.broadcast_to(
        lse.reshape(bh, t_q, 1), (bh, t_q, _LANES)).astype(jnp.float32)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta3 = jnp.broadcast_to(
        delta.reshape(bh, t_q, 1), (bh, t_q, _LANES))

    def qspec(f):
        return pl.BlockSpec((1, block_q, d), f)

    def kspec(f):
        return pl.BlockSpec((1, block_k, d), f)

    def lspec(f):
        return pl.BlockSpec((1, block_q, _LANES), f)

    off = t_kv - t_q  # bottom-aligned diagonal (reference tril k=off)
    if causal:
        # Same bandwidth trick as the forward: clamp dead iterations onto
        # an already-needed block so masked K/V (dq kernel) and masked Q
        # rows (dk/dv kernel) are never fetched.
        def kv_of_q(ib, iq, ik):
            last = (iq * block_q + block_q - 1 + off) // block_k
            last = jnp.clip(last, 0, n_kb - 1)
            return (ib, jnp.minimum(ik, last), 0)

        def q_of_kv(ib, ik, iq):
            first = (ik * block_k - off) // block_q
            first = jnp.clip(first, 0, n_qb - 1)
            return (ib, jnp.maximum(iq, first), 0)
    else:
        def kv_of_q(ib, iq, ik):
            return (ib, ik, 0)

        def q_of_kv(ib, ik, iq):
            return (ib, iq, 0)

    compiler = {}
    if not interpret:
        compiler["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.GridDimensionSemantics.PARALLEL,
                pltpu.GridDimensionSemantics.PARALLEL,
                pltpu.GridDimensionSemantics.ARBITRARY,
            ))

    dq3 = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, n_kb=n_kb, off=off,
            dot_mode=DEFAULT_DOT_MODE),
        grid=(bh, n_qb, n_kb),
        in_specs=[
            qspec(lambda ib, iq, ik: (ib, iq, 0)),
            kspec(kv_of_q),
            kspec(kv_of_q),
            qspec(lambda ib, iq, ik: (ib, iq, 0)),
            lspec(lambda ib, iq, ik: (ib, iq, 0)),
            lspec(lambda ib, iq, ik: (ib, iq, 0)),
        ],
        out_specs=qspec(lambda ib, iq, ik: (ib, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        **compiler,
    )(q3, k3, v3, g3, lse3, delta3)

    dk3, dv3 = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, n_qb=n_qb, off=off,
            dot_mode=DEFAULT_DOT_MODE),
        grid=(bh, n_kb, n_qb),
        in_specs=[
            qspec(q_of_kv),
            kspec(lambda ib, ik, iq: (ib, ik, 0)),
            kspec(lambda ib, ik, iq: (ib, ik, 0)),
            qspec(q_of_kv),
            lspec(q_of_kv),
            lspec(q_of_kv),
        ],
        out_specs=[
            kspec(lambda ib, ik, iq: (ib, ik, 0)),
            kspec(lambda ib, ik, iq: (ib, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        **compiler,
    )(q3, k3, v3, g3, lse3, delta3)

    return (dq3.reshape(b, h, t_q, d),
            dk3.reshape(b, h, t_kv, d),
            dv3.reshape(b, h, t_kv, d))


# -- public op with custom vjp ------------------------------------------------

_BHTD = "bh.."  # per_shard layout of q/k/v/o/g and of lse [B, H, T, 1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, use_pallas, window=None):
    o, _ = _flash_fwd(q, k, v, causal, sm_scale, use_pallas, window)
    return o


def _flash_fwd(q, k, v, causal, sm_scale, use_pallas, window=None):
    if use_pallas in ("tpu", "interpret"):
        o, lse = per_shard(
            functools.partial(
                _flash_forward_pallas, causal=causal, sm_scale=sm_scale,
                block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                interpret=(use_pallas == "interpret"), window=window),
            (q, k, v), (_BHTD,) * 3, (_BHTD, _BHTD))
    else:
        o, lse = _attn_fwd_reference(q, k, v, causal, sm_scale, window)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, use_pallas, window, res, g):
    q, k, v, o, lse = res
    if window is not None:
        # The two backward kernels know no window yet: a windowed
        # backward takes the masked reference whatever ran forward (the
        # saved log-sum-exp is the same quantity on either path).
        return _attn_bwd_reference(q, k, v, o, lse, g, causal, sm_scale,
                                   window)
    if use_pallas in ("tpu", "interpret"):
        return per_shard(
            functools.partial(
                _flash_backward_pallas, causal=causal, sm_scale=sm_scale,
                block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                interpret=(use_pallas == "interpret")),
            (q, k, v, o, lse, g), (_BHTD,) * 6, (_BHTD,) * 3)
    return _attn_bwd_reference(q, k, v, o, lse, g, causal, sm_scale)


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
