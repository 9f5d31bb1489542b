"""Ring attention — sequence/context parallelism over an ICI ring.

Absent from the reference (SURVEY.md §2.5: SP/CP "Absent"); first-class
here. Sequence is sharded over the ``sp`` mesh axis; K/V blocks rotate
around the ring via ``ppermute`` (one ICI hop per step) while each device
accumulates its queries' attention with the blockwise-stable softmax of
flash attention (running max/denominator). Compute on each hop overlaps
the next hop's transfer when XLA schedules the collective-permute async —
the classic ring-attention overlap (Liu et al.) without hand-written DMA.

Differentiable end-to-end (`ppermute` has a transpose rule), so the same
code path serves training.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _vary(x, axis_name: str):
    """Mark a freshly-created array as device-varying over `axis_name`
    (shard_map tracks varying-manual-axes; loop carries must agree)."""
    return lax.pcast(x, (axis_name,), to="varying")


def _block_attn_update(q, k, v, m, l, o, mask, sm_scale):
    """One flash-attention accumulation step against a K/V block.

    q: [B,H,Tq,D]; k,v: [B,H,Tk,D]; m,l: [B,H,Tq,1]; o: [B,H,Tq,D].
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m_block = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_block)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * corr + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          sm_scale: float):
    """Per-device body (inside shard_map). q,k,v: [B,H,T_local,D]."""
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    tq = q.shape[2]
    f32 = jnp.float32

    q32 = q.astype(f32)
    m0 = jnp.full(q.shape[:3] + (1,), -1e30, f32)
    l0 = jnp.zeros(q.shape[:3] + (1,), f32)
    o0 = jnp.zeros(q.shape[:3] + (q.shape[3],), f32)
    m0, l0, o0 = (_vary(x, axis_name) for x in (m0, l0, o0))

    qpos = my * tq + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def step(t, carry):
        m, l, o, kt, vt = carry
        # After t forward rotations, this device holds the chunk that
        # originated at ring position (my - t) mod n.
        src = (my - t) % n
        if causal:
            kpos = src * tq + lax.broadcasted_iota(jnp.int32, (1, tq), 1)
            mask = kpos <= qpos  # [Tq, Tk]
            mask = mask[None, None]
        else:
            mask = None
        m, l, o = _block_attn_update(q32, kt.astype(f32), vt.astype(f32),
                                     m, l, o, mask, sm_scale)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kt = lax.ppermute(kt, axis_name, perm)
        vt = lax.ppermute(vt, axis_name, perm)
        return m, l, o, kt, vt

    m, l, o, _, _ = lax.fori_loop(0, n, step, (m0, l0, o0, k, v))
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True,
                   sm_scale: Optional[float] = None):
    """Per-shard ring attention; call inside `shard_map` with the sequence
    dim sharded on `axis_name`. Shapes [B, H, T_local, D]."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _ring_attention_local(q, k, v, axis_name, causal, sm_scale)


def ring_attention_sharded(q, k, v, mesh: Mesh, *, axis_name: str = "sp",
                           causal: bool = True,
                           sm_scale: Optional[float] = None):
    """Driver-level entry: q,k,v are global [B, H, T, D] arrays; the T dim
    is sharded over `axis_name` and the ring runs inside one compiled
    program."""
    from jax import shard_map

    spec = P(None, None, axis_name, None)
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,

    )(q, k, v)


def reference_attention(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None):
    """Unsharded reference for tests. [B, H, T, D]."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        t = q.shape[2]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
