"""Operations and bytes the algorithms need, computed from shapes.

One place for the arithmetic behind ``mfu_pct``, ``flash_attn_roofline``
and ``paged_attn_roofline``. Recomputation (remat, the backward kernels'
second pass over the scores) is counted where a kernel really runs it,
because a roofline share is about the kernel as called; it is *not*
counted in model FLOPs, which are what the mathematics needs once.
The model-FLOP formula is ``bench.py``'s (6N + 12·L·E·T).
"""

from __future__ import annotations


def train_flops_per_token(params: float, layers: int, width: int,
                          seq_len: int) -> float:
    """Model FLOPs of one training token, forward and backward:
    6·N for the matrix multiplications over the ``params`` a token uses
    plus 12·L·E·T for attention over the full T×T scores (the convention
    of the repo's earlier numbers; the causal half is not taken off,
    recomputation is not added). A family file (``families/``) gives N, L
    and E of its configurations."""
    return 6.0 * params + 12.0 * layers * width * seq_len


# Matrix products of T×T×d each, per (batch·head), in each flash kernel:
# forward QKᵀ and PV; dq recomputes QKᵀ, then dO·Vᵀ and dS·K; dk/dv
# recomputes QKᵀ, then dO·Vᵀ, Pᵀ·dO and dSᵀ·Q.
FLASH_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_flops(kind: str, batch_heads: int, seq_len: int, head_dim: int,
                causal: bool = True) -> float:
    """FLOPs one call of a flash kernel needs. A causal call needs the
    lower triangle only: T·(T+1)/2 of the T² score entries."""
    entries = seq_len * (seq_len + 1) / 2 if causal else seq_len * seq_len
    return FLASH_PRODUCTS[kind] * 2.0 * batch_heads * entries * head_dim


def paged_attn_bytes(live_pages: int, page_size: int, kv_heads: int,
                     head_dim: int, itemsize: int) -> float:
    """Pool bytes one layer's paged-attention call must read: the K and
    the V rows of every live page of every sequence in the step. Queries,
    tables and the output are left out (a few KB against megabytes)."""
    return 2.0 * live_pages * page_size * kv_heads * head_dim * itemsize


def roofline_share_pct(flops: float, bytes_: float, seconds: float,
                       peaks) -> float:
    """Least time the chip could take (the larger of FLOPs over the peak
    rate and bytes over the peak bandwidth) over the time taken, in %."""
    least = max(flops / peaks.bf16_flops_per_s,
                bytes_ / peaks.hbm_bytes_per_s)
    return 100.0 * least / seconds
