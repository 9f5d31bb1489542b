"""Llama-family decoder in Flax — the second flagship model family.

Reference scope: the reference trains torch models through Train/DeepSpeed
(e.g. ``doc/source/train/examples/deepspeed/gptj_deepspeed_fine_tuning
.ipynb``) and serves llama-class models via user libs on Serve; the model
itself is never in-tree. Here the family is first-class and TPU-first:
RMSNorm + rotary embeddings + grouped-query attention + SwiGLU, bf16
activations with fp32 logits math, flash attention
(:mod:`raytpu.ops.flash_attention`), `lax.scan` over layers, selective
rematerialization, and parameter names chosen to match
``parallel.sharding.TRANSFORMER_RULES`` (q_proj/k_proj/v_proj column-
parallel, o_proj/down_proj row-parallel, embed_tokens vocab-sharded), so
``tree_shardings`` gives Megatron-style tp/fsdp layouts with no
model-specific code.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from raytpu.models.gpt2 import (Serving, cast_leaves, remat_block,
                                state_specs, write_prompt_rows)

# The two kinds of attention layer, as a published ``layer_types`` names
# them, and where each stands in what is given a kind (a cache's tables,
# a program's ``dests``): full first.
FULL, WINDOW = "full_attention", "sliding_attention"
KINDS = (FULL, WINDOW)
# A third kind of layer, which is no attention: a gated short convolution
# (:mod:`raytpu.models.short_conv`). It holds no keys and values, so it has
# no pool and stands in nothing that is given a kind of pool; a sequence
# keeps a state in it instead (``Serving.layer_states``).
CONV = "conv"
# A fourth, which is no softmax attention either: a delta-rule linear
# attention (:mod:`raytpu.models.kda`), whose state is two arrays a layer,
# a float32 matrix a head and the tails of its short convolutions.
KDA = "kda"
STATE_KINDS = (CONV, KDA)


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer kind's rotary embedding: plain at ``theta``, or YaRN
    (Peng et al. 2023, as ``transformers`` computes ``rope_type: yarn``)
    where ``yarn_factor`` is set: the frequencies a context of
    ``original_max_position`` turns more than ``beta_fast`` times stay,
    those it turns fewer than ``beta_slow`` times are divided by the
    factor, the ones between are blended linearly, and cos and sin are
    both scaled by ``attention_factor`` (``0.1 ln(factor) + 1`` if not
    given)."""

    theta: float = 10000.0
    yarn_factor: Optional[float] = None
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000          # multiple of 128 for MXU tiling
    block_size: int = 2048
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: int = 4               # grouped-query attention
    n_embd: int = 768
    # A head's size; None is n_embd // n_head, which a model need not
    # keep (Mellum2: 32 heads of 128 on a hidden size of 2,304).
    head_dim: Optional[int] = None
    n_inter: int = 2048              # SwiGLU hidden (≈ 8/3 · n_embd)
    rope_theta: float = 10000.0
    # Each layer's kind, FULL or WINDOW; None is every layer full. A
    # window layer's query sees the ``window`` newest positions, its own
    # among them. ``full_rope`` / ``window_rope`` give a kind a rotary
    # embedding of its own; None is plain rope at ``rope_theta``.
    layer_types: Optional[Tuple[str, ...]] = None
    window: Optional[int] = None
    full_rope: Optional[Rope] = None
    window_rope: Optional[Rope] = None
    norm_eps: float = 1e-5           # every RMSNorm's epsilon
    # RMSNorm over the whole q and the whole k projection, before the
    # heads are split and roped (OLMoE).
    qk_norm: bool = False
    # RMSNorm over each head's ``head_dim`` values of q and of k, one
    # scale vector for q and one for k a layer, before rope (EXAONE).
    qk_head_norm: bool = False
    # The kinds of layer whose q and k are roped; None is every kind. A
    # kind left out sees no positions at all (EXAONE's full layers).
    rope_kinds: Optional[Tuple[str, ...]] = None
    # A CONV layer's depthwise convolution reaches this many positions,
    # its own among them; a sequence's state there is the ``conv_taps -
    # 1`` newest rows of the convolution's input.
    conv_taps: int = 3
    # The output head is the embedding, transposed, and not a matrix of
    # its own.
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # The type the matrices and the embedding are *held* in. The norms'
    # scales (and a routed layer's router) stay float32 whatever it is.
    param_dtype: Any = jnp.float32
    remat: Any = "dots"              # False/"none" | True/"full" | "dots"
    scan_layers: bool = True
    attn_impl: Optional[str] = None
    # Paged-attention impl of ``step`` against the KV page pool: None is
    # the kernel on a TPU and the reference elsewhere; "kernel"/
    # "interpret"/"reference" pin it (see raytpu.ops.paged_attention).
    paged_attn: Optional[str] = None
    loss_chunk: int = 0

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=512, block_size=128, n_layer=2, n_head=4,
                   n_kv_head=2, n_embd=128, n_inter=352)

    @classmethod
    def small(cls) -> "LlamaConfig":  # ~125M, GPT-2-small class
        return cls()

    @classmethod
    def llama2_7b(cls) -> "LlamaConfig":
        return cls(vocab_size=32000, block_size=4096, n_layer=32,
                   n_head=32, n_kv_head=32, n_embd=4096, n_inter=11008)

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.n_embd // self.n_head)
        if self.layer_types is not None:
            types = tuple(self.layer_types)
            object.__setattr__(self, "layer_types", types)
            if len(types) != self.n_layer \
                    or set(types) - {*KINDS, *STATE_KINDS}:
                raise ValueError(
                    f"layer_types names {self.n_layer} layers, each "
                    f"{FULL!r}, {WINDOW!r}, {CONV!r} or {KDA!r}: got "
                    f"{types}")
            if WINDOW in types and not self.window:
                raise ValueError("a window layer needs `window`")

    def layer_kind(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else FULL

    def rope_of(self, kind: str):
        """What :func:`rope_tables` takes for a layer of ``kind``; None
        for a kind that is not roped (``rope_kinds``)."""
        if self.rope_kinds is not None and kind not in self.rope_kinds:
            return None
        own = self.window_rope if kind == WINDOW else self.full_rope
        return own if own is not None else self.rope_theta

    def attn_scope(self, kind: str) -> Optional[str]:
        """The ``jax.named_scope`` a layer of ``kind`` attends under in
        the serving walk; ``None`` where every layer is alike."""
        if not self.layer_types or kind in STATE_KINDS:  # (its own scopes)
            return None
        return "attn.window" if kind == WINDOW else "attn.full"

    def attention(self, kind: str = FULL, **kw):
        """The operator of a layer of ``kind``, attention or the short
        convolution: what a block builds (``name=``) and what the
        serving walk applies."""
        if kind == CONV:
            from raytpu.models.short_conv import ShortConv  # imports this

            return ShortConv(self, **kw)
        if kind == KDA:
            from raytpu.models.kda import KimiDeltaAttention

            return KimiDeltaAttention(self, **kw)
        return LlamaAttention(self, kind, **kw)

    def layer_state(self, kind: str):
        """What a sequence keeps in a layer of ``kind`` that has no pool
        (an entry of ``Serving.layer_states``); ``None`` for a layer with
        a pool."""
        return (self.conv_taps - 1, self.n_embd) if kind == CONV else None

    def held_index(self, i: int) -> int:
        """Where layer ``i``'s own arrays stand in what a served model is
        given: its pools among the pools (``k_caches``, ``v_caches``), or
        the first of its state arrays among the ``states``; ``i`` itself
        where every layer has a pool."""
        kinds = self.layer_types
        if not kinds:
            return i
        if kinds[i] in STATE_KINDS:
            return sum(len(state_specs(self.layer_state(kind)))
                       for kind in kinds[:i])
        return sum(kind not in STATE_KINDS for kind in kinds[:i])

    def ffn_width(self, i: int) -> Optional[int]:
        """Layer ``i``'s feed-forward by its index: the width of its
        SwiGLU, or ``None`` where it is a routed-expert layer."""
        return self.n_inter

    def shortcut_to(self, i: int) -> Optional[int]:
        """``None``, or the layer at whose end a routed-expert layer is
        added that stands *beside* layer ``i``'s feed-forward: it reads
        the same normed stream, and what lies between (the feed-forward,
        the layers up to that one) does not wait for it: a shortcut
        (:class:`raytpu.models.mixtral.LongcatFlashConfig`)."""
        return None

    def layer_scope(self, i: int) -> Optional[str]:
        """The ``jax.named_scope`` the serving walk runs all of layer
        ``i`` under; ``None``: none."""
        return None

    @property
    def serving(self) -> Serving:
        """How ``InferenceEngine`` serves this family; a routed config
        (``MixtralConfig`` and what extends it) through the same walk."""
        routed = sum(self.ffn_width(i) is None
                     or self.shortcut_to(i) is not None
                     for i in range(self.n_layer))
        kinds = self.layer_types or ()
        return Serving(
            llama_prefill, llama_step, serving_params,
            kv_heads=self.n_kv_head, head_dim=self.head_dim,
            expert_counts=(routed, self.n_expert_held) if routed else None,
            expert_pairs=bool(routed and self.n_zero_expert),
            layer_windows=tuple(
                self.window if kind == WINDOW else None
                for kind in kinds if kind not in STATE_KINDS),
            layer_states=tuple(self.layer_state(kind) for kind in kinds)
            if set(kinds) & set(STATE_KINDS) else ())

    @property
    def n_params_approx(self) -> int:
        c = self
        attn = c.n_embd * (c.n_head + 2 * c.n_kv_head) * c.head_dim \
            + c.n_head * c.head_dim * c.n_embd
        mlp = 3 * c.n_embd * c.n_inter
        return 2 * c.vocab_size * c.n_embd + c.n_layer * (attn + mlp)


class RMSNorm(nn.Module):
    """``gain``: a constant the normed values are multiplied by beside
    the learned ``scale``, in float32 before the cast (a latent
    attention's scale corrections, :mod:`raytpu.models.mla`)."""

    dtype: Any = jnp.bfloat16
    eps: float = 1e-5
    gain: float = 1.0

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        normed = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        normed = normed * scale
        if self.gain != 1.0:
            normed = normed * self.gain
        return normed.astype(self.dtype)


def yarn_frequencies(head_dim: int, rope: Rope):
    """YaRN's ``head_dim / 2`` blended inverse frequencies, float64."""
    import numpy as np

    plain = rope.theta ** -(np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim)

    def dim_of(turns: float) -> float:
        # The (fractional) pair whose wavelength fits ``turns`` times
        # into the original context.
        return head_dim * math.log(rope.original_max_position
                                   / (2 * math.pi * turns)) \
            / (2 * math.log(rope.theta))

    low = min(max(math.floor(dim_of(rope.beta_fast)), 0), head_dim - 1)
    high = min(max(math.ceil(dim_of(rope.beta_slow)), 0), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / (high - low if high != low else 0.001), 0.0, 1.0)
    return plain / rope.yarn_factor * ramp + plain * (1.0 - ramp)


def rope_tables(head_dim: int, positions, rope):
    """(cos, sin) tables for rotary embeddings, fp32, [T, head_dim/2].
    ``rope`` is plain rope's theta, or a :class:`Rope`."""
    if isinstance(rope, Rope) and rope.yarn_factor:
        freqs = jnp.asarray(yarn_frequencies(head_dim, rope), jnp.float32)
        scale = rope.attention_factor or (
            0.1 * math.log(rope.yarn_factor) + 1.0)
        angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
        return jnp.cos(angles) * scale, jnp.sin(angles) * scale
    theta = rope.theta if isinstance(rope, Rope) else rope
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """Rotate pairs of channels; x is [B, H, T, D]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[None, None, :, :].astype(x.dtype)
    sin = sin[None, None, :, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def apply_rope_single(x, cos, sin):
    """Rotate rows that each have a position of their own; x is
    [N, H, D], cos/sin [N, D/2] (from ``rope_tables(d, positions)`` with
    the rows' absolute positions — a paged step's counterpart of
    :func:`apply_rope`)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[:, None, :].astype(x.dtype)
    sin = sin[:, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


class LlamaAttention(nn.Module):
    """GQA attention with three entry points sharing one parameter set:
    ``__call__`` (training forward), ``prefill`` (forward that also
    returns the roped K/V for cache writing), and ``step`` (``[B, T]``
    positions against the paged cache). setup()-style so all three
    can touch the projections; attribute names keep the param tree
    identical to the old compact version (q_proj/k_proj/v_proj/o_proj),
    so ``TRANSFORMER_RULES`` sharding and existing checkpoints are
    unaffected. ``kind`` is the layer's (FULL or WINDOW): it picks the
    rotary embedding and, for a window layer, the window every one of
    the three attends through."""

    config: LlamaConfig
    kind: str = FULL

    @property
    def window(self) -> Optional[int]:
        return self.config.window if self.kind == WINDOW else None

    def setup(self):
        c = self.config
        dense = functools.partial(nn.Dense, use_bias=False, dtype=c.dtype,
                                  param_dtype=c.param_dtype)
        self.q_proj = dense(c.n_head * c.head_dim)
        self.k_proj = dense(c.n_kv_head * c.head_dim)
        self.v_proj = dense(c.n_kv_head * c.head_dim)
        self.o_proj = dense(c.n_embd)
        if c.qk_norm or c.qk_head_norm:
            self.q_norm = RMSNorm(dtype=c.dtype, eps=c.norm_eps)
            self.k_norm = RMSNorm(dtype=c.dtype, eps=c.norm_eps)

    def __call__(self, x):
        return self.prefill(x)[0]

    def _qkv(self, x):
        """The three projections of ``x`` [..., E], q and k normed over
        their whole width or over each head's where the config says so;
        heads not yet split."""
        c = self.config
        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        if c.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        if c.qk_head_norm:
            q = self.q_norm(q.reshape(*q.shape[:-1], c.n_head,
                                      c.head_dim)).reshape(q.shape)
            k = self.k_norm(k.reshape(*k.shape[:-1], c.n_kv_head,
                                      c.head_dim)).reshape(k.shape)
        return q, k, v

    def _roped(self, q, k, positions, rotate):
        """q and k rotated by ``rotate`` at ``positions`` [T], or as they
        are in a layer whose kind is not roped."""
        rope = self.config.rope_of(self.kind)
        if rope is None:
            return q, k
        cos, sin = rope_tables(self.config.head_dim, positions, rope)
        return rotate(q, cos, sin), rotate(k, cos, sin)

    def prefill(self, x):
        """Full-sequence attention over ``x`` [B, T, E]; returns
        ``(out [B, T, E], k [B, T, KV, D], v [B, T, KV, D])`` where
        k (roped, pre-GQA-repeat) and v are exactly what belongs in the
        paged KV cache for positions 0..T-1."""
        c = self.config
        b, t, _ = x.shape
        h, kv, d = c.n_head, c.n_kv_head, c.head_dim
        q, k, v = self._qkv(x)
        q = q.reshape(b, t, h, d).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, kv, d).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, kv, d).transpose(0, 2, 1, 3)
        q, k = self._roped(q, k, jnp.arange(t), apply_rope)
        k_cache = k.transpose(0, 2, 1, 3)
        v_cache = v.transpose(0, 2, 1, 3)
        if kv != h:
            # GQA: each kv head serves n_head/n_kv_head query heads.
            rep = h // kv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        from raytpu.ops.flash_attention import flash_attention

        y = flash_attention(q, k, v, causal=True, force=c.attn_impl,
                            window=self.window)
        y = y.transpose(0, 2, 1, 3).reshape(b, t, h * d)
        return self.o_proj(y), k_cache, v_cache

    def step(self, x, k_pages, v_pages, dests, block_tables, positions):
        """Attention against the paged cache: ``T`` consecutive positions
        a sequence (a prompt's chunk at ``B = 1``, a decode step at
        ``T = 1``, a step that verifies a draft at ``T = 2``).

        Args:
            x: [B * T, E] hidden states, a row a position, sequence by
                sequence (the serving walk runs over rows: ``B`` and
                ``T`` are ``positions``' shape).
            k_pages / v_pages: [num_pages, page_size, KV * D] pools.
            dests: [B, T] flat slots where the rows' K/V are written;
                padding rows name the scratch page, page 0.
            block_tables: [B, P] page ids per sequence (0-padded, so
                padding attends to masked garbage only).
            positions: [B, T] absolute positions, rising by one along a
                sequence: a row's query sees slots ``0 .. position``
                (a window layer: the newest ``window`` of them).

        All rows are written before any attends, so a row sees itself
        and the rows before it of its own step. Padding rows' outputs
        are garbage the engine discards. Returns ``(out [B * T, E],
        k_pages', v_pages')``."""
        c = self.config
        b, t = positions.shape
        h, kv, d = c.n_head, c.n_kv_head, c.head_dim
        q, k, v = self._qkv(x)
        q, k = self._roped(q.reshape(b * t, h, d), k.reshape(b * t, kv, d),
                           positions.reshape(b * t), apply_rope_single)
        from raytpu.ops.paged_attention import (paged_attention,
                                                scatter_kv_slots)

        k_pages = scatter_kv_slots(k_pages, dests.reshape(b * t),
                                   k.reshape(b * t, kv * d))
        v_pages = scatter_kv_slots(v_pages, dests.reshape(b * t),
                                   v.reshape(b * t, kv * d))
        o = paged_attention(q.reshape(b, t, h, d), k_pages, v_pages,
                            block_tables, positions, force=c.paged_attn,
                            window=self.window)
        return self.o_proj(o.reshape(b * t, h * d)), k_pages, v_pages


class LlamaMLP(nn.Module):
    """SwiGLU of ``width`` (``config.n_inter`` if not given)."""

    config: LlamaConfig
    width: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        c = self.config
        width = self.width or c.n_inter
        dense = functools.partial(nn.Dense, use_bias=False, dtype=c.dtype,
                                  param_dtype=c.param_dtype)
        gate = dense(width, name="gate_proj")(x)
        up = dense(width, name="up_proj")(x)
        return dense(c.n_embd, name="down_proj")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    kind: str = FULL

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = x + c.attention(self.kind, name=op_name(self.kind))(
            RMSNorm(dtype=c.dtype, eps=c.norm_eps, name="input_norm")(x))
        x = x + LlamaMLP(c, name="mlp")(
            RMSNorm(dtype=c.dtype, eps=c.norm_eps,
                    name="post_attn_norm")(x))
        return x


class Llama(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        c = self.config
        embed = nn.Embed(c.vocab_size, c.n_embd, dtype=c.dtype,
                         param_dtype=c.param_dtype, name="embed_tokens")
        x = embed(tokens)
        block = remat_block(LlamaBlock, c.remat)
        if c.scan_layers and not c.layer_types:
            x, _ = nn.scan(
                lambda mdl, carry, _: (mdl(carry), None),
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=c.n_layer,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block(c, name="layers"), x, None)
        else:
            # Layers of different kinds are not one scanned body.
            for i in range(c.n_layer):
                x = block(c, c.layer_kind(i), name=f"layers_{i}")(x)
        x = RMSNorm(dtype=c.dtype, eps=c.norm_eps, name="final_norm")(x)
        if return_hidden:
            return x
        if c.tie_embeddings:
            return embed.attend(x).astype(jnp.float32)
        # Untied LM head (llama-style), bf16 matmul with fp32 accumulation.
        logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                          param_dtype=c.param_dtype, name="lm_head")(x)
        return logits.astype(jnp.float32)


def llama_loss_fn(model: Llama, params, tokens):
    """Next-token cross-entropy; same chunked flash-xent option as GPT-2
    (:func:`raytpu.models.gpt2._chunked_xent` — the LM-head weight is the
    untied ``lm_head`` kernel here)."""
    c = model.config
    targets = tokens[:, 1:]
    if c.loss_chunk:
        from raytpu.models.gpt2 import _chunked_xent

        x = model.apply({"params": params}, tokens, return_hidden=True)
        # lm_head kernel is [embed, vocab]; chunked xent expects
        # [vocab, embed] (embedding-style), so pass the transpose.
        w = params["lm_head"]["kernel"].T
        return _chunked_xent(x[:, :-1], targets, w, c)
    logits = model.apply({"params": params}, tokens)[:, :-1]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    label = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (lse - label).mean()


def make_train_step(model, optimizer, loss_fn=None):
    """(params, opt_state, tokens) -> (params, opt_state, loss); pure —
    jit with shardings from :func:`raytpu.parallel.sharding.tree_shardings`
    (param names already match TRANSFORMER_RULES). Shared by the llama and
    mixtral families via ``loss_fn``."""
    loss_fn = loss_fn or llama_loss_fn

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, tokens))(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates)
        return params, opt_state, loss

    return train_step


def init_params(model: Llama, config: LlamaConfig, seed: int = 0,
                batch: int = 2):
    tokens = jnp.zeros((batch, config.block_size), jnp.int32)
    return model.init(jax.random.PRNGKey(seed), tokens)["params"]


# ---------------------------------------------------------------------------
# Inference forward paths (used by raytpu.inference.engine). These are
# pure functions over the SAME param tree __call__ trains: layers are
# looped in Python (the engine jits the whole prefill/decode step, so
# an unrolled loop over 2-32 layers compiles fine and sidesteps
# carrying the paged cache through nn.scan).
# ---------------------------------------------------------------------------

def layer_params(params, i: int):
    """Params of layer ``i`` from either layout: scanned (stacked under
    "layers" with a leading layer axis) or unrolled ("layers_{i}")."""
    if "layers" in params:
        return jax.tree_util.tree_map(lambda p: p[i], params["layers"])
    return params[f"layers_{i}"]


def serving_params(config: LlamaConfig, params):
    """The working copy of ``params`` to serve from: the leaves that
    :func:`llama_prefill` and :func:`llama_step` cast to
    ``config.dtype`` (every ``nn.Dense``
    kernel, ``lm_head`` among them, ``embed_tokens``, and a routed
    layer's stacked expert matrices ``wi``/``wg``/``wo``) are in it
    already, so no step converts a weight and the logits are the same
    bits. The norms' ``scale`` stays as given: :class:`RMSNorm`
    multiplies by it in float32; so does a router's kernel, which is
    multiplied in float32. Same contract as
    :func:`raytpu.models.gpt2.serving_params`."""
    return cast_leaves(
        params, config.dtype,
        lambda keys: keys[-1] in ("embedding", "wi", "wg", "wo")
        or (keys[-1] == "kernel" and keys[-2] != "router"))


def _lm_logits(c: LlamaConfig, params, x):
    if c.tie_embeddings:  # the embedding's rows are the head's columns
        embedding = params["embed_tokens"]["embedding"].astype(c.dtype)
        return jax.lax.dot_general(
            x, embedding, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    kernel = params["lm_head"]["kernel"].astype(c.dtype)
    return jnp.dot(x, kernel).astype(jnp.float32)


def op_name(kind: str) -> str:
    """What a block's parameters call its first operator."""
    return kind if kind in STATE_KINDS else "attn"


def _routed(c: LlamaConfig, lp, h, live):
    """A block's routed-expert layer on the normed ``h``: its output and
    its count, the tokens each expert held here received."""
    return c.routed().apply({"params": lp["moe"]}, h, live)


def _feed_forward(c: LlamaConfig, lp, h, live, i: int):
    """The second half of block ``i`` on the normed ``h``, as the config
    says of that layer (``ffn_width``): SwiGLU, or the routed experts.
    ``live`` marks the rows that are tokens and not padding; only the
    routed layer needs it, to route padding nowhere. Returns the output
    and the routed layer's count (``None`` when dense)."""
    width = c.ffn_width(i)
    if width is None:
        return _routed(c, lp, h, live)
    return LlamaMLP(c, width).apply({"params": lp["mlp"]}, h), None


def _scope(name: Optional[str]):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def of_kind(x, kind: str):
    """``x`` for a layer of ``kind``: an engine over a cache of two kinds
    of pool gives ``dests`` and ``block_tables`` as one array a kind, in
    the order of ``KINDS``; over one kind, the array itself."""
    return x[KINDS.index(kind)] if isinstance(x, (tuple, list)) else x


def live_rows(dests, k_cache):
    """Rows of a bucket that are tokens, from where their K/V is written
    in the full layers' pools: the engine points padding at the scratch
    page, page 0. (A window layer's pool also sends there the rows of a
    whole prompt that lie left of its window.)"""
    return of_kind(dests, FULL) >= k_cache.shape[1]


def _serve(c: LlamaConfig, params, x, live, cache_args, whole: bool = False,
           hidden: bool = False):
    """The serving walk, written once: the blocks over the embedded
    ``x``, the final norm and the head. Layer ``i`` attends
    through its module's ``step(h, *cache_args(i))`` over ``x`` [B * T,
    E], a row a position, or through ``prefill`` of a ``whole`` prompt
    from position 0 over ``x`` [1, T, E], which returns its
    output and the layer's K and V (rows, or the pools it wrote), or
    the one pool of a latent layer (``V list`` is then empty; with an
    indexer it holds the layer's index keys), or a CONV
    layer's state array, written (a KDA layer's two);
    ``live`` (``x``'s leading shape) marks the rows that are tokens, for
    :func:`_feed_forward`. Returns ``(fp32 logits, K list, V list)``,
    then the list of state arrays where a layer keeps one, and
    for a config with routed layers one value more, the int32 ``[routed
    layers, experts held]`` count of tokens each expert received (two
    columns more under ``Serving.expert_pairs``). A routed layer that
    stands beside layer ``i``'s feed-forward (``c.shortcut_to(i)``)
    reads the same normed stream, and its output is carried to the end
    of the layer named and added there. Where
    the layers are of two kinds each attends under
    ``jax.named_scope("attn.full")`` or ``("attn.window")``; a latent
    layer under ``("attn.mla")``. With ``hidden`` a last value more: the
    residual stream after the last block, before the final norm, which a
    prediction module reads (:func:`raytpu.models.mixtral.draft_step`)."""
    attn = {kind: c.attention(kind) for kind in set(c.layer_types or KINDS)}
    norm = RMSNorm(dtype=c.dtype, eps=c.norm_eps)
    method = "prefill" if whole else "step"
    ks, vs, states, routed = [], [], [], []
    shortcuts = {}  # a routed layer's output, by the layer it is added at
    for i in range(c.n_layer):
        with _scope(c.layer_scope(i)):
            lp = layer_params(params, i)
            h = norm.apply({"params": lp["input_norm"]}, x)
            kind = c.layer_kind(i)
            with _scope(c.attn_scope(kind)):
                y, k, *v = attn[kind].apply(
                    {"params": lp[op_name(kind)]}, h, *cache_args(i),
                    method=method)
            if kind in STATE_KINDS:
                states.extend((k, *v))
            else:
                ks.append(k)
                vs.extend(v)
            x = x + y
            h = norm.apply({"params": lp["post_attn_norm"]}, x)
            y, counts = _feed_forward(c, lp, h, live, i)
            to = c.shortcut_to(i)
            if to is not None:
                shortcuts[to], counts = _routed(c, lp, h, live)
            if counts is not None:
                routed.append(counts)
            x = x + y
            if i in shortcuts:
                x = x + shortcuts.pop(i)
    last = (x,) if hidden else ()
    x = norm.apply({"params": params["final_norm"]}, x)
    logits = _lm_logits(c, params, x)
    held = (ks, vs, states) if states else (ks, vs)
    if not routed:
        return (logits, *held, *last)
    return (logits, *held, jnp.stack(routed), *last)


def _pools(k_caches, v_caches, i: int):
    """Pool ``i`` as an attention module takes it: K and V (a latent
    layer with an indexer: its latent pool and its index keys), or the
    one pool of a latent layer (``v_caches`` is then empty)."""
    return (k_caches[i], v_caches[i]) if v_caches else (k_caches[i],)


def _by_layer(c: LlamaConfig, k_caches, v_caches, states, conv, paged):
    """``cache_args`` of :func:`_serve`: layer ``i``'s own pools before
    ``paged(kind)`` (nothing at all where ``paged`` is None), or for a
    layer that keeps a state its state arrays before ``conv``
    (:meth:`LlamaConfig.held_index` says which of each list are its)."""
    def cache_args(i: int):
        kind, own = c.layer_kind(i), c.held_index(i)
        if kind in STATE_KINDS:
            held = len(state_specs(c.layer_state(kind)))
            return (*states[own:own + held], *conv)
        if paged is None:  # a whole prompt's attention reads no pool
            return ()
        return (*_pools(k_caches, v_caches, own), *paged(kind))

    return cache_args


def llama_prefill(config: LlamaConfig, params, tokens, dests, k_caches,
                  v_caches, states=(), seats=None):
    """Whole-prompt forward: ``tokens`` [1, T] from position 0 (flash
    attention), its roped K and V written to the pools at ``dests`` [T]
    -> (fp32 logits [T, V], k_caches, v_caches[, states][, count]).
    ``states`` and ``seats``: ``Serving.layer_states``."""
    c = config
    live = live_rows(dests, k_caches[0])[None]
    x = params["embed_tokens"]["embedding"].astype(c.dtype)[tokens]
    logits, ks, vs, *more = _serve(c, params, x, live, _by_layer(
        c, k_caches, v_caches, states, (seats, live), None), whole=True)
    if c.layer_types:  # each layer's rows where its kind of pool has them
        dests = [of_kind(dests, kind) for kind in c.layer_types
                 if kind not in STATE_KINDS]
    ks, vs = write_prompt_rows(k_caches, v_caches, dests, ks, vs)
    return (logits[0], ks, vs, *more)


def llama_step(config: LlamaConfig, params, tokens, positions, dests,
               block_tables, k_caches, v_caches, states=(), seats=None):
    """The paged forward (``Serving.step``): ``tokens`` [B, T] at
    absolute ``positions`` [B, T] against the pools -> (fp32 logits
    [B, T, V], updated k_caches, v_caches[, states][, count]). See
    :meth:`LlamaAttention.step` for the cache argument shapes; a sequence
    whose first row stands at position 0 starts a CONV layer's state
    from zeros. The walk runs over the ``B * T`` rows, not over
    ``[B, T, E]``: with an axis of one in the middle of every activation
    the v5e's compiler laid a decode step's projections out so that it
    copied their matrices every step (``PERF.md``, PR 50)."""
    c = config
    x = params["embed_tokens"]["embedding"].astype(c.dtype)[
        tokens.reshape(-1)]
    live = live_rows(dests, k_caches[0])
    logits, *held = _serve(c, params, x, live.reshape(-1), _by_layer(
        c, k_caches, v_caches, states, (seats, live, positions[:, 0] == 0),
        lambda kind: (of_kind(dests, kind), of_kind(block_tables, kind),
                      positions)))
    return (logits.reshape(*tokens.shape, -1), *held)
