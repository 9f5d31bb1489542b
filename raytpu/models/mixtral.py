"""Sparse-MoE decoders — the third model family: Mixtral and OLMoE.

Reference scope: MoE machinery is absent from the reference (SURVEY.md
§2.5 EP row); serving/training MoE models there is delegated to user
libraries. Here the family is first-class and TPU-shaped: llama blocks
(RMSNorm/RoPE/GQA via :mod:`raytpu.models.llama`) whose feed-forward is
one top-k routed expert layer, :class:`MoEFFN`. It is *dropless*: every
(token, expert) pair the router chooses is computed, so a token's output
does not depend on what shares its batch. The assignments are sorted by
expert and the three expert matrices multiplied group by group
(:mod:`raytpu.ops.grouped_matmul`: a Pallas kernel on one TPU, an
expert's matrices streamed whole where they fit its fast memory and in
blocks of columns where they do not, and ``jax.lax.ragged_dot``, which
the TPU compiler turns into a kernel of its own, everywhere else; both
read only the experts that received a row), so the work grows with
``n_expert_per_tok`` and not with ``n_expert``. A Switch-style
load-balancing loss is sown as an intermediate for training. Expert
parameters are stacked on a leading experts dim so ``TRANSFORMER_RULES``
shards them over the ``ep`` mesh axis with no model-specific code.

Training goes through :class:`Mixtral`; serving through the llama
family's walk (:func:`raytpu.models.llama.llama_prefill` and its
siblings), which picks this layer for a :class:`MixtralConfig`.
:class:`OlmoeConfig` is OLMoE-1B-7B's block: 64 experts of which a token
takes 8 with weights that are not renormalised, and a norm over the
whole q and k projections. :class:`MellumConfig` is Mellum2-12B-A2.5B's:
window layers among full ones, a rotary embedding a kind, a head size
of its own; :class:`Mixtral` trains it too (``Mellum`` is its name
there), its layers unrolled because they are not all alike.
:class:`JoyAIConfig` is JoyAI-LLM-Flash's: latent attention
(:mod:`raytpu.models.mla`) behind one pool a layer, sigmoid routing with
a correction bias, a shared expert, a leading dense layer, and
optionally a share of the experts held (``experts_held``).
:class:`ExaoneMoeConfig` is K-EXAONE-236B-A23B's: window layers of 128
positions, roped, among full ones that see no positions, a norm over each
head of q and of k, the same sigmoid-routed layer, and one
multi-token-prediction module (``mtp_layers``, :class:`PredictionModule`)
through which the model drafts for itself when it is served
(:func:`mtp_prefill` and :func:`mtp_step`; ``Serving.drafting``).
:class:`Lfm2MoeConfig` is LFM2-24B-A2B's: gated short convolutions
(:mod:`raytpu.models.short_conv`) in three layers of four, which keep a
state a sequence and no keys or values, full attention in the others,
two leading dense layers, the sigmoid-routed layer with no shared expert,
and a head tied to the embedding.
:class:`LongcatFlashConfig` is LongCat-Flash-Chat's: a published layer is
two latent-attention sublayers, each with a dense feed-forward, and one
routed layer beside the first feed-forward whose output is added at the
layer's end (a shortcut: ``shortcut_to``); its router scores identity
experts beside the real ones (``n_zero_expert``), which return their
input and cost no product.
:class:`GlmDsaConfig` is GLM-5's: latent attention whose queries read
only the cached positions a learned indexer chooses
(:class:`raytpu.models.mla.SparseLatentAttention`), the index keys in a
pool of their own beside the latent pool, three leading dense layers and
JoyAI's routed layer.
:class:`LingHybridConfig` is Ling-3.0-flash-VL's language model: five
delta-rule linear-attention layers (:mod:`raytpu.models.kda`), which keep
a float32 matrix a head and their convolutions' tails at a sequence's
seat, to every latent-attention layer (no query rank, a gate a head), and
JoyAI's routed layer with its experts chosen inside the best groups
(``n_group``, ``topk_group``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from raytpu.models.gpt2 import (Drafting, State, remat_block,
                                write_prompt_rows)
from raytpu.models.llama import (CONV, FULL, KDA, WINDOW, LlamaConfig,
                                 LlamaMLP, RMSNorm, Rope, _lm_logits, _serve,
                                 live_rows, of_kind, op_name)
from raytpu.ops.grouped_matmul import grouped_matmul, grouped_swiglu


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    n_expert: int = 8
    # Identity ("zero-computation") experts the router scores after the
    # ``n_expert`` real ones: one that is chosen returns the token as it
    # came, times its weight; no matrices, no row in the grouped products.
    n_zero_expert: int = 0
    n_expert_per_tok: int = 2
    # Whether the chosen experts' weights are rescaled to sum to one.
    norm_topk_prob: bool = True
    router_aux_coef: float = 0.01
    # How the router scores an expert: "softmax" over all of them, or
    # "sigmoid" of each alone (DeepSeek-V3's ``noaux_tc``).
    scoring: str = "softmax"
    # Not None: the experts are chosen by score + a learned float32
    # vector, ``bias``, that moves the choice and not the weight; the
    # value is the standard deviation it is seeded with (a trained one is
    # not zero).
    choice_bias: Optional[float] = None
    # Added to the chosen experts' weights' sum before they are divided
    # by it (LFM2's published code: 1e-6).
    topk_sum_eps: float = 0.0
    # What the chosen experts' weights are multiplied by, normalised or not.
    routed_scale: float = 1.0
    # Choice by groups (DeepSeek-V3's ``noaux_tc`` with groups): the
    # router's experts are ``n_group`` runs of neighbours, a group's score
    # is the sum of its two best scores + bias, and a token chooses among
    # the experts of its ``topk_group`` best groups alone. 1 and 1: no
    # groups.
    n_group: int = 1
    topk_group: int = 1
    # Shared experts: one SwiGLU of ``n_shared * n_inter`` beside the
    # routed ones, applied to every token.
    n_shared: int = 0
    # The leading layers whose feed-forward is a dense SwiGLU of
    # ``dense_inter`` and not a routed layer.
    first_dense: int = 0
    dense_inter: Optional[int] = None
    # ``(first, count)``: the experts held here, where a layer's experts
    # are shared out over chips. The router keeps its ``n_expert`` outputs
    # and a token its ``n_expert_per_tok`` choices, weights normalised over
    # all of them; a pair whose expert is not held is a dead row, and the
    # layer returns the held experts' part (plus the shared expert).
    experts_held: Optional[Tuple[int, int]] = None
    # Multi-token-prediction modules after the last layer (DeepSeek-V3's
    # form, :class:`PredictionModule`); 0 or 1. A family that drafts
    # through it says so in its ``serving`` (:class:`ExaoneMoeConfig`).
    mtp_layers: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring is 'softmax' or 'sigmoid': "
                             f"{self.scoring!r}")
        if self.experts_held is not None:
            first, count = held = tuple(self.experts_held)
            object.__setattr__(self, "experts_held", held)
            if first < 0 or count < 1 or first + count > self.n_expert:
                raise ValueError(
                    f"experts_held={held} is not a (first, count) share of "
                    f"the router's {self.n_expert} experts")
        if self.n_group > 1 and (
                self.n_expert % self.n_group or self.n_zero_expert
                or not 0 < self.topk_group <= self.n_group
                or self.n_expert // self.n_group < 2
                or self.topk_group * (self.n_expert // self.n_group)
                < self.n_expert_per_tok):
            raise ValueError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                f"the {self.n_expert} experts fall into equal groups of "
                f"two or more, of which the kept hold a token's "
                f"{self.n_expert_per_tok}")
        if self.first_dense and not self.dense_inter:
            raise ValueError("leading dense layers need `dense_inter`")
        if self.mtp_layers not in (0, 1):
            raise ValueError("one prediction module at most: a step drafts "
                             "one token")

    @property
    def n_expert_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_expert

    def ffn_width(self, i: int) -> Optional[int]:
        return self.dense_inter if i < self.first_dense else None

    def routed(self, **kw):
        """The routed-expert layer: what a block builds (``name=``) and
        what the serving walk applies."""
        return MoEFFN(self, **kw)

    @classmethod
    def tiny(cls) -> "MixtralConfig":
        return cls(vocab_size=512, block_size=128, n_layer=2, n_head=4,
                   n_kv_head=2, n_embd=128, n_inter=256, n_expert=4,
                   n_expert_per_tok=2)


@dataclasses.dataclass(frozen=True)
class OlmoeConfig(MixtralConfig):
    """OLMoE-1B-7B (``allenai/OLMoE-1B-7B-0125-Instruct``) as published;
    ``n_inter`` is one expert's width."""

    vocab_size: int = 50304
    block_size: int = 4096
    n_layer: int = 16
    n_head: int = 16
    n_kv_head: int = 16
    n_embd: int = 2048
    n_inter: int = 1024
    n_expert: int = 64
    n_expert_per_tok: int = 8
    norm_topk_prob: bool = False
    qk_norm: bool = True

    @classmethod
    def tiny(cls) -> "OlmoeConfig":
        return cls(vocab_size=512, block_size=128, n_layer=2, n_head=4,
                   n_kv_head=4, n_embd=64, n_inter=32, n_expert=8,
                   n_expert_per_tok=2)


def _cut_to_depth(config, published) -> None:
    """``config.layer_types`` as given, or the ``published`` pattern, cut
    to the first ``n_layer`` entries: a cut in depth keeps the list's
    head, and a configuration file keeps the list whole."""
    types = config.layer_types or published
    object.__setattr__(config, "layer_types",
                       tuple(types)[:config.n_layer])


@dataclasses.dataclass(frozen=True)
class MellumConfig(MixtralConfig):
    """Mellum2-12B-A2.5B (``JetBrains/Mellum2-12B-A2.5B-Instruct``) as
    published: 32 query heads on 4 kv heads of 128 over a hidden size of
    2,304, three window layers of 1,024 positions to every full one, a
    rotary embedding a kind (YaRN x16 over 8,192 on the full layers),
    every layer's feed-forward 64 experts of width 896 (``n_inter``)
    of which a token takes 8, renormalised. Its ``layer_types`` is the
    S S S F pattern cut to ``n_layer``; layers are held one tree each."""

    vocab_size: int = 98304
    block_size: int = 131072
    n_layer: int = 28
    n_head: int = 32
    n_kv_head: int = 4
    n_embd: int = 2304
    head_dim: int = 128
    n_inter: int = 896
    n_expert: int = 64
    n_expert_per_tok: int = 8
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    window: int = 1024
    full_rope: Rope = Rope(theta=500000.0, yarn_factor=16.0,
                           original_max_position=8192, beta_fast=32.0,
                           beta_slow=1.0,
                           attention_factor=1.2772588722239782)
    scan_layers: bool = False

    def __post_init__(self):
        _cut_to_depth(self, (
            FULL if i % 4 == 3 else WINDOW for i in range(self.n_layer)))
        super().__post_init__()

    @classmethod
    def tiny(cls) -> "MellumConfig":
        """Two periods at toy widths: head_dim 16 is not 64 / 8, window
        8, and an original length of 32 so that a context of a hundred
        positions reaches both of YaRN's regimes."""
        return cls(vocab_size=512, block_size=256, n_layer=8, n_head=8,
                   n_kv_head=2, n_embd=64, head_dim=16, n_inter=32,
                   n_expert=8, n_expert_per_tok=2, window=8,
                   rope_theta=10000.0,
                   full_rope=Rope(theta=10000.0, yarn_factor=4.0,
                                  original_max_position=32))


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig(MixtralConfig):
    """A routed config whose attention is latent
    (:mod:`raytpu.models.mla`): queries through a rank of ``q_lora_rank``,
    keys and values through a latent of ``kv_lora_rank`` and one roped
    key of ``qk_rope_dim``, behind one pool a layer. ``head_dim`` sizes
    nothing here. Layers are held one tree each."""

    # None: queries through one matrix, no bottleneck.
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_interleave: bool = True
    # The normed query latent times sqrt(n_embd / q_lora_rank), the
    # normed key/value latent times sqrt(n_embd / kv_lora_rank).
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # Each head's attended values times ``sigmoid(W_g x)_h`` before
    # ``o_proj``.
    attn_head_gate: bool = False
    scan_layers: bool = False

    def attention(self, kind: str = FULL, **kw):
        from raytpu.models.mla import LatentAttention  # it imports llama

        return LatentAttention(self, **kw)

    def attn_scope(self, kind: str) -> str:
        return "attn.mla"

    def chunk_parts(self, t: int, start, page_size: int):
        """How ``LatentAttention.step`` attends one sequence's ``t``
        tokens from ``start`` on: ``None`` absorbed, or expanded in
        ``(rows of a segment, parts)``, by these widths
        (:func:`raytpu.ops.mla_attention.expands`, ``expanded_parts``)."""
        from raytpu.ops.mla_attention import expanded_parts, expands

        if not expands(t, rank=self.kv_lora_rank, nope_dim=self.qk_nope_dim,
                       rope_dim=self.qk_rope_dim, v_dim=self.v_head_dim):
            return None
        return expanded_parts(t, start, page_size)

    @property
    def serving(self):
        from raytpu.ops.mla_attention import latent_row_width

        return dataclasses.replace(
            super().serving,
            kv_row=latent_row_width(self.kv_lora_rank, self.qk_rope_dim),
            chunk_parts=self.chunk_parts)


@dataclasses.dataclass(frozen=True)
class JoyAIConfig(LatentMoEConfig):
    """JoyAI-LLM-Flash (``jdopensource/JoyAI-LLM-Flash``, 48B-A2.7B) as
    published: 40 layers of latent attention (:mod:`raytpu.models.mla`:
    32 heads, queries through a rank of 1,536, keys and values through a
    latent of 512 and one roped key of 64, interleaved rope at theta
    32,000,000 with no scaling); layer 0 a dense SwiGLU of 7,168, the
    others 256 routed experts of 768 (``n_inter``) of which a token takes
    8 by sigmoid score + a correction bias, weights normalised and
    multiplied by 2.5, beside one shared expert. The multi-token
    prediction module is not built. Layers are held one tree each.
    ``head_dim`` (64, as the config has it) sizes nothing here."""

    vocab_size: int = 129280
    block_size: int = 131072
    n_layer: int = 40
    n_head: int = 32
    n_kv_head: int = 32
    n_embd: int = 2048
    head_dim: int = 64
    n_inter: int = 768
    n_expert: int = 256
    n_expert_per_tok: int = 8
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    rope_theta: float = 32000000.0
    scoring: str = "sigmoid"
    choice_bias: float = 0.0
    routed_scale: float = 2.5
    n_shared: int = 1
    first_dense: int = 1
    dense_inter: int = 7168

    @classmethod
    def tiny(cls) -> "JoyAIConfig":
        """A dense layer and two routed ones at toy widths; the latent is
        128 wide because the kernel slices values out of a row by whole
        lane tiles."""
        return cls(vocab_size=512, block_size=256, n_layer=3, n_head=4,
                   n_kv_head=4, n_embd=64, head_dim=16, n_inter=32,
                   n_expert=16, n_expert_per_tok=4, dense_inter=96,
                   q_lora_rank=48, kv_lora_rank=128, qk_nope_dim=16,
                   qk_rope_dim=8, v_head_dim=16)


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig(LatentMoEConfig):
    """LongCat-Flash-Chat (``meituan-longcat/LongCat-Flash-Chat``,
    560B-A27B; arXiv:2509.01322) as published: 28 layers over a hidden
    size of 6,144, each **two** latent-attention sublayers (64 heads, the
    ranks and head sizes of DeepSeek-V3, both latents scaled, interleaved
    rope at theta 1e7) with a dense SwiGLU of 12,288 each, and **one**
    routed layer that reads the first sublayer's normed stream beside its
    feed-forward and is added at the layer's end, so that the second
    sublayer does not wait for it (the shortcut, "ScMoE"): with the two
    attentions ``A``, the four norms ``N`` and the feed-forwards ``F``,

        x1 = x + A0(N(x)); h1 = N(x1); s = MoE(h1); x2 = x1 + F0(h1)
        x3 = x2 + A1(N(x2)); out = x3 + F1(N(x3)) + s

    The router is a softmax over 768 outputs, 512 routed experts of 2,048
    (``n_inter``) and 256 identity experts (``n_zero_expert``); a token
    takes 12 by score + a bias, weights the scores without it, not
    renormalised, times 6; no shared expert. A token's work is between 0
    and 12 expert products a layer, by how many of its choices are
    identities.

    **``n_layer`` counts attention sublayers**, two a published layer:
    the serving walk, the cache and the engine see 56 layers of one
    attention, one pool and one dense feed-forward each, of which the
    even ones carry a routed layer's output to the end of the next
    (``shortcut_to``). So every count of pools by layer holds as it is,
    and the walk learned one thing, to carry a value from a layer to a
    later one. ``head_dim`` sizes nothing here."""

    vocab_size: int = 131072
    block_size: int = 131072
    n_layer: int = 56
    n_head: int = 64
    n_kv_head: int = 64
    n_embd: int = 6144
    n_inter: int = 2048
    n_expert: int = 512
    n_zero_expert: int = 256
    n_expert_per_tok: int = 12
    norm_topk_prob: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    choice_bias: float = 0.0
    routed_scale: float = 6.0
    dense_inter: int = 12288
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True

    def __post_init__(self):
        super().__post_init__()
        if self.n_layer % 2:
            raise ValueError(f"n_layer counts sublayers, two a published "
                             f"layer: {self.n_layer} is odd")

    def ffn_width(self, i: int) -> int:
        return self.dense_inter

    def shortcut_to(self, i: int) -> Optional[int]:
        return i + 1 if i % 2 == 0 else None

    def layer_scope(self, i: int) -> str:
        return f"sublayer.{i % 2}"

    @classmethod
    def tiny(cls) -> "LongcatFlashConfig":
        """Two published layers (four sublayers) at toy widths: of 32
        routed and 16 identity experts a token takes 6, so 32 shares of
        one expert add up to the layer; the latent is 128 wide because
        the kernel slices values out of a row by whole lane tiles."""
        return cls(vocab_size=512, block_size=256, n_layer=4, n_head=4,
                   n_kv_head=4, n_embd=64, n_inter=32, n_expert=32,
                   n_zero_expert=16, n_expert_per_tok=6, dense_inter=96,
                   q_lora_rank=48, kv_lora_rank=128, qk_nope_dim=16,
                   qk_rope_dim=8, v_head_dim=16, choice_bias=0.01)


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig(LatentMoEConfig):
    """GLM-5 (``zai-org/GLM-5``, ``model_type: glm_moe_dsa``, 744B-A40B)
    as published: 78 layers over a hidden size of 6,144 of latent
    attention that reads only the cached positions a learned indexer
    chooses (:class:`raytpu.models.mla.SparseLatentAttention`: 64 heads
    of 192 not roped + 64 roped, values of 256, queries through a rank of
    2,048, keys and values through a latent of 512, interleaved rope at
    theta 1e6; the indexer 32 heads of 128 that keep the 2,048
    best-scored positions). Layers 0-2 a dense SwiGLU of 12,288, the
    others JoyAI's routed layer to the letter: 256 experts of 2,048
    (``n_inter``), 8 a token by sigmoid score + a correction bias,
    weights normalised and times 2.5, beside one shared expert. The
    multi-token-prediction module and the indexer's training loss are
    not built. A layer keeps two pools under one block table, the
    latent rows and the index keys (``serving.indexer``). Layers are
    held one tree each; ``head_dim`` sizes nothing here."""

    vocab_size: int = 154880
    block_size: int = 202752
    n_layer: int = 78
    n_head: int = 64
    n_kv_head: int = 64
    n_embd: int = 6144
    head_dim: int = 64
    n_inter: int = 2048
    n_expert: int = 256
    n_expert_per_tok: int = 8
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    scoring: str = "sigmoid"
    choice_bias: float = 0.0
    routed_scale: float = 2.5
    n_shared: int = 1
    first_dense: int = 3
    dense_inter: int = 12288
    q_lora_rank: int = 2048
    qk_nope_dim: int = 192
    v_head_dim: int = 256
    # The indexer: a query keeps the ``index_topk`` cached positions its
    # ``index_n_head`` heads of ``index_head_dim`` score best.
    index_topk: int = 2048
    index_n_head: int = 32
    index_head_dim: int = 128
    index_rope_interleave: bool = True
    index_norm_eps: float = 1e-6  # the index key's LayerNorm

    def __post_init__(self):
        super().__post_init__()
        if self.index_head_dim < self.qk_rope_dim:
            raise ValueError(
                f"an index key of {self.index_head_dim} values has no "
                f"room for the {self.qk_rope_dim} that are roped")

    def attention(self, kind: str = FULL, **kw):
        from raytpu.models.mla import SparseLatentAttention

        return SparseLatentAttention(self, **kw)

    @property
    def serving(self):
        # A chunk reads the rows its indexer chooses, not its context.
        return dataclasses.replace(
            super().serving, indexer=(self.index_head_dim, self.index_topk),
            chunk_parts=None)

    @classmethod
    def tiny(cls) -> "GlmDsaConfig":
        """A dense layer and two routed ones at toy widths, whose
        indexer keeps 16 positions: a context of 96 is six times that.
        The latent is 128 wide because the kernel slices values out of a
        row by whole lane tiles."""
        return cls(vocab_size=512, block_size=256, n_layer=3, n_head=4,
                   n_kv_head=4, n_embd=64, head_dim=16, n_inter=32,
                   n_expert=16, n_expert_per_tok=4, first_dense=1,
                   dense_inter=96, q_lora_rank=48, kv_lora_rank=128,
                   qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                   choice_bias=0.01, index_topk=16, index_n_head=4,
                   index_head_dim=16)


@dataclasses.dataclass(frozen=True)
class LingHybridConfig(LatentMoEConfig):
    """Ling-3.0-flash-VL's language model (``inclusionAI/Ling-3.0-flash-
    VL``) as published: 42 layers over a hidden size of 2,560 and 32 heads
    of 128, layer ``i`` a latent-attention layer where ``(i + 1) % 6 ==
    0`` (queries through one matrix, a latent of 512 and one roped key of
    64 at theta 6e6, values of 128, a sigmoid gate a head before
    ``o_proj``) and a KDA layer everywhere else
    (:mod:`raytpu.models.kda`: short convolutions of 4 taps, a decay a
    channel bounded at -5, a float32 matrix a head); layers 0 and 1 a
    dense SwiGLU of 6,144, the others 512 routed experts of 768
    (``n_inter``), 8 a token by sigmoid score + a bias inside the best 4
    of 8 groups, weights normalised and times 2.5, beside one shared
    expert. The prediction module, the SwiGLU clamp of the last layers
    and the vision tower are not built. ``layer_types`` is that pattern
    cut to ``n_layer`` (a configuration that holds other layers gives its
    own); a KDA layer has no pool but two state arrays
    (``serving.layer_states``), a latent layer one pool. Layers are held
    one tree each."""

    vocab_size: int = 157184
    block_size: int = 131072
    n_layer: int = 42
    n_head: int = 32
    n_kv_head: int = 32
    n_embd: int = 2560
    head_dim: int = 128
    n_inter: int = 768
    n_expert: int = 512
    n_expert_per_tok: int = 8
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    rope_theta: float = 6000000.0
    scoring: str = "sigmoid"
    choice_bias: float = 0.0
    routed_scale: float = 2.5
    n_group: int = 8
    topk_group: int = 4
    n_shared: int = 1
    first_dense: int = 2
    dense_inter: int = 6144
    q_lora_rank: Optional[int] = None
    attn_head_gate: bool = True
    # The published rope rotates adjacent pairs only where the config says
    # so; this one has no such key.
    rope_interleave: bool = False
    conv_taps: int = 4
    # A KDA layer's gate: ``g = kda_lower_bound * sigmoid(exp(A_log) (W_f
    # x + dt_bias))``; and what a seeded ``A`` and ``dt_bias`` are drawn
    # from, uniformly (a trained gate is what it is).
    kda_lower_bound: float = -5.0
    kda_gate_init: Tuple[Tuple[float, float], Tuple[float, float]] = (
        (1.0, 4.0), (-4.0, 0.0))

    def __post_init__(self):
        _cut_to_depth(self, (
            FULL if (i + 1) % 6 == 0 else KDA for i in range(self.n_layer)))
        super().__post_init__()

    def attention(self, kind: str = FULL, **kw):
        if kind == KDA:
            return LlamaConfig.attention(self, kind, **kw)
        return super().attention(kind, **kw)

    def attn_scope(self, kind: str) -> Optional[str]:
        return None if kind == KDA else super().attn_scope(kind)

    def layer_state(self, kind: str):
        if kind != KDA:
            return None
        h, d = self.n_head, self.head_dim
        return (State((h, d, d), jnp.float32),
                State((self.conv_taps - 1, 3 * h * d)))

    @classmethod
    def tiny(cls) -> "LingHybridConfig":
        """A dense KDA layer and a whole period after it (five KDA, one
        latent, routed) at toy widths: of 16 experts in 4 groups a token
        takes 4 inside its best 2; the latent is 128 wide because the
        kernel slices values out of a row by whole lane tiles."""
        return cls(vocab_size=512, block_size=256, n_layer=7, n_head=4,
                   n_kv_head=4, n_embd=64, head_dim=16, n_inter=32,
                   n_expert=16, n_expert_per_tok=4, n_group=4, topk_group=2,
                   first_dense=1, dense_inter=96, kv_lora_rank=128,
                   qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                   choice_bias=0.01, layer_types=(KDA,) * 6 + (FULL,))


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig(MixtralConfig):
    """K-EXAONE-236B-A23B (``LGAI-EXAONE/K-EXAONE-236B-A23B``,
    ``model_type: exaone_moe``) as published: 64 query heads on 8 kv
    heads of 128 over a hidden size of 6,144, three window layers of 128
    positions to every full one, rope at theta 1e6 on the window layers
    and none on the full ones, a norm over each head of q and of k;
    layer 0 a dense SwiGLU of 18,432, the others 128 routed experts of
    2,048 (``n_inter``) of which a token takes 8 by sigmoid score + a
    correction bias, weights normalised and multiplied by 2.5, beside one
    shared expert; one prediction module. ``layer_types`` is the S S S F
    pattern cut to ``n_layer``; layers are held one tree each."""

    vocab_size: int = 153600
    block_size: int = 262144
    n_layer: int = 48
    n_head: int = 64
    n_kv_head: int = 8
    n_embd: int = 6144
    head_dim: int = 128
    n_inter: int = 2048
    n_expert: int = 128
    n_expert_per_tok: int = 8
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    window: int = 128
    qk_head_norm: bool = True
    rope_kinds: Optional[Tuple[str, ...]] = (WINDOW,)
    scoring: str = "sigmoid"
    choice_bias: float = 0.0
    routed_scale: float = 2.5
    n_shared: int = 1
    first_dense: int = 1
    dense_inter: int = 18432
    mtp_layers: int = 1
    scan_layers: bool = False

    def __post_init__(self):
        _cut_to_depth(self, (
            FULL if i % 4 == 3 else WINDOW for i in range(self.n_layer)))
        super().__post_init__()

    @property
    def serving(self):
        served = super().serving
        if not self.mtp_layers:
            return served
        return dataclasses.replace(served, drafting=Drafting(
            mtp_prefill, mtp_step, draft_prefill, draft_step,
            pools=self.mtp_layers))

    @classmethod
    def tiny(cls) -> "ExaoneMoeConfig":
        """The dense layer, one period after it and the module at toy
        widths: window 8, four query heads a kv head, and of 8 experts a
        token takes 2."""
        return cls(vocab_size=512, block_size=256, n_layer=5, n_head=8,
                   n_kv_head=2, n_embd=64, head_dim=16, n_inter=32,
                   n_expert=8, n_expert_per_tok=2, dense_inter=96,
                   window=8, rope_theta=10000.0)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(MixtralConfig):
    """LFM2-24B-A2B (``LiquidAI/LFM2-24B-A2B``, ``model_type: lfm2_moe``)
    as published: 40 layers over a hidden size of 2,048, three gated
    short convolutions of 3 taps (``conv_L_cache``) to every full
    attention layer (32 query heads on 8 kv heads of 64, a norm over each
    head of q and of k, rope at theta 1e6); layers 0 and 1
    (``num_dense_layers``) a dense SwiGLU of 11,776, the others 64 routed
    experts of 1,536 (``n_inter``) of which a token takes 4 by sigmoid
    score + a bias (``use_expert_bias``), weights over their sum + 1e-6,
    no shared expert; the head is the embedding (the LFM2 family's
    convention: the published config has no key for it). ``layer_types``
    is the published list cut to ``n_layer``; layers are held one tree
    each. A conv layer has no pool of pages: ``serving.layer_states``."""

    vocab_size: int = 65536
    block_size: int = 128000
    n_layer: int = 40
    n_head: int = 32
    n_kv_head: int = 8
    n_embd: int = 2048
    head_dim: int = 64
    n_inter: int = 1536
    n_expert: int = 64
    n_expert_per_tok: int = 4
    norm_topk_prob: bool = True
    topk_sum_eps: float = 1e-6
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    qk_head_norm: bool = True
    conv_taps: int = 3
    tie_embeddings: bool = True
    scoring: str = "sigmoid"
    choice_bias: float = 0.0
    first_dense: int = 2
    dense_inter: int = 11776
    scan_layers: bool = False

    def __post_init__(self):
        # Published: full attention at layers 2, 6, 10, ... 38.
        _cut_to_depth(self, (
            FULL if i % 4 == 2 else CONV for i in range(self.n_layer)))
        super().__post_init__()

    @classmethod
    def tiny(cls) -> "Lfm2MoeConfig":
        """Both dense layers and one period after them at toy widths:
        conv conv.dense | full conv conv conv, four query heads a kv
        head, and of 8 experts a token takes 2."""
        return cls(vocab_size=512, block_size=256, n_layer=6, n_head=8,
                   n_kv_head=2, n_embd=64, head_dim=8, n_inter=32,
                   n_expert=8, n_expert_per_tok=2, dense_inter=96,
                   rope_theta=10000.0, choice_bias=0.01)


class MoEFFN(nn.Module):
    """Top-k routed SwiGLU experts, dropless.

    ``x`` is ``[..., D]``; ``live`` (same leading shape, bool) marks the
    rows that are tokens: padding is routed nowhere, costs no expert a
    row and is not counted. Returns ``(y, tokens)``: the layer's output
    and the int32 ``[n_expert]`` number of live tokens each expert
    received, after them, where the router has identity experts, two
    values more (``Serving.expert_pairs``): the live (token, choice)
    pairs that chose one, and all live pairs. The router runs in float32
    at full precision over all the
    experts; the expert matrices multiply in ``config.dtype`` with
    float32 sums, gate and up in one pass over the sorted rows and down
    in another (``ops.grouped_matmul``: which of its two ways, and in
    what blocks of an expert's columns, is a matter of the operands'
    shapes and type and of where the program is lowered, and both read
    an expert's matrices only if a row chose it).

    Scores are a softmax over the experts, or each expert's own sigmoid
    (``config.scoring``), then chosen by score + ``bias`` where the config
    has a ``choice_bias`` (the weights are the scores without it), among
    the experts of the token's best groups where it has groups
    (``n_group``, ``topk_group``: a share of the experts still scores all
    of them and all the groups),
    normalised over the chosen (``norm_topk_prob``: over their sum, plus
    ``topk_sum_eps``) and multiplied by ``routed_scale``. With
    ``experts_held = (first, count)`` the three matrices hold ``count``
    experts, the router still ``n_expert``: a pair whose expert lies outside
    the share is a dead row like padding's, ``tokens`` counts the held
    experts alone, and the output is their part of the layer's. A shared
    expert (``n_shared``) is added under ``jax.named_scope("moe.shared")``.

    With ``n_zero_expert`` the router and the bias are ``n_expert +
    n_zero_expert`` wide. A chosen index past the real experts is an
    identity expert: a dead row for the grouped products, like another
    chip's expert, whose weight goes to the identity term, ``(sum of the
    token's identity weights) x`` added in float32 with the routed sum
    under ``jax.named_scope("moe.zero")``; whole on every chip, which owns
    its tokens, whatever share of the real experts it holds.
    """

    config: MixtralConfig

    @nn.nowrap  # a plain function of the config: no scope of its own
    def route(self, probs, bias):
        """A token's choices and their weights from its scores ``probs``
        [N, outputs] and the choice ``bias`` (``None``: the config has
        none): the ``n_expert_per_tok`` largest of score + bias (inside
        the token's ``topk_group`` best groups, where the config has
        groups), weighed by the score without it, normalised and scaled
        as the config says -> ``(topw, topi)``, both [N, k]."""
        c = self.config
        choice = probs if bias is None else probs + bias
        if c.n_group > 1:
            # The groups' scores, the best ``topk_group`` of them, and
            # every expert of another group out of the choice.
            groups = choice.reshape(-1, c.n_group, c.n_expert // c.n_group)
            # (Two maxima and not ``top_k``, which sorts on the TPU.)
            best = jnp.argmax(groups, axis=-1, keepdims=True)
            ahead = jnp.arange(groups.shape[-1]) == best
            score = jnp.max(groups, axis=-1) + jnp.max(
                jnp.where(ahead, -jnp.inf, groups), axis=-1)
            _, kept = jax.lax.top_k(score, c.topk_group)
            keep = jnp.any(kept[..., None] == jnp.arange(c.n_group), axis=-2)
            choice = jnp.where(keep[..., None], groups, -jnp.inf) \
                .reshape(choice.shape)
        if bias is not None or c.n_group > 1:
            _, topi = jax.lax.top_k(choice, c.n_expert_per_tok)
            topw = jnp.take_along_axis(probs, topi, axis=-1)
        else:
            topw, topi = jax.lax.top_k(probs, c.n_expert_per_tok)
        if c.norm_topk_prob:
            total = jnp.sum(topw, axis=-1, keepdims=True)
            if c.topk_sum_eps:
                total = total + c.topk_sum_eps
            topw = topw / total
        if c.routed_scale != 1.0:
            topw = topw * c.routed_scale
        return topw, topi

    @nn.nowrap
    def identity(self, xf, topw, topi):
        """The identity experts' term, float32 [N, D]: each token times
        the sum of its weights for the identity experts it chose."""
        chosen = jnp.where(topi >= self.config.n_expert, topw, 0.0)
        return xf.astype(jnp.float32) * jnp.sum(chosen, axis=-1,
                                                keepdims=True)

    @nn.compact
    def __call__(self, x, live=None):
        c = self.config
        d = x.shape[-1]
        k, e = c.n_expert_per_tok, c.n_expert_held
        scored = c.n_expert + c.n_zero_expert  # the router's outputs
        xf = x.reshape(-1, d)
        n = xf.shape[0]
        with jax.named_scope("moe.router"):
            router = nn.Dense(scored, use_bias=False, dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              name="router")(xf.astype(jnp.float32))
            if c.scoring == "softmax":
                probs = jax.nn.softmax(router, axis=-1)       # [N, E]
            else:
                probs = jax.nn.sigmoid(router)
            bias = None
            if c.choice_bias is not None:
                bias = self.param(
                    "bias", nn.initializers.normal(c.choice_bias),
                    (scored,), jnp.float32)
            topw, topi = self.route(probs, bias)              # [N, k]

        # Switch-style load balance: E * sum_e(frac_routed_e * mean_prob_e)
        top1 = jax.nn.one_hot(topi[:, 0], scored, dtype=jnp.float32)
        aux = scored * jnp.sum(jnp.mean(top1, axis=0)
                               * jnp.mean(probs, axis=0))
        self.sow("intermediates", "moe_aux", aux)

        init = nn.initializers.normal
        wi = self.param("wi", init(d ** -0.5), (e, d, c.n_inter),
                        c.param_dtype)
        wg = self.param("wg", init(d ** -0.5), (e, d, c.n_inter),
                        c.param_dtype)
        wo = self.param("wo", init(c.n_inter ** -0.5), (e, c.n_inter, d),
                        c.param_dtype)
        with jax.named_scope("moe.experts"):
            # One row per (token, expert) pair, in order of expert. A
            # dead row's expert is ``e``: it sorts past the last group,
            # belongs to none and is multiplied by nothing.
            flat = topi.reshape(n * k)
            if c.experts_held is not None:
                # An expert of another chip's share: no row here.
                flat = flat - c.experts_held[0]
                flat = jnp.where((flat >= 0) & (flat < e), flat, e)
            elif c.n_zero_expert:
                flat = jnp.minimum(flat, e)  # an identity: no row either
            if live is not None:
                flat = jnp.where(jnp.repeat(live.reshape(n), k), flat, e)
            order = jnp.argsort(flat)
            tokens = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
            rows = xf.astype(c.dtype)[order // k]             # [kN, D]
            # Row groups x their experts: the grouped kernel or
            # ``ragged_dot``, by shape and device (ops/grouped_matmul).
            h = grouped_swiglu(rows, wg.astype(c.dtype), wi.astype(c.dtype),
                               tokens)
            out = grouped_matmul(h, wo.astype(c.dtype), tokens)
            # Weighted and summed per token in float32, in the order of
            # the token's own top-k: the same whatever else is batched.
            out = jnp.where((flat[order] < e)[:, None],
                            out.astype(jnp.float32)
                            * topw.reshape(n * k)[order][:, None], 0.0)
            y = jnp.sum(out[jnp.argsort(order)].reshape(n, k, d), axis=1)
        if c.n_zero_expert:
            with jax.named_scope("moe.zero"):
                y = y + self.identity(xf, topw, topi)
                tokens_live = (jnp.ones(n, bool) if live is None
                               else live.reshape(n))
                tokens = jnp.concatenate([tokens, jnp.stack([
                    jnp.sum((topi >= c.n_expert) & tokens_live[:, None]),
                    k * jnp.sum(tokens_live)]).astype(jnp.int32)])
        y = y.reshape(x.shape).astype(c.dtype)
        if c.n_shared:
            with jax.named_scope("moe.shared"):
                y = y + LlamaMLP(c, c.n_shared * c.n_inter, name="shared")(x)
        return y, tokens


class MixtralBlock(nn.Module):
    """``dense_width``: the block's feed-forward is a SwiGLU that wide
    and not the routed layer (``config.ffn_width`` of its index).
    ``routed_beside``: a routed layer reads the normed stream beside that
    SwiGLU, and the block returns its output as a second value, for a
    later block to add at its end as ``shortcut``
    (``config.shortcut_to``)."""

    config: MixtralConfig
    kind: str = FULL
    dense_width: Optional[int] = None
    routed_beside: bool = False

    @nn.compact
    def __call__(self, x, shortcut=None):
        c = self.config
        x = x + c.attention(self.kind, name=op_name(self.kind))(
            RMSNorm(dtype=c.dtype, eps=c.norm_eps, name="input_norm")(x))
        h = RMSNorm(dtype=c.dtype, eps=c.norm_eps, name="post_attn_norm")(x)
        if self.dense_width is None:
            x = x + c.routed(name="moe")(h)[0]
        else:
            x = x + LlamaMLP(c, self.dense_width, name="mlp")(h)
            if self.routed_beside:  # carried on, not added here
                return x, c.routed(name="moe")(h)[0]
        return x if shortcut is None else x + shortcut


class PredictionModule(nn.Module):
    """One multi-token-prediction module in DeepSeek-V3's form
    (arXiv:2412.19437, section 2.2), whose parameter names the published
    configs use. For position ``i`` it takes the model's residual stream
    after its last block, ``hidden`` (before the final norm), and the
    embedding of the token that follows, ``next_emb``: ``u = W_eh
    [RMSNorm_e(next_emb) ; RMSNorm_h(hidden)]`` (twice the width to
    once), one block of the model's routed shape with full attention
    over all ``u`` (keys and values of its own), and a final norm of its
    own. The model's head over the result is the logits of the token
    after the next. This is its whole-sequence form, which makes its
    parameters; a served model runs it through :func:`draft_step` and
    :func:`draft_prefill`. (Its training loss is not built:
    :class:`Mixtral` returns the model's own logits.)"""

    config: MixtralConfig

    @nn.compact
    def __call__(self, hidden, next_emb):
        c = self.config
        norm = functools.partial(RMSNorm, dtype=c.dtype, eps=c.norm_eps)
        u = jnp.concatenate([norm(name="enorm")(next_emb),
                             norm(name="hnorm")(hidden)], axis=-1)
        u = nn.Dense(c.n_embd, use_bias=False, dtype=c.dtype,
                     param_dtype=c.param_dtype, name="eh_proj")(u)
        u = MixtralBlock(c, FULL, None, name="block")(u)
        return norm(name="final_norm")(u)


class Mixtral(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        c = self.config
        embed = nn.Embed(c.vocab_size, c.n_embd, dtype=c.dtype,
                         param_dtype=c.param_dtype, name="embed_tokens")
        x = embed(tokens)
        block = remat_block(MixtralBlock, c.remat)
        if c.scan_layers and not c.layer_types and not c.first_dense:
            x, _ = nn.scan(
                lambda mdl, carry, _: (mdl(carry), None),
                variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True},
                length=c.n_layer,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block(c, name="layers"), x, None)
        else:
            # Layers of different kinds are not one scanned body.
            shortcuts = {}  # a routed layer's output, by where it is added
            for i in range(c.n_layer):
                to = c.shortcut_to(i)
                layer = block(c, c.layer_kind(i), c.ffn_width(i),
                              to is not None, name=f"layers_{i}")
                out = layer(x, shortcuts.pop(i)) if i in shortcuts \
                    else layer(x)
                if to is None:
                    x = out
                else:
                    x, shortcuts[to] = out
        if c.mtp_layers and self.is_initializing():
            # The module's parameters are made with the model's; the
            # training forward does not read them.
            PredictionModule(c, name="mtp")(
                x, self.variables["params"]["embed_tokens"]["embedding"]
                .astype(c.dtype)[jnp.roll(tokens, -1, axis=-1)])
        x = RMSNorm(dtype=c.dtype, eps=c.norm_eps, name="final_norm")(x)
        if return_hidden:
            return x
        if c.tie_embeddings:
            return embed.attend(x).astype(jnp.float32)
        # Untied output head, as llama's.
        logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                          param_dtype=c.param_dtype, name="lm_head")(x)
        return logits.astype(jnp.float32)


def mixtral_loss_fn(model: Mixtral, params, tokens):
    """Next-token cross-entropy + router load-balance auxiliary."""
    c = model.config
    targets = tokens[:, 1:]
    logits, mutables = model.apply({"params": params}, tokens,
                                   mutable=["intermediates"])
    logits = logits[:, :-1]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    label = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    xent = (lse - label).mean()
    aux_leaves = jax.tree_util.tree_leaves(mutables.get("intermediates", {}))
    aux = (sum(jnp.sum(a) for a in aux_leaves) / max(1, c.n_layer)
           if aux_leaves else 0.0)
    return xent + c.router_aux_coef * aux


def make_train_step(model: Mixtral, optimizer):
    from raytpu.models.llama import make_train_step as _shared

    return _shared(model, optimizer, loss_fn=mixtral_loss_fn)


def init_params(model: Mixtral, config: MixtralConfig, seed: int = 0,
                batch: int = 2):
    """Seeded parameters, made inside one jitted program: the forward
    pass ``model.init`` traces is dead code there, so a 7 B tree costs
    its own bytes and no activations. Same signature as the llama
    helper."""
    tokens = jnp.zeros((batch, config.block_size), jnp.int32)
    return jax.jit(lambda key: model.init(key, tokens)["params"])(
        jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# Serving a model that drafts for itself (``Serving.drafting``): the
# model's two walks, which also give the residual stream the module
# reads, and the module's two. The module's pool is the last of
# ``k_caches`` / ``v_caches``, behind the full layers' tables and dests.
# ---------------------------------------------------------------------------


def _embedded(c: MixtralConfig, params, tokens):
    return params["embed_tokens"]["embedding"].astype(c.dtype)[tokens]


def mtp_prefill(config, params, tokens, dests, k_caches, v_caches):
    """:func:`raytpu.models.llama.llama_prefill` over the model's pools,
    the module's handed on as they are, with the residual stream [1, T, E]
    as the last value."""
    c, n = config, config.n_layer
    live = live_rows(dests, k_caches[0])[None]
    logits, ks, vs, count, hidden = _serve(
        c, params, _embedded(c, params, tokens), live, lambda i: (),
        whole=True, hidden=True)
    per_layer = [of_kind(dests, c.layer_kind(i)) for i in range(n)]
    ks, vs = write_prompt_rows(k_caches[:n], v_caches[:n], per_layer, ks, vs)
    return logits[0], ks + k_caches[n:], vs + v_caches[n:], count, hidden


def mtp_step(config, params, tokens, positions, dests, block_tables,
             k_caches, v_caches):
    """:func:`raytpu.models.llama.llama_step` over the model's pools, the
    module's handed on as they are, with the residual stream [B, T, E] as
    the last value. A decode step is two positions a sequence: ``tokens``
    [B, 2], the last emitted one and the draft, -> fp32 logits
    [B, 2, V]."""
    c = config
    logits, ks, vs, count, hidden = _serve(
        c, params, _embedded(c, params, tokens.reshape(-1)),
        live_rows(dests, k_caches[0]).reshape(-1),
        lambda i: (k_caches[i], v_caches[i],
                   of_kind(dests, c.layer_kind(i)),
                   of_kind(block_tables, c.layer_kind(i)), positions),
        hidden=True)
    return (logits.reshape(*tokens.shape, -1), ks + k_caches[c.n_layer:],
            vs + v_caches[c.n_layer:], count,
            hidden.reshape(*tokens.shape, -1))


def _module(c: MixtralConfig, params, hidden, next_tokens, live, cache_args):
    """:class:`PredictionModule` over ``hidden`` and the tokens that
    follow, attending through ``c.attention(FULL).step(h, *cache_args)``
    over rows [B * T, E] (``prefill(h)`` of a whole prompt [1, T, E],
    where there are no ``cache_args``) as a block of :func:`_serve` does,
    under
    ``jax.named_scope("attn.mtp")``. Returns the module's normed output,
    what its attention returned of K and V, and its expert count."""
    mp = params["mtp"]
    norm = RMSNorm(dtype=c.dtype, eps=c.norm_eps)

    def normed(name, x, of=mp):
        return norm.apply({"params": of[name]}, x)

    u = jnp.concatenate(
        [normed("enorm", _embedded(c, params, jnp.maximum(next_tokens, 0))),
         normed("hnorm", hidden)], axis=-1)
    u = jnp.dot(u, mp["eh_proj"]["kernel"].astype(c.dtype))
    bp = mp["block"]
    with jax.named_scope("attn.mtp"):
        y, k, v = c.attention(FULL).apply(
            {"params": bp["attn"]}, normed("input_norm", u, bp),
            *cache_args, method="step" if cache_args else "prefill")
    u = u + y
    y, count = c.routed().apply({"params": bp["moe"]},
                                normed("post_attn_norm", u, bp), live)
    return normed("final_norm", u + y), k, v, count


def draft_prefill(config, params, hidden, next_tokens, row, dests,
                  k_caches, v_caches):
    """The module over a whole prompt: ``hidden`` [1, T, E] beside
    ``next_tokens`` [1, T], its K and V written at the full layers'
    ``dests`` -> (its logits of row ``row`` [V], k_caches, v_caches,
    count [experts])."""
    c = config
    dests = of_kind(dests, FULL)
    live = live_rows(dests, k_caches[0])[None]
    x, k, v, count = _module(c, params, hidden, next_tokens, live, ())
    ks, vs = write_prompt_rows(k_caches[c.n_layer:], v_caches[c.n_layer:],
                               dests, [k], [v])
    return (_lm_logits(c, params, x[0, row]), k_caches[:c.n_layer] + ks,
            v_caches[:c.n_layer] + vs, count)


def draft_step(config, params, hidden, next_tokens, row, positions, dests,
               block_tables, k_caches, v_caches):
    """The module behind the model's :func:`mtp_step`, against its pool:
    ``hidden`` [B, T, E] beside the tokens that follow, ``next_tokens``
    [B, T], of which a sequence's rows up to ``row[b]`` count: a prompt's
    chunk up to its last live row, a decode step's two up to the last
    token the step kept (a rejected draft's place holds any id: its row
    is routed nowhere, attends nothing that is kept and is written again
    by the next step) -> (its logits of row ``row[b]`` of each sequence
    [B, V], k_caches, v_caches, count)."""
    c = config
    dests = of_kind(dests, FULL)
    live = live_rows(dests, k_caches[0]) \
        & (jnp.arange(hidden.shape[1]) <= row[:, None])
    x, k, v, count = _module(
        c, params, hidden.reshape(-1, hidden.shape[-1]),
        next_tokens.reshape(-1), live.reshape(-1),
        (k_caches[c.n_layer], v_caches[c.n_layer], dests,
         of_kind(block_tables, FULL), positions))
    x = jnp.take_along_axis(x.reshape(hidden.shape), row[:, None, None],
                            axis=1)[:, 0]
    return (_lm_logits(c, params, x), k_caches[:c.n_layer] + [k],
            v_caches[:c.n_layer] + [v], count)


# Mellum2's training forward is Mixtral's over a config whose layers are
# of two kinds (``MixtralBlock.kind``); JoyAI-LLM-Flash's over one whose
# attention is latent and whose first layer is dense.
Mellum = Mixtral
JoyAI = Mixtral
ExaoneMoe = Mixtral
Lfm2Moe = Mixtral
LongcatFlash = Mixtral
GlmDsa = Mixtral
LingHybrid = Mixtral
