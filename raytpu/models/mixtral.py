"""Sparse-MoE decoders — the third model family: Mixtral and OLMoE.

Reference scope: MoE machinery is absent from the reference (SURVEY.md
§2.5 EP row); serving/training MoE models there is delegated to user
libraries. Here the family is first-class and TPU-shaped: llama blocks
(RMSNorm/RoPE/GQA via :mod:`raytpu.models.llama`) whose feed-forward is
one top-k routed expert layer, :class:`MoEFFN`. It is *dropless*: every
(token, expert) pair the router chooses is computed, so a token's output
does not depend on what shares its batch. The assignments are sorted by
expert and the three expert matrices multiplied group by group
(``jax.lax.ragged_dot``, which the TPU compiler turns into a kernel of
its own that reads only the experts that received a row), so the work
grows with ``n_expert_per_tok`` and not with ``n_expert``. A Switch-style
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
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from raytpu.models.llama import (FULL, WINDOW, LlamaAttention, LlamaConfig,
                                 RMSNorm, Rope)


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    n_expert: int = 8
    n_expert_per_tok: int = 2
    # Whether the chosen experts' weights are rescaled to sum to one.
    norm_topk_prob: bool = True
    router_aux_coef: float = 0.01

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
        # The published pattern, or the first ``n_layer`` entries of a
        # longer list (a cut in depth keeps the list's head).
        types = self.layer_types or tuple(
            FULL if i % 4 == 3 else WINDOW for i in range(self.n_layer))
        object.__setattr__(self, "layer_types",
                           tuple(types)[:self.n_layer])
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


class MoEFFN(nn.Module):
    """Top-k routed SwiGLU experts, dropless.

    ``x`` is ``[..., D]``; ``live`` (same leading shape, bool) marks the
    rows that are tokens: padding is routed nowhere, costs no expert a
    row and is not counted. Returns ``(y, tokens)``: the layer's output
    and the int32 ``[n_expert]`` number of live tokens each expert
    received. The router runs in float32 at full precision over all the
    experts; the expert matrices multiply in ``config.dtype``.
    """

    config: MixtralConfig

    @nn.compact
    def __call__(self, x, live=None):
        c = self.config
        d = x.shape[-1]
        k, e = c.n_expert_per_tok, c.n_expert
        xf = x.reshape(-1, d)
        n = xf.shape[0]
        with jax.named_scope("moe.router"):
            router = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              name="router")(xf.astype(jnp.float32))
            probs = jax.nn.softmax(router, axis=-1)           # [N, E]
            topw, topi = jax.lax.top_k(probs, k)              # [N, k]
            if c.norm_topk_prob:
                topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

        # Switch-style load balance: E * sum_e(frac_routed_e * mean_prob_e)
        top1 = jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32)
        aux = e * jnp.sum(jnp.mean(top1, axis=0)
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
            if live is not None:
                flat = jnp.where(jnp.repeat(live.reshape(n), k), flat, e)
            order = jnp.argsort(flat)
            tokens = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
            rows = xf.astype(c.dtype)[order // k]             # [kN, D]
            grouped = jax.lax.ragged_dot  # row groups x their experts
            h = (nn.silu(grouped(rows, wg.astype(c.dtype), tokens))
                 * grouped(rows, wi.astype(c.dtype), tokens))
            out = grouped(h, wo.astype(c.dtype), tokens)
            # Weighted and summed per token in float32, in the order of
            # the token's own top-k: the same whatever else is batched.
            out = jnp.where((flat[order] < e)[:, None],
                            out.astype(jnp.float32)
                            * topw.reshape(n * k)[order][:, None], 0.0)
            y = jnp.sum(out[jnp.argsort(order)].reshape(n, k, d), axis=1)
        return y.reshape(x.shape).astype(c.dtype), tokens


class MixtralBlock(nn.Module):
    config: MixtralConfig
    kind: str = FULL

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = x + LlamaAttention(c, self.kind, name="attn")(
            RMSNorm(dtype=c.dtype, eps=c.norm_eps, name="input_norm")(x))
        y, _ = MoEFFN(c, name="moe")(
            RMSNorm(dtype=c.dtype, eps=c.norm_eps,
                    name="post_attn_norm")(x))
        return x + y


class Mixtral(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        c = self.config
        x = nn.Embed(c.vocab_size, c.n_embd, dtype=c.dtype,
                     param_dtype=c.param_dtype,
                     name="embed_tokens")(tokens)
        block = MixtralBlock
        if c.remat and c.remat != "none":
            policy = None
            if c.remat == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            block = nn.remat(MixtralBlock, prevent_cse=False, policy=policy)
        if c.scan_layers and not c.layer_types:
            x, _ = nn.scan(
                lambda mdl, carry, _: (mdl(carry), None),
                variable_axes={"params": 0, "intermediates": 0},
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


# Mellum2's training forward is Mixtral's over a config whose layers are
# of two kinds (``MixtralBlock.kind``).
Mellum = Mixtral
