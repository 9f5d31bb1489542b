"""The ``olmoe`` family: OLMoE-1B-7B, a decoder whose feed-forward is a
routed-expert layer. Same interface as ``gpt2.py`` (``README.md`` lists
it), plus ``moe_shape`` and ``active_param_count`` for the expert
layer's readers.

Program side: ``raytpu/models/mixtral.py`` (``OlmoeConfig``, ``Mixtral``,
``MoEFFN``), served by the llama family's three walks. The plain
reference below is the published forward pass ("OLMoE: Open
Mixture-of-Experts Language Models", Muennighoff et al. 2024, and the
``transformers`` ``OlmoeForCausalLM`` ``config.json`` fields the
configuration file copies) in straightforward ``jax.numpy`` and float32,
matrix products at ``jax.default_matmul_precision("highest")``: per
layer RMSNorm, q/k/v, an RMSNorm over the whole q and the whole k
projection, rotary positions on the two halves of each head, causal
softmax attention, residual; RMSNorm, a float32 router over all experts,
softmax, the ``num_experts_per_tok`` largest kept by a mask and *not*
renormalised (``norm_topk_prob`` false), every expert applied to every
token one expert at a time, residual; a final norm and an untied output
head. No sort, no grouping, no cache, no kernel.

It reads the program's parameter tree and nothing else of the program:
``embed_tokens``, per layer ``input_norm``, ``attn/{q,k,v,o}_proj``,
``attn/{q,k}_norm``, ``post_attn_norm``, ``moe/{router,wg,wi,wo}``
(experts stacked on the first axis), ``final_norm``, ``lm_head``; layers
either stacked under ``layers`` or one tree each under ``layers_<i>``.
Weights are upcast where they are used, one expert at a time: the whole
tree in float32 would be twice what a chip holds.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

from perfbench import roofline
# Imported here, not where it is first used: a tree without the model
# fails when the family is loaded, before JAX has started a device.
from raytpu.models.mixtral import Mixtral, OlmoeConfig, make_train_step

SERVE_MODEL = "olmoe"
DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}


# ---- the program's side ----------------------------------------------------


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``OlmoeConfig`` for a configuration file. Layers are
    held one tree each (``scan_layers`` false): the grouped matmul is a
    kernel call and cannot read one layer out of a stack in place."""
    train = cfg.get("train", {})
    fields = dict(
        vocab_size=vocab_rows_held(cfg),
        block_size=cfg["max_position_embeddings"],
        n_layer=cfg["num_hidden_layers"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], n_embd=cfg["hidden_size"],
        n_inter=cfg["intermediate_size"], n_expert=cfg["num_experts"],
        n_expert_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype=DTYPES[cfg["compute_dtype"]][0],
        param_dtype=DTYPES[cfg["param_dtype"]][0], scan_layers=False,
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return OlmoeConfig(**fields)


def train_parts(pcfg):
    model = Mixtral(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, pcfg.block_size), jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


# ---- counts, from the configuration file ----------------------------------------


def vocab_rows_held(cfg: Mapping) -> int:
    """50304 is a multiple of 128 already: every row is a published one."""
    return int(cfg["vocab_size"])


def _widths(cfg):
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return e, h, cfg["num_key_value_heads"], e // h


def _layer_params(cfg: Mapping, experts: int) -> int:
    """One layer with ``experts`` of its experts: the four attention
    projections, the q and k norms, the two block norms, the router and
    three matrices an expert."""
    e, h, kv, d = _widths(cfg)
    return (e * (h + 2 * kv) * d + h * d * e + (h + kv) * d + 2 * e
            + e * cfg["num_experts"]
            + experts * 3 * e * cfg["intermediate_size"])


def _outside_layers(cfg: Mapping) -> int:
    """Embedding, untied output head, final norm."""
    return 2 * vocab_rows_held(cfg) * cfg["hidden_size"] + cfg["hidden_size"]


def param_count(cfg: Mapping) -> int:
    """Parameters as the program holds them."""
    return _outside_layers(cfg) + cfg["num_hidden_layers"] \
        * _layer_params(cfg, cfg["num_experts"])


def active_param_count(cfg: Mapping) -> int:
    """Parameters one token uses: ``num_experts_per_tok`` experts a layer."""
    return _outside_layers(cfg) + cfg["num_hidden_layers"] \
        * _layer_params(cfg, cfg["num_experts_per_tok"])


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    return roofline.train_flops_per_token(
        active_param_count(cfg), cfg["num_hidden_layers"],
        cfg["hidden_size"], seq_len)


def kv_shape(cfg: Mapping):
    _, _, kv, d = _widths(cfg)
    return (cfg["num_hidden_layers"], kv, d,
            DTYPES[cfg["compute_dtype"]][1])


def moe_shape(cfg: Mapping):
    """``(layers, experts, experts per token, hidden, one expert's width,
    bytes an element of an expert matrix as multiplied)``."""
    return (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["hidden_size"],
            cfg["intermediate_size"], DTYPES[cfg["compute_dtype"]][1])


def expert_ffn_flops(cfg: Mapping, assignments: int) -> float:
    """FLOPs of the expert matrices for ``assignments`` (token, expert)
    pairs: three products of hidden x width each, two a multiply-add."""
    _, _, _, hidden, width, _ = moe_shape(cfg)
    return assignments * 3 * 2.0 * hidden * width


def expert_ffn_bytes(cfg: Mapping, experts_touched: int) -> float:
    """Weight bytes the expert layer must read when ``experts_touched``
    (expert, layer) pairs received a token: three matrices each, once.
    Activations (a few KB a row) are left out."""
    _, _, _, hidden, width, itemsize = moe_shape(cfg)
    return experts_touched * 3.0 * hidden * width * itemsize


# ---- the plain reference -----------------------------------------------------------


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _rope(x, theta):
    """``x`` [B, H, T, D]: rotate the two halves of each head."""
    t, d = x.shape[-2], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def router_weights(cfg: Mapping, moe, y):
    """``y`` [..., E] float32 -> [..., experts]: each token's softmax
    score at its ``num_experts_per_tok`` largest experts, zero
    elsewhere."""
    probs = jax.nn.softmax(y @ moe["router"]["kernel"].astype(jnp.float32),
                           axis=-1)
    kth = jax.lax.top_k(probs, cfg["num_experts_per_tok"])[0][..., -1:]
    w = jnp.where(probs >= kth, probs, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w


def _experts(cfg: Mapping, moe, y):
    """Every expert on every token, one expert at a time; a token keeps
    the outputs of the experts its router chose, weighted."""
    w = router_weights(cfg, moe, y)

    def one(acc, ex):
        wg, wi, wo, we = ex
        wg, wi, wo = _f32((wg, wi, wo))
        out = (jax.nn.silu(y @ wg) * (y @ wi)) @ wo
        return acc + we[..., None] * out, None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (moe["wg"], moe["wi"], moe["wo"], jnp.moveaxis(w, -1, 0)))
    return acc


def _block(cfg: Mapping, x, lp, causal):
    b, t, _ = x.shape
    _, h, kv, d = _widths(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])

    def heads(z, n):
        return z.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    a = _f32(lp["attn"])
    y = _rms_norm(x, lp["input_norm"], eps)
    q = _rms_norm(y @ a["q_proj"]["kernel"], a["q_norm"], eps)
    k = _rms_norm(y @ a["k_proj"]["kernel"], a["k_norm"], eps)
    q, k = _rope(heads(q, h), theta), _rope(heads(k, kv), theta)
    v = heads(y @ a["v_proj"]["kernel"], kv)
    k, v = (jnp.repeat(z, h // kv, axis=1) for z in (k, v))
    s = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(d)
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    y = (p @ v).transpose(0, 2, 1, 3).reshape(b, t, h * d)
    x = x + y @ a["o_proj"]["kernel"]
    return x + _experts(cfg, lp["moe"],
                        _rms_norm(x, lp["post_attn_norm"], eps))


def hidden_states(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> final-norm hidden states [B, T, E], float32."""
    t = tokens.shape[1]
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
        causal = jnp.tril(jnp.ones((t, t), bool))
        if "layers" in params:
            x, _ = jax.lax.scan(
                lambda x, lp: (_block(cfg, x, lp, causal), None), x,
                params["layers"])
        else:
            for i in range(cfg["num_hidden_layers"]):
                x = _block(cfg, x, params[f"layers_{i}"], causal)
        return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


def logits(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> logits [B, T, vocabulary]."""
    x = hidden_states(cfg, params, tokens)
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"]["kernel"].astype(jnp.float32)


def loss(cfg: Mapping, params, tokens):
    """Mean next-token cross-entropy, one sequence at a time. The
    router's load-balance term is the trainer's, not the model's
    likelihood, and is left out."""

    def one(seq):
        lg = logits(cfg, params, seq[None])[0, :-1]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        label = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
        return (lse - label).mean()

    return jax.lax.map(one, tokens).mean()
