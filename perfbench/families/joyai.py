"""The ``joyai`` family: JoyAI-LLM-Flash (48B-A2.7B), a decoder with latent
attention (MLA) in every layer, a leading dense layer, and after it
routed layers of 256 experts chosen by sigmoid score plus a correction
bias, beside one shared expert. Same interface as ``gpt2.py``, ``olmoe.py``
and ``mellum.py``, with its counts over the experts *held* (the
configuration holds one chip's share of each layer's experts:
``experts_held``), plus two optional functions of its own.

**``latent_attn_bytes(cfg, page_size, live_pages)``** and
**``latent_attn_flops(cfg, live_tokens)``** (optional; a family without
latent attention leaves them out and the readers ``mla_attn_roofline``
and ``mla_attn_busy_pct`` return ``None``): what the absorbed latent
kernel must read and compute in decode steps that read ``live_pages``
pages (``live_tokens`` slots) in one layer. A latent layer has ONE pool
whose row is read once, as keys and as values: 576 values a token as
published, where ``roofline.paged_attn_bytes`` counts a K row and a V row
of ``kv_heads x head_dim``. So a cell of this family stays out of
``paged_attn_roofline``, ``paged_attn_kinds_roofline`` and
``paged_attn_busy_pct``: their time is the events named ``_paged_pallas*``
(this family's kernel is ``_mla_paged_pallas*``, and would read as
silence) and their bytes are ``kv_shape``'s K and V; and out of
``kv_resident_vs_flat_pct``, which compares two kinds of pool.
``kv_shape`` here gives one "head" of 576 for what still asks.

Program side: ``raytpu/models/mixtral.py`` (``JoyAIConfig``; ``Mixtral`` is
its training forward, ``MoEFFN`` its routed layer), ``raytpu/models/
mla.py`` (``LatentAttention``), ``raytpu/ops/mla_attention.py`` (the
absorbed paged kernel), served by the llama family's three walks over one
latent pool a layer.

The plain reference below is written from the layer equations of the
published ``config.json`` (``transformers`` conventions for its keys) in
straightforward ``jax.numpy`` and float32, matrix products at
``jax.default_matmul_precision("highest")``, in the **expanded** form
only, so that the program's absorbed decode is compared with something
that is not itself. For layer ``i``, ``eps`` = ``rms_norm_eps``:
RMSNorm; ``c_q = RMSNorm(h W_qa)`` (hidden -> ``q_lora_rank``), ``q = c_q
W_qb`` (-> heads x ``qk_head_dim``), each head ``q_nope``
(``qk_nope_head_dim``) | ``q_pe`` (``qk_rope_head_dim``); ``[c_kv | k_pe]
= h W_kva`` (hidden -> ``kv_lora_rank`` + ``qk_rope_head_dim``), ``c_kv <-
RMSNorm(c_kv)``, ``k_pe`` ONE key for all heads; rotary on ``q_pe`` and
``k_pe`` at ``rope_theta`` with ``rope_interleave`` (the values read as
adjacent pairs ``(2j, 2j+1)``, laid out ``[evens | odds]``, rotated by
halves at angle ``p theta^(-2j/d)``; ``rope_scaling`` null: nothing
else); ``[k_nope | v] = c_kv W_kvb`` (-> heads x (``qk_nope_head_dim`` +
``v_head_dim``)); score of head n, query p, key j <= p: ``(q_nope .
k_nope + q_pe . k_pe) / sqrt(qk_head_dim)``; softmax; ``o = concat(sum a
v) W_o``; no bias anywhere. Residual, RMSNorm. Layer ``i <
first_k_dense_replace``: SwiGLU of ``intermediate_size``. The others: ``s
= sigmoid(h W_r)`` over all ``published_n_routed_experts`` in float32;
the ``num_experts_per_tok`` experts are the largest of ``s + b``
(``e_score_correction_bias``; ``n_group`` 1 and ``topk_group`` 1: the
group step is the identity); their weights are ``s`` without ``b``, over
their sum (``norm_topk_prob``), times ``routed_scaling_factor``; every
held expert (SwiGLU of ``moe_intermediate_size``) applied to every token
one at a time with that weight as a mask, plus the shared expert (SwiGLU
of ``n_shared_experts x moe_intermediate_size``). What the experts of
other chips would add is left out, here as in the program. Final
RMSNorm, untied head. The multi-token-prediction module
(``num_nextn_predict_layers``) is not built: the main model's logits do
not depend on it. No cache, no sort, no absorbed form, no kernel.

Attention is computed a block of query rows at a time and
``logits(..., rows=...)`` gives chosen positions alone, as
``families/mellum.py``; the head is multiplied a block of the
vocabulary's columns at a time, so that no float32 copy of its 265 M
weights stands beside a full chip.

It reads the program's parameter tree and nothing else of the program:
``embed_tokens``, per layer ``layers_<i>/{input_norm, attn/{q_a_proj,
q_a_norm, q_b_proj, kv_a_proj, kv_a_norm, kv_b_proj, o_proj},
post_attn_norm}`` and ``mlp/{gate,up,down}_proj`` (dense) or
``moe/{router, bias, wg, wi, wo, shared/{gate,up,down}_proj}`` (experts
stacked on the first axis, the held ones only), ``final_norm``,
``lm_head``.
"""

from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp

from perfbench import roofline
# Imported here, not where it is first used: a tree without the model
# fails when the family is loaded, before JAX has started a device.
from raytpu.models.mixtral import JoyAIConfig, Mixtral, make_train_step

SERVE_MODEL = "joyai"
DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}
# The most float32 score entries one block of query rows may hold.
SCORE_ENTRIES = 1 << 25


# ---- the program's side ----------------------------------------------------


def experts_held(cfg: Mapping):
    """``(first, count)`` of the routed experts this chip holds."""
    first, count = cfg["experts_held"]
    assert count == cfg["n_routed_experts"], cfg["experts_held"]
    return int(first), int(count)


def router_width(cfg: Mapping) -> int:
    """The experts the router scores: the published count."""
    return int(cfg.get("published_n_routed_experts",
                       cfg["n_routed_experts"]))


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``JoyAIConfig`` for a configuration file."""
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1 \
        and cfg["scoring_func"] == "sigmoid" \
        and cfg["topk_method"] == "noaux_tc" \
        and cfg["rope_scaling"] is None and cfg["moe_layer_freq"] == 1 \
        and not cfg["attention_bias"] and cfg["hidden_act"] == "silu" \
        and not cfg["tie_word_embeddings"] \
        and cfg["qk_head_dim"] == cfg["qk_nope_head_dim"] \
        + cfg["qk_rope_head_dim"]
    train = cfg.get("train", {})
    fields = dict(
        vocab_size=vocab_rows_held(cfg),
        block_size=cfg["max_position_embeddings"],
        n_layer=cfg["num_hidden_layers"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], n_embd=cfg["hidden_size"],
        head_dim=cfg["head_dim"], n_inter=cfg["moe_intermediate_size"],
        n_expert=router_width(cfg), experts_held=experts_held(cfg),
        n_expert_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), scoring=cfg["scoring_func"],
        choice_bias=float(cfg["assumed"]["e_score_correction_bias_std"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        n_shared=cfg["n_shared_experts"],
        first_dense=cfg["first_k_dense_replace"],
        dense_inter=cfg["intermediate_size"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_interleave=cfg["rope_interleave"],
        dtype=DTYPES[cfg["compute_dtype"]][0],
        param_dtype=DTYPES[cfg["param_dtype"]][0], scan_layers=False,
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return JoyAIConfig(**fields)


def train_parts(pcfg):
    model = Mixtral(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, min(pcfg.block_size, 128)),
                           jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


# ---- counts, from the configuration file ----------------------------------------


def vocab_rows_held(cfg: Mapping) -> int:
    """129,280 is a multiple of 128 already: every row is a published one."""
    return int(cfg["vocab_size"])


def _attn_params(cfg: Mapping) -> int:
    """A layer's latent attention: the five matrices and the two norms."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (e * qr + qr + qr * h * (nope + rope) + e * (kr + rope) + kr
            + kr * h * (nope + vd) + h * vd * e)


def _expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_params(cfg: Mapping, i: int, experts: float) -> float:
    """Layer ``i`` with ``experts`` of its routed experts: attention, the
    two block norms, and the dense SwiGLU or the router, its bias, the
    shared expert and the routed ones."""
    e = cfg["hidden_size"]
    outside = _attn_params(cfg) + 2 * e
    if i < cfg["first_k_dense_replace"]:
        return outside + 3 * e * cfg["intermediate_size"]
    return (outside + e * router_width(cfg) + router_width(cfg)
            + cfg["n_shared_experts"] * _expert_params(cfg)
            + experts * _expert_params(cfg))


def _outside_layers(cfg: Mapping) -> int:
    """Embedding, untied output head, final norm."""
    return 2 * vocab_rows_held(cfg) * cfg["hidden_size"] + cfg["hidden_size"]


def param_count(cfg: Mapping) -> int:
    """Parameters as the program holds them: the held experts only."""
    return _outside_layers(cfg) + sum(
        _layer_params(cfg, i, experts_held(cfg)[1])
        for i in range(cfg["num_hidden_layers"]))


def active_param_count(cfg: Mapping) -> float:
    """Parameters one token uses here: of its ``num_experts_per_tok``
    experts a layer, the share that is held."""
    here = cfg["num_experts_per_tok"] * experts_held(cfg)[1] \
        / router_width(cfg)
    return _outside_layers(cfg) + sum(
        _layer_params(cfg, i, here)
        for i in range(cfg["num_hidden_layers"]))


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    return roofline.train_flops_per_token(
        active_param_count(cfg), cfg["num_hidden_layers"],
        cfg["hidden_size"], seq_len)


def latent_row(cfg: Mapping) -> int:
    """Values a token's cache row holds in one layer, as published: the
    latent and the one roped key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def kv_shape(cfg: Mapping):
    """``(layers, kv_heads, head_dim, itemsize)`` as the other families
    give it, for a cache of one row a token a layer: one "head" of the
    row's published width. The row is read once, as keys and as values;
    a reader that counts a K and a V (``roofline.paged_attn_bytes``)
    counts it twice, and this family's cell is in none."""
    return (cfg["num_hidden_layers"], 1, latent_row(cfg),
            DTYPES[cfg["compute_dtype"]][1])


def latent_attn_bytes(cfg: Mapping, page_size: int, live_pages: int) -> float:
    """Pool bytes the latent kernel must read when the decode steps
    counted read ``live_pages`` pages in one layer (the step records'
    sum): each page's rows once, at the published width, in every layer.
    (The pool holds a row on 640 lanes; the 64 of padding are not bytes
    the algorithm needs.)"""
    layers, _, row, itemsize = kv_shape(cfg)
    return float(layers) * live_pages * page_size * row * itemsize


def latent_attn_flops(cfg: Mapping, live_tokens: int) -> float:
    """FLOPs of the absorbed form over ``live_tokens`` cached positions of
    one layer, one query token a sequence: every head's score over the
    row (latent + roped key) and its weighted sum of the latent, two a
    multiply-add, in every layer. ``W_uk`` on the query and ``W_uv`` on
    the result are outside the kernel and not counted."""
    return (float(cfg["num_hidden_layers"]) * live_tokens
            * cfg["num_attention_heads"]
            * 2.0 * (latent_row(cfg) + cfg["kv_lora_rank"]))


def routed_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def moe_shape(cfg: Mapping):
    """``(routed layers, experts held, experts per token, hidden, one
    expert's width, bytes an element of an expert matrix as
    multiplied)``: over the experts held here, which are the ones the
    program counts (``moe_assignments``, ``moe_experts_touched``)."""
    return (routed_layers(cfg), experts_held(cfg)[1],
            cfg["num_experts_per_tok"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], DTYPES[cfg["compute_dtype"]][1])


def expert_ffn_flops(cfg: Mapping, assignments: int) -> float:
    """FLOPs of the routed expert matrices for ``assignments`` (token,
    expert) pairs computed here: three products of hidden x width each.
    The shared expert is a dense layer and not in these counts."""
    _, _, _, hidden, width, _ = moe_shape(cfg)
    return assignments * 3 * 2.0 * hidden * width


def expert_ffn_bytes(cfg: Mapping, experts_touched: int) -> float:
    """Weight bytes the routed layer must read when ``experts_touched``
    (expert, layer) pairs received a token: three matrices each, once."""
    _, _, _, hidden, width, itemsize = moe_shape(cfg)
    return experts_touched * 3.0 * hidden * width * itemsize


# ---- the plain reference -----------------------------------------------------------


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _rope(cfg: Mapping, x):
    """``x`` [..., T, D] at positions 0..T-1: with ``rope_interleave`` the
    values are read as adjacent pairs and laid out [evens | odds], then
    the two halves are rotated."""
    t, d = x.shape[-2], x.shape[-1]
    if cfg["rope_interleave"]:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    freqs = float(cfg["rope_theta"]) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v):
    """``q``, ``k`` [B, H, T, Dk] and ``v`` [B, H, T, Dv], a block of query
    rows at a time: row p sees keys ``j <= p``; scores over sqrt(Dk)."""
    b, h, t, d = q.shape
    rows = 1 << max(3, int(math.log2(max(8, SCORE_ENTRIES // (h * t)))))
    rows = min(rows, 1 << (t - 1).bit_length())
    blocks = -(-t // rows)
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, blocks * rows - t), (0, 0)))
    qb = qb.reshape(b, h, blocks, rows, d).transpose(2, 0, 1, 3, 4)
    j = jnp.arange(t)

    def one(args):
        i, qi = args
        # (The last block's padding rows stand at the last position.)
        p = jnp.minimum(i * rows + jnp.arange(rows), t - 1)[:, None]
        s = qi @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(j <= p, s, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(one, (jnp.arange(blocks), qb))
    return out.transpose(1, 2, 0, 3, 4).reshape(
        b, h, blocks * rows, v.shape[-1])[:, :, :t]


def _attention(cfg: Mapping, a, y):
    """Expanded latent attention of the normed ``y`` [B, T, E]."""
    b, t, _ = y.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    kern = {k: v["kernel"].astype(jnp.float32) for k, v in a.items()
            if "kernel" in v}
    c_q = _rms_norm(y @ kern["q_a_proj"], a["q_a_norm"], eps)
    q = (c_q @ kern["q_b_proj"]).reshape(b, t, h, -1).transpose(0, 2, 1, 3)
    kva = y @ kern["kv_a_proj"]
    c_kv = _rms_norm(kva[..., :rank], a["kv_a_norm"], eps)
    k_pe = _rope(cfg, kva[..., rank:])                     # one key, [B,T,r]
    kv = (c_kv @ kern["kv_b_proj"]).reshape(b, t, h, -1)
    kv = kv.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], _rope(cfg, q[..., nope:])], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, None], (b, h, t, k_pe.shape[-1]))], -1)
    o = _attend(q, k, kv[..., nope:])
    return o.transpose(0, 2, 1, 3).reshape(b, t, -1) @ kern["o_proj"]


def _swiglu(p, y):
    p = {k: v["kernel"].astype(jnp.float32) for k, v in p.items()}
    return (jax.nn.silu(y @ p["gate_proj"]) * (y @ p["up_proj"])) \
        @ p["down_proj"]


def router_weights(cfg: Mapping, moe, y):
    """``y`` [..., E] float32 -> [..., published experts]: each token's
    sigmoid score at the ``num_experts_per_tok`` experts whose score +
    bias is largest, over their sum, times the scaling factor; zero
    elsewhere."""
    s = jax.nn.sigmoid(y @ moe["router"]["kernel"].astype(jnp.float32))
    choice = s + moe["bias"].astype(jnp.float32)
    kth = jax.lax.top_k(choice, cfg["num_experts_per_tok"])[0][..., -1:]
    w = jnp.where(choice >= kth, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * float(cfg["routed_scaling_factor"])


def _experts(cfg: Mapping, moe, y):
    """Every held expert on every token, one expert at a time; a token
    keeps the outputs of the experts its router chose, weighted. Then
    the shared expert, on every token."""
    first, count = experts_held(cfg)
    w = router_weights(cfg, moe, y)[..., first:first + count]

    def one(acc, ex):
        wg, wi, wo, we = ex
        wg, wi, wo = _f32((wg, wi, wo))
        out = (jax.nn.silu(y @ wg) * (y @ wi)) @ wo
        return acc + we[..., None] * out, None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (moe["wg"], moe["wi"], moe["wo"], jnp.moveaxis(w, -1, 0)))
    return acc + _swiglu(moe["shared"], y)


def _block(cfg: Mapping, i: int, x, lp):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, lp["attn"], _rms_norm(x, lp["input_norm"], eps))
    y = _rms_norm(x, lp["post_attn_norm"], eps)
    if i < cfg["first_k_dense_replace"]:
        return x + _swiglu(lp["mlp"], y)
    return x + _experts(cfg, lp["moe"], y)


def hidden_states(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> final-norm hidden states [B, T, E], float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = _block(cfg, i, x, params[f"layers_{i}"])
        return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


def _head(x, kernel):
    """``x @ kernel`` in float32, a block of the vocabulary's columns at a
    time, written where it belongs."""
    v = kernel.shape[1]
    blocks = next(n for n in (10, 8, 5, 4, 2, 1) if v % n == 0)
    width = v // blocks

    def one(i, out):
        w = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ w.astype(jnp.float32), i * width, x.ndim - 1)

    return jax.lax.fori_loop(
        0, blocks, one, jnp.zeros(x.shape[:-1] + (v,), jnp.float32))


def logits(cfg: Mapping, params, tokens, rows=None):
    """``tokens`` [B, T] -> logits [B, T, vocabulary]; with ``rows`` (a
    list of positions) [B, len(rows), vocabulary], of those alone."""
    x = hidden_states(cfg, params, tokens)
    if rows is not None:
        x = x[:, jnp.asarray(rows)]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["lm_head"]["kernel"])


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
