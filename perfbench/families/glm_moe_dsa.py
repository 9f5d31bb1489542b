"""The ``glm_moe_dsa`` family: GLM-5 (744B-A40B), a decoder whose latent
attention (MLA) reads only the cached positions a learned *indexer*
chooses (DeepSeek sparse attention), three leading dense layers, and after
them JoyAI-LLM-Flash's routed layer: 256 experts chosen by sigmoid score
plus a correction bias, beside one shared expert. Same interface as
``joyai.py``, its counts over the experts *held* (``experts_held``) and the
leading dense layers *held* (``leading_dense_layers_held``: the cut keeps
one of the published three), plus four optional functions of its own.

**``dsa_index_bytes(cfg, live_tokens)``** / **``dsa_index_flops(cfg,
live_tokens)``**: what the indexers must read and compute to score
``live_tokens`` cached positions (one layer's, one query each): one index
key of ``index_head_dim`` values a position at the itemsize held, and
``index_n_heads x index_head_dim`` multiply-adds, in every layer.
**``dsa_attn_bytes(cfg, selected_rows)``** / **``dsa_attn_flops(cfg,
selected_rows)``**: what the attention over ``selected_rows`` chosen rows
(one layer's) must read and compute: a latent row of 576 values as
published a chosen row, once, and every head's score over the row and its
weighted sum of the latent, in every layer: the same work whatever
implements it (a gather and a kernel here). A cell of this family stays
out of ``mla_attn_roofline`` and ``mla_attn_busy_pct``, whose count is every
live row and whose kernel is not on its path; the readers ``dsa_*`` return
``None`` for a family without these four.

Program side: ``raytpu/models/mixtral.py`` (``GlmDsaConfig``),
``raytpu/models/mla.py`` (``SparseLatentAttention``), ``raytpu/ops/
dsa_attention.py`` (the index kernel, the exact top-k, the attention over
gathered rows), served by the llama family's walks over two pools a
layer.

The plain reference below is written from the layer equations of the
published ``config.json`` in straightforward ``jax.numpy`` and float32,
matrix products at ``jax.default_matmul_precision("highest")``, in the
**expanded** form with the choice as a **mask**, so that the program's
absorbed attention over gathered rows is compared with something that is
not itself. For layer ``i``, ``eps`` = ``rms_norm_eps``, ``h`` the normed
input: ``c_q = RMSNorm(h W_qa)``, ``q = c_q W_qb`` (heads x
``qk_head_dim``: ``qk_nope_head_dim`` not roped | ``qk_rope_head_dim``
roped); ``[c_kv | k_pe] = h W_kva``, ``c_kv <- RMSNorm(c_kv)``, ``k_pe`` one
roped key for all heads; ``[k_nope | v] = c_kv W_kvb``; rope at
``rope_parameters.rope_theta`` with ``rope_interleave`` (adjacent pairs).
The indexer: ``q_I = c_q W_Iqb`` (``index_n_heads`` x ``index_head_dim``),
``k_I = LayerNorm(h W_Ik)`` (scale and bias, eps ``assumed.
index_k_norm_eps``), the first ``qk_rope_head_dim`` values of each roped by
the same tables (``indexer_rope_interleave``), ``w = (h W_Iw) /
sqrt(index_n_heads x index_head_dim)``; ``I[t, s] = sum_h w[t, h]
relu(q_I[t, h] . k_I[s])`` for ``s <= t``; ``S_t`` the ``min(index_topk,
t + 1)`` positions of largest ``I[t, :]``, equal scores to the lower
position. Attention: softmax over ``s in S_t`` of ``(q_nope . k_nope +
q_pe . k_pe) / sqrt(qk_head_dim)``, values ``v``; ``W_o``; no bias.
Feed-forward: SwiGLU of ``intermediate_size`` in the leading dense layers,
else the routed layer as ``joyai.py`` has it (sigmoid scores in float32,
the choice by score + ``e_score_correction_bias``, weights the scores over
their sum times ``routed_scaling_factor``, every held expert a Python loop
over the tokens, plus the shared expert). Final RMSNorm, untied head. The
multi-token-prediction module and the indexer's training loss are not
built. Attention and the choice are computed a block of query rows at a
time and the head a block of columns at a time, so that 4,096 positions at
the published widths fit beside the engine.

It reads the program's parameter tree and nothing else of the program:
``embed_tokens``, per layer ``layers_<i>/{input_norm, attn/{q_a_proj,
q_a_norm, q_b_proj, kv_a_proj, kv_a_norm, kv_b_proj, o_proj, index_q_proj,
index_k_proj, index_k_norm, index_w_proj}, post_attn_norm}`` and
``mlp/{gate,up,down}_proj`` or ``moe/{router, bias, wg, wi, wo,
shared/...}``, ``final_norm``, ``lm_head``.
"""

from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp

from perfbench import roofline
# Imported here, not where it is first used: a tree without the model
# fails when the family is loaded, before JAX has started a device.
from raytpu.models.mixtral import GlmDsaConfig, Mixtral, make_train_step

SERVE_MODEL = "glm_moe_dsa"
DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}
# The most float32 score entries one block of query rows may hold.
SCORE_ENTRIES = 1 << 25
# The widest block of a dense SwiGLU multiplied at once.
SWIGLU_BLOCK = 3072


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


def dense_layers(cfg: Mapping) -> int:
    """The leading dense layers as held: a cut in depth counts the
    published ``first_k_dense_replace`` once."""
    return int(cfg.get("leading_dense_layers_held",
                       cfg["first_k_dense_replace"]))


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``GlmDsaConfig`` for a configuration file."""
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1 \
        and cfg["scoring_func"] == "sigmoid" \
        and cfg["topk_method"] == "noaux_tc" \
        and cfg["rope_parameters"]["rope_type"] == "default" \
        and cfg["moe_layer_freq"] == 1 \
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
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        scoring=cfg["scoring_func"],
        choice_bias=float(cfg["assumed"]["e_score_correction_bias_std"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        n_shared=cfg["n_shared_experts"], first_dense=dense_layers(cfg),
        dense_inter=cfg["intermediate_size"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_interleave=cfg["rope_interleave"],
        index_topk=cfg["index_topk"], index_n_head=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_rope_interleave=cfg["indexer_rope_interleave"],
        index_norm_eps=float(cfg["assumed"]["index_k_norm_eps"]),
        dtype=DTYPES[cfg["compute_dtype"]][0],
        param_dtype=DTYPES[cfg["param_dtype"]][0], scan_layers=False,
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return GlmDsaConfig(**fields)


def train_parts(pcfg):
    model = Mixtral(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, min(pcfg.block_size, 128)),
                           jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


# ---- counts, from the configuration file ----------------------------------------


def vocab_rows_held(cfg: Mapping) -> int:
    return int(cfg["vocab_size"])


def _indexer_params(cfg: Mapping) -> int:
    """A layer's indexer: its three matrices and the key's LayerNorm."""
    e, d = cfg["hidden_size"], cfg["index_head_dim"]
    heads = cfg["index_n_heads"]
    return cfg["q_lora_rank"] * heads * d + e * d + e * heads + 2 * d


def _attn_params(cfg: Mapping) -> int:
    """A layer's latent attention: the five matrices, the two norms and
    the indexer."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (e * qr + qr + qr * h * (nope + rope) + e * (kr + rope) + kr
            + kr * h * (nope + vd) + h * vd * e + _indexer_params(cfg))


def _expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_params(cfg: Mapping, i: int, experts: float) -> float:
    """Layer ``i`` with ``experts`` of its routed experts: attention, the
    two block norms, and the dense SwiGLU or the router, its bias, the
    shared expert and the routed ones."""
    e = cfg["hidden_size"]
    outside = _attn_params(cfg) + 2 * e
    if i < dense_layers(cfg):
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
    """Values a token's latent row holds in one layer, as published."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_row_held(cfg: Mapping) -> int:
    """Lanes of a latent row as the pool holds it: whole 128-lane tiles
    (``raytpu.ops.mla_attention.latent_row_width``)."""
    return -(-latent_row(cfg) // 128) * 128


def kv_shape(cfg: Mapping):
    """``(layers, kv_heads, head_dim, itemsize)`` as the other families
    give it, for a cache of two rows a token a layer: one "head" of the
    latent row's and the index key's published widths together."""
    return (cfg["num_hidden_layers"], 1,
            latent_row(cfg) + cfg["index_head_dim"],
            DTYPES[cfg["compute_dtype"]][1])


def dsa_index_bytes(cfg: Mapping, live_tokens: int) -> float:
    """Bytes the indexers must read to score ``live_tokens`` cached
    positions of one layer: an index key each, in every layer."""
    return (float(cfg["num_hidden_layers"]) * live_tokens
            * cfg["index_head_dim"] * DTYPES[cfg["compute_dtype"]][1])


def dsa_index_flops(cfg: Mapping, live_tokens: int) -> float:
    """FLOPs of scoring them: every index head's product with the key,
    two a multiply-add, in every layer."""
    return (float(cfg["num_hidden_layers"]) * live_tokens
            * cfg["index_n_heads"] * cfg["index_head_dim"] * 2.0)


def dsa_attn_bytes(cfg: Mapping, selected_rows: int) -> float:
    """Bytes the attention must read for ``selected_rows`` chosen rows of
    one layer: each row once at its published width, in every layer."""
    return (float(cfg["num_hidden_layers"]) * selected_rows
            * latent_row(cfg) * DTYPES[cfg["compute_dtype"]][1])


def dsa_attn_flops(cfg: Mapping, selected_rows: int) -> float:
    """FLOPs of the absorbed form over them: every head's score over the
    row and its weighted sum of the latent, two a multiply-add."""
    return (float(cfg["num_hidden_layers"]) * selected_rows
            * cfg["num_attention_heads"]
            * 2.0 * (latent_row(cfg) + cfg["kv_lora_rank"]))


def routed_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def moe_shape(cfg: Mapping):
    """``(routed layers, experts held, experts per token, hidden, one
    expert's width, bytes an element of an expert matrix as
    multiplied)``, over the experts held here."""
    return (routed_layers(cfg), experts_held(cfg)[1],
            cfg["num_experts_per_tok"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], DTYPES[cfg["compute_dtype"]][1])


def expert_ffn_flops(cfg: Mapping, assignments: int) -> float:
    _, _, _, hidden, width, _ = moe_shape(cfg)
    return assignments * 3 * 2.0 * hidden * width


def expert_ffn_bytes(cfg: Mapping, experts_touched: int) -> float:
    _, _, _, hidden, width, itemsize = moe_shape(cfg)
    return experts_touched * 3.0 * hidden * width * itemsize


# ---- the plain reference -----------------------------------------------------------


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) \
        * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _rope(cfg: Mapping, x, interleave: bool):
    """``x`` [..., T, D] at positions 0..T-1: with ``interleave`` the
    values are read as adjacent pairs and laid out [evens | odds], then
    the two halves are rotated."""
    t, d = x.shape[-2], x.shape[-1]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    freqs = float(cfg["rope_parameters"]["rope_theta"]) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _index_rope(cfg: Mapping, x):
    """The indexer's rope: the first ``qk_rope_head_dim`` values of the
    last axis, ``x`` [..., T, D]."""
    r = cfg["qk_rope_head_dim"]
    return jnp.concatenate(
        [_rope(cfg, x[..., :r], cfg["indexer_rope_interleave"]),
         x[..., r:]], -1)


def chosen(cfg: Mapping, scores, p):
    """``scores`` [B, rows, T] float32, row ``r`` the query at position
    ``p[r]`` -> bool [B, rows, T]: the ``min(index_topk, p + 1)``
    positions ``<= p`` of largest score, equal scores to the lower one."""
    t = scores.shape[-1]
    j = jnp.arange(t)
    seen = j <= p[:, None]
    k = min(int(cfg["index_topk"]), t)
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    keep = jnp.zeros(scores.shape, bool)
    b, r = jnp.meshgrid(jnp.arange(scores.shape[0]),
                        jnp.arange(scores.shape[1]), indexing="ij")
    keep = keep.at[b[..., None], r[..., None], idx].set(True)
    return keep & seen  # (fewer than k seen: the rest of idx is unseen)


def _attend(cfg: Mapping, q, k, v, q_i, k_i, w_i):
    """``q``, ``k`` [B, H, T, Dk], ``v`` [B, H, T, Dv]; the indexer's
    ``q_i`` [B, Hi, T, D], ``k_i`` [B, T, D], ``w_i`` [B, Hi, T]. A block
    of query rows at a time: row p scores every key ``j <= p``, keeps the
    chosen ones, and attends those; scores over sqrt(Dk)."""
    b, h, t, d = q.shape
    rows = 1 << max(3, int(math.log2(max(8, SCORE_ENTRIES // (h * t)))))
    rows = min(rows, 1 << (t - 1).bit_length())
    blocks = -(-t // rows)
    pad = blocks * rows - t

    def by_block(x, axis):
        """Axis ``axis`` (the positions) cut into blocks, blocks first."""
        x = jnp.pad(x, [(0, pad if a == axis else 0)
                        for a in range(x.ndim)])
        x = x.reshape(x.shape[:axis] + (blocks, rows) + x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    def one(args):
        i, qi, qii, wii = args
        # (The last block's padding rows stand at the last position.)
        p = jnp.minimum(i * rows + jnp.arange(rows), t - 1)
        index = jnp.einsum("bhrd,btd->bhrt", qii, k_i)
        index = (jax.nn.relu(index) * wii[..., None]).sum(1)  # [B, rows, T]
        keep = chosen(cfg, index, p)[:, None]
        s = qi @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(one, (jnp.arange(blocks), by_block(q, 2),
                            by_block(q_i, 2), by_block(w_i, 2)))
    return out.transpose(1, 2, 0, 3, 4).reshape(
        b, h, blocks * rows, v.shape[-1])[:, :, :t]


def _attention(cfg: Mapping, a, y):
    """Expanded latent attention of the normed ``y`` [B, T, E] over the
    positions its indexer chooses."""
    b, t, _ = y.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    heads, scale = cfg["index_n_heads"], cfg["index_head_dim"]
    kern = {k: v["kernel"].astype(jnp.float32) for k, v in a.items()
            if "kernel" in v}
    c_q = _rms_norm(y @ kern["q_a_proj"], a["q_a_norm"], eps)
    q = (c_q @ kern["q_b_proj"]).reshape(b, t, h, -1).transpose(0, 2, 1, 3)
    kva = y @ kern["kv_a_proj"]
    c_kv = _rms_norm(kva[..., :rank], a["kv_a_norm"], eps)
    k_pe = _rope(cfg, kva[..., rank:], cfg["rope_interleave"])
    kv = (c_kv @ kern["kv_b_proj"]).reshape(b, t, h, -1)
    kv = kv.transpose(0, 2, 1, 3)
    q = jnp.concatenate(
        [q[..., :nope], _rope(cfg, q[..., nope:], cfg["rope_interleave"])],
        -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, None], (b, h, t, k_pe.shape[-1]))], -1)
    q_i = (c_q @ kern["index_q_proj"]).reshape(b, t, heads, -1)
    q_i = _index_rope(cfg, q_i.transpose(0, 2, 1, 3))
    k_i = _index_rope(cfg, _layer_norm(
        y @ kern["index_k_proj"], a["index_k_norm"],
        float(cfg["assumed"]["index_k_norm_eps"])))
    w_i = (y @ kern["index_w_proj"]) * (heads * scale) ** -0.5
    o = _attend(cfg, q, k, kv[..., nope:], q_i, k_i, w_i.transpose(0, 2, 1))
    return o.transpose(0, 2, 1, 3).reshape(b, t, -1) @ kern["o_proj"]


def _swiglu(p, y):
    """SwiGLU of ``y``, a block of its width at a time."""
    gate, up, down = (p[name]["kernel"]
                      for name in ("gate_proj", "up_proj", "down_proj"))
    width = gate.shape[1]
    block = next(w for w in range(min(SWIGLU_BLOCK, width), 0, -1)
                 if width % w == 0)

    def one(i, out):
        g = jax.lax.dynamic_slice_in_dim(gate, i * block, block, 1)
        u = jax.lax.dynamic_slice_in_dim(up, i * block, block, 1)
        d = jax.lax.dynamic_slice_in_dim(down, i * block, block, 0)
        mid = jax.nn.silu(y @ g.astype(jnp.float32)) \
            * (y @ u.astype(jnp.float32))
        return out + mid @ d.astype(jnp.float32)

    return jax.lax.fori_loop(0, width // block, one, jnp.zeros_like(y))


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
    """Every held expert on every token, one expert at a time (a Python
    loop); a token keeps the outputs of the experts its router chose,
    weighted. Then the shared expert, on every token."""
    first, count = experts_held(cfg)
    w = router_weights(cfg, moe, y)[..., first:first + count]
    acc = jnp.zeros_like(y)
    for e in range(count):
        wg, wi, wo = _f32((moe["wg"][e], moe["wi"][e], moe["wo"][e]))
        acc = acc + w[..., e:e + 1] * (
            (jax.nn.silu(y @ wg) * (y @ wi)) @ wo)
    return acc + _swiglu(moe["shared"], y)


def _block(cfg: Mapping, i: int, x, lp):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, lp["attn"], _rms_norm(x, lp["input_norm"], eps))
    y = _rms_norm(x, lp["post_attn_norm"], eps)
    if i < dense_layers(cfg):
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
    router's load-balance term and the indexer's own loss are the
    trainer's, not the model's likelihood, and are left out."""

    def one(seq):
        lg = logits(cfg, params, seq[None])[0, :-1]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        label = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
        return (lse - label).mean()

    return jax.lax.map(one, tokens).mean()
