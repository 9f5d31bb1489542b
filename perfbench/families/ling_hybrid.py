"""The ``ling_hybrid`` family: Ling-3.0-flash-VL's language model, a decoder
whose layers are delta-rule linear attention (KDA: Kimi Delta Attention,
arXiv:2510.26692) five to every latent-attention (MLA) one, two leading
dense layers and after them routed layers of 512 experts of which a token
takes 8 by sigmoid score plus a bias *inside the best 4 of 8 groups*,
beside one shared expert. A KDA layer keeps no keys and values: a served
sequence holds there a float32 matrix a head and the three newest rows of
its short convolutions' inputs (its *state*), the same bytes however long
it is. Same interface as ``joyai.py`` (one latent pool a layer that has
one, counts over the experts *held*) and ``lfm2_moe.py``
(``state_bytes_per_seq``), plus two functions of its own:

**``kda_state_bytes(cfg, rows)``** and **``kda_state_flops(cfg, rows)``**
(``kda_state_roofline`` reads them): what one decode position of ``rows``
sequences must move and compute in the recurrence of every KDA layer held,
from the configuration's shapes alone, whatever implements it: each
sequence's matrices read once and written once, and the row's ``q``,
``k``, ``v``, ``g``, ``beta`` in and ``o`` out in float32; the decay, the
two contractions and the rank-one update. The convolutions' tails are not
in it: they belong to the convolution, which the events the metric times
(the state kernel's) never touch.

Program side: ``raytpu/models/kda.py`` (``KimiDeltaAttention``),
``raytpu/ops/kda.py`` (the chunked prompt form and the one-pass decode
kernel), ``raytpu/models/mla.py`` (``LatentAttention`` with ``q_lora_rank``
``None`` and the head gate), ``raytpu/models/mixtral.py``
(``LingHybridConfig``; ``Mixtral`` its training forward, ``MoEFFN`` its
routed layer with the choice by groups), ``raytpu/models/llama.py`` (the
serving walks), ``raytpu/inference/kv_cache.py`` (seats, typed state
arrays beside a latent pool).

The plain reference below is written from the layer equations (ISSUE 59,
"What Ling-3.0-flash-VL is"; the Kimi Linear report and
flash-linear-attention's ``KimiDeltaAttention`` for the KDA layer;
``transformers`` conventions for the keys of the published ``config.json``)
in straightforward ``jax.numpy`` and float32, matrix products at
``jax.default_matmul_precision("highest")``. ``n = RMSNorm(x)``, eps
``rms_norm_eps``, no bias anywhere. Block ``i`` (a *published* index:
``layers_held`` lists them): ``h = x + Op_i(n)``, ``y = h + FFN_i(RMSNorm
(h))``.

``Op`` of a KDA layer (``(i + 1) % layer_group_size != 0``), per head of
``num_attention_heads``, ``d = head_dim``: ``[q~ | k~ | v~] = n [W_q | W_k
| W_v]``; each channel through a causal convolution of
``short_conv_kernel_size`` taps (zeros left of position 0), as shifted
adds, then SiLU (``linear_silu``); ``q = q~ / sqrt(sum q~^2 + 1e-6) /
sqrt(d)``, ``k`` likewise without the ``1 / sqrt(d)`` (``use_qk_norm``),
``v = v~``; ``g = kda_lower_bound sigmoid(exp(A_log_h) (n W_f + dt_bias))``
a channel (``kda_safe_gate``, ``no_kda_lora``); ``beta = sigmoid(n W_b)``
a head; then **the recurrence literally, one position after another**
(``lax.scan``): ``S' = Diag(exp g_t) S``; ``S = S' + beta_t k_t (v_t - S'^T
k_t)^T``; ``o_t = S^T q_t``, ``S`` zero before position 0; ``Op = (RMSNorm_d
(o) * sigmoid(n W_g)_h) W_o`` (``group_norm_size`` 1,
``gated_attention_proj_granularity_type`` head_wise). No rotary embedding,
no chunked form, no ``[C, C, d]`` product anywhere.

``Op`` of a latent layer: ``families/joyai.py``'s expanded form with
queries through one matrix (``q_lora_rank`` null), rope by halves over the
``qk_rope_head_dim`` (``rotary_dim``) values, and each head's attended
values times ``sigmoid(n W_g)_h`` before ``W_o``.

``FFN`` of a layer ``i < first_k_dense_replace``: SwiGLU of
``intermediate_size``. Of the others, by DeepSeek-V3's published steps for
``noaux_tc`` with groups: ``s = sigmoid(h W_r)`` over all
``published_num_experts`` in float32; ``c = s + b``
(``moe_router_enable_expert_bias``); the experts are ``n_group`` runs of
neighbours, a group's score the sum of its two largest ``c``; the
``topk_group`` best groups stay; the ``num_experts_per_tok`` experts are
the largest ``c`` among them; their weights are ``s`` without ``b``, over
their sum (``norm_topk_prob``), times ``routed_scaling_factor``; every
*held* expert (SwiGLU of ``moe_intermediate_size``) applied to every token
one at a time with that weight as a mask, plus the shared expert (SwiGLU
of ``moe_shared_expert_intermediate_size``). What the experts of other
chips would add is left out, here as in the program. Final RMSNorm, untied
head over the rows of the vocabulary held.

Departures from the published code, each also under ``assumed`` in the
configuration file: experts outside the kept groups are taken out of the
choice by ``-inf`` where the published code writes 0.0 (the same choice
whenever the kept groups hold ``num_experts_per_tok`` experts with ``c >
0``); the SwiGLU clamp (``expert_swiglu_limit_list``) is 0 in every layer
held and is not built; no prediction module; text path only; seeded
weights, a seeded gate (``kda_gate_init``) and a seeded expert bias.

It reads the program's parameter tree and nothing else of the program:
``embed_tokens``, per layer ``layers_<j>/{input_norm, post_attn_norm}``
(``j`` counts the layers held), ``kda/{q_proj, k_proj, v_proj, f_proj,
b_proj, g_proj, o_proj, conv_kernel, A_log, dt_bias, o_norm}`` or
``attn/{q_proj, kv_a_proj, kv_a_norm, kv_b_proj, g_proj, o_proj}``, and
``mlp/{gate,up,down}_proj`` (dense) or ``moe/{router, bias, wg, wi, wo,
shared/{gate,up,down}_proj}``, ``final_norm``, ``lm_head``.
"""

from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from perfbench import roofline
# Imported here, not where it is first used: a tree without the model
# fails when the family is loaded, before JAX has started a device.
from raytpu.models.mixtral import LingHybridConfig, Mixtral, make_train_step

SERVE_MODEL = "ling_hybrid"
DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}
KDA, LATENT = "kda", "full_attention"
# The most float32 score entries one block of query rows may hold.
SCORE_ENTRIES = 1 << 25


# ---- the program's side ----------


def layers_held(cfg: Mapping) -> Sequence[int]:
    """The published indices of the layers held, in order."""
    held = tuple(cfg["layers_held"])
    assert len(held) == cfg["num_hidden_layers"], held
    return held


def kind_of(cfg: Mapping, published: int) -> str:
    return LATENT if (published + 1) % cfg["layer_group_size"] == 0 else KDA


def layer_types(cfg: Mapping) -> Sequence[str]:
    return tuple(kind_of(cfg, i) for i in layers_held(cfg))


def dense_layers(cfg: Mapping) -> int:
    """The held layers whose feed-forward is the dense SwiGLU: they lead."""
    return sum(i < cfg["first_k_dense_replace"] for i in layers_held(cfg))


def experts_held(cfg: Mapping):
    """``(first, count)`` of the routed experts this chip holds."""
    first, count = cfg["experts_held"]
    assert count == cfg["num_experts"], cfg["experts_held"]
    return int(first), int(count)


def router_width(cfg: Mapping) -> int:
    """The experts the router scores: the published count."""
    return int(cfg.get("published_num_experts", cfg["num_experts"]))


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``LingHybridConfig`` for a configuration file."""
    assert cfg["score_function"] == "sigmoid" \
        and cfg["moe_router_enable_expert_bias"] and cfg["use_qk_norm"] \
        and cfg["q_lora_rank"] is None and cfg["linear_silu"] \
        and cfg["kda_safe_gate"] and cfg["no_kda_lora"] \
        and not cfg["use_kda_lora"] and cfg["group_norm_size"] == 1 \
        and cfg["gated_attention_proj_granularity_type"] == "head_wise" \
        and cfg["rotary_dim"] == cfg["qk_rope_head_dim"] \
        and not any(cfg[k][i] for i in layers_held(cfg)
                    for k in ("expert_swiglu_limit_list",
                              "share_expert_swiglu_limit_list")) \
        and cfg["moe_shared_expert_intermediate_size"] \
        == cfg["moe_intermediate_size"]
    train = cfg.get("train", {})
    gate = cfg["assumed"]["kda_gate_init"]
    fields = dict(
        vocab_size=vocab_rows_held(cfg),
        block_size=cfg["max_position_embeddings"],
        n_layer=cfg["num_hidden_layers"], layer_types=layer_types(cfg),
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], n_embd=cfg["hidden_size"],
        head_dim=cfg["head_dim"], n_inter=cfg["moe_intermediate_size"],
        n_expert=router_width(cfg), experts_held=experts_held(cfg),
        n_expert_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        norm_topk_prob=cfg["norm_topk_prob"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), scoring=cfg["score_function"],
        choice_bias=float(cfg["assumed"]["expert_bias_std"]),
        routed_scale=float(cfg["routed_scaling_factor"]), n_shared=1,
        first_dense=dense_layers(cfg), dense_inter=cfg["intermediate_size"],
        q_lora_rank=None, kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_interleave=False, attn_head_gate=True,
        conv_taps=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        kda_gate_init=(tuple(gate["A"]), tuple(gate["dt_bias"])),
        dtype=DTYPES[cfg["compute_dtype"]][0],
        param_dtype=DTYPES[cfg["param_dtype"]][0], scan_layers=False,
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return LingHybridConfig(**fields)


def train_parts(pcfg):
    model = Mixtral(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, min(pcfg.block_size, 128)),
                           jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


# ---- counts, from the configuration file ----------


def vocab_rows_held(cfg: Mapping) -> int:
    return int(cfg["vocab_size"])


def _heads(cfg: Mapping):
    return cfg["num_attention_heads"], cfg["head_dim"]


def _kda_params(cfg: Mapping) -> int:
    """A KDA operator: q, k, v, the decay's matrix and the output's, the
    two a head, the convolutions' taps, ``A_log``, ``dt_bias`` and the
    head norm."""
    e, (h, d) = cfg["hidden_size"], _heads(cfg)
    return (5 * e * h * d + 2 * e * h
            + cfg["short_conv_kernel_size"] * 3 * h * d + h + h * d + d)


def _latent_params(cfg: Mapping) -> int:
    """A latent attention: queries through one matrix, the latent's two,
    the output's, the gate a head and the latent's norm."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kr, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                          cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (e * h * (nope + rope) + e * (kr + rope) + kr
            + kr * h * (nope + vd) + h * vd * e + e * h)


def _expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_params(cfg: Mapping, published: int, experts: float) -> float:
    """A block with ``experts`` of its routed experts: the operator of its
    kind, the two block norms, and the dense SwiGLU or the router, its
    bias, the shared expert and the routed ones."""
    e = cfg["hidden_size"]
    outside = 2 * e + (_kda_params(cfg) if kind_of(cfg, published) == KDA
                       else _latent_params(cfg))
    if published < cfg["first_k_dense_replace"]:
        return outside + 3 * e * cfg["intermediate_size"]
    return (outside + e * router_width(cfg) + router_width(cfg)
            + 3 * e * cfg["moe_shared_expert_intermediate_size"]
            + experts * _expert_params(cfg))


def _outside_layers(cfg: Mapping) -> int:
    """Embedding, untied output head, final norm."""
    return 2 * vocab_rows_held(cfg) * cfg["hidden_size"] + cfg["hidden_size"]


def param_count(cfg: Mapping) -> int:
    """Parameters as the program holds them: the held experts only."""
    return int(_outside_layers(cfg) + sum(
        _layer_params(cfg, i, experts_held(cfg)[1])
        for i in layers_held(cfg)))


def active_param_count(cfg: Mapping) -> float:
    """Parameters one token uses here: of its ``num_experts_per_tok``
    experts a layer, the share that is held."""
    here = cfg["num_experts_per_tok"] * experts_held(cfg)[1] \
        / router_width(cfg)
    return _outside_layers(cfg) + sum(
        _layer_params(cfg, i, here) for i in layers_held(cfg))


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    return roofline.train_flops_per_token(
        active_param_count(cfg), layer_types(cfg).count(LATENT),
        cfg["hidden_size"], seq_len)


def latent_row(cfg: Mapping) -> int:
    """Values a token's cache row holds in one latent layer, as published."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def kv_shape(cfg: Mapping):
    """``(pools, kv_heads, head_dim, itemsize)``: one latent pool for every
    latent layer held, one "head" of the row's published width (read once,
    as keys and as values: ``families/joyai.py``); a KDA layer has none."""
    return (layer_types(cfg).count(LATENT), 1, latent_row(cfg),
            DTYPES[cfg["compute_dtype"]][1])


def latent_attn_bytes(cfg: Mapping, page_size: int, live_pages: int) -> float:
    """Pool bytes the latent kernel must read when the decode steps counted
    read ``live_pages`` pages in one layer: each page's rows once, at the
    published width, in every latent layer held."""
    layers, _, row, itemsize = kv_shape(cfg)
    return float(layers) * live_pages * page_size * row * itemsize


def latent_attn_flops(cfg: Mapping, live_tokens: int) -> float:
    """FLOPs of the absorbed form over ``live_tokens`` cached positions of
    one layer, one query token a sequence, in every latent layer held
    (``families/joyai.py``)."""
    return (float(kv_shape(cfg)[0]) * live_tokens
            * cfg["num_attention_heads"]
            * 2.0 * (latent_row(cfg) + cfg["kv_lora_rank"]))


def _state_arrays(cfg: Mapping):
    """``(a sequence's matrices, its convolutions' tails)`` of one KDA
    layer, in bytes: float32 ``[heads, d, d]``, and ``taps - 1`` rows of
    ``[q~ | k~ | v~]`` in the model's dtype."""
    h, d = _heads(cfg)
    return (h * d * d * 4,
            (cfg["short_conv_kernel_size"] - 1) * 3 * h * d
            * DTYPES[cfg["compute_dtype"]][1])


def state_bytes_per_seq(cfg: Mapping) -> int:
    """Bytes one sequence holds in the state arrays of every KDA layer
    held, whatever its length."""
    return layer_types(cfg).count(KDA) * sum(_state_arrays(cfg))


def kda_state_bytes(cfg: Mapping, rows: int) -> float:
    """Bytes one decode position of ``rows`` sequences must move in the
    recurrence of every KDA layer held: the matrices read and written,
    and the row's ``q``, ``k``, ``g`` (``d`` a head), ``v`` and ``o``
    (``d`` a head) and ``beta`` (one a head) in float32. What the
    recurrence moves and no more: ISSUE 59's count also had the
    convolutions' tails read and written (2 x 73,728 B a row a layer, 3.4
    %), which the convolution moves before the recurrence starts;
    ``kda_state_roofline`` divides these bytes by the time of the state
    kernel's events alone, so bytes that kernel never touches read as
    bandwidth it never had."""
    h, d = _heads(cfg)
    matrices, _ = _state_arrays(cfg)
    a_row = 4 * (5 * h * d + h)
    return float(rows) * layer_types(cfg).count(KDA) * (
        2 * matrices + a_row)


def kda_state_flops(cfg: Mapping, rows: int) -> float:
    """FLOPs of that position: on each head's ``d x d`` matrix the decay
    (1 an element), ``S'^T k`` (2), the rank-one update (2) and ``S^T q``
    (2)."""
    h, d = _heads(cfg)
    return float(rows) * layer_types(cfg).count(KDA) * h * 7.0 * d * d


def routed_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def moe_shape(cfg: Mapping):
    """``(routed layers, experts held, experts per token, hidden, one
    expert's width, bytes an element of an expert matrix as multiplied)``:
    over the experts held here, which are the ones the program counts."""
    return (routed_layers(cfg), experts_held(cfg)[1],
            cfg["num_experts_per_tok"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], DTYPES[cfg["compute_dtype"]][1])


def expert_ffn_flops(cfg: Mapping, assignments: int) -> float:
    """FLOPs of the routed expert matrices for ``assignments`` (token,
    expert) pairs computed here: three products of hidden x width each."""
    _, _, _, hidden, width, _ = moe_shape(cfg)
    return assignments * 3 * 2.0 * hidden * width


def expert_ffn_bytes(cfg: Mapping, experts_touched: int) -> float:
    """Weight bytes the routed layer must read when ``experts_touched``
    (expert, layer) pairs received a token: three matrices each, once."""
    _, _, _, hidden, width, itemsize = moe_shape(cfg)
    return experts_touched * 3.0 * hidden * width * itemsize


# ---- the plain reference ----------


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _kernels(p):
    return {k: v["kernel"].astype(jnp.float32) for k, v in p.items()
            if isinstance(v, Mapping) and "kernel" in v}


def _rms(x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def _rms_norm(x, p, eps):
    return _rms(x, eps) * p["scale"].astype(jnp.float32)


def _rope(cfg: Mapping, x):
    """``x`` [..., T, D] at positions 0..T-1, the two halves rotated by
    angle ``p theta^(-2j/D)``."""
    t, d = x.shape[-2], x.shape[-1]
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


def _head_gate(kern, y, o):
    """``o`` [B, T, H, D] times each head's ``sigmoid(y W_g)``, heads side
    by side."""
    o = o * jax.nn.sigmoid(y @ kern["g_proj"])[..., None]
    return o.reshape(*o.shape[:2], -1)


def _latent_attention(cfg: Mapping, a, y):
    """Expanded latent attention of the normed ``y`` [B, T, E]."""
    b, t, _ = y.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    kern = _kernels(a)
    q = (y @ kern["q_proj"]).reshape(b, t, h, -1).transpose(0, 2, 1, 3)
    kva = y @ kern["kv_a_proj"]
    c_kv = _rms_norm(kva[..., :rank], a["kv_a_norm"], eps)
    k_pe = _rope(cfg, kva[..., rank:])                     # one key, [B,T,r]
    kv = (c_kv @ kern["kv_b_proj"]).reshape(b, t, h, -1)
    kv = kv.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], _rope(cfg, q[..., nope:])], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, None], (b, h, t, k_pe.shape[-1]))], -1)
    o = _attend(q, k, kv[..., nope:]).transpose(0, 2, 1, 3)
    return _head_gate(kern, y, o) @ kern["o_proj"]


def _short_conv(taps, x):
    """``x`` [B, T, W] through a causal convolution a channel, ``taps``
    [L, W] oldest first, as ``L`` shifted adds; then SiLU."""
    last = taps.shape[0] - 1
    mixed = taps[last] * x
    for back in range(1, last + 1):               # x_{t - back}: zeros
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :-back]
        mixed = mixed + taps[last - back] * shifted
    return jax.nn.silu(mixed)


def _l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def kda_recurrence(q, k, v, g, beta, state=None):
    """The recurrence literally: ``q``, ``k``, ``g`` [B, T, H, d], ``v``
    [B, T, H, dv], ``beta`` [B, T, H] -> ``(o [B, T, H, dv], the state
    after the last position)``; ``state`` [B, H, d, dv], zeros if not
    given."""
    if state is None:
        state = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:],
                          jnp.float32)

    def one(s, x):
        q, k, v, g, beta = x
        s = s * jnp.exp(g)[..., None]                      # S'
        u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k))
        s = s + k[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q)

    state, o = jax.lax.scan(
        one, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _kda(cfg: Mapping, p, y):
    """The KDA operator of the normed ``y`` [B, T, E] -> ``(its output,
    each head's state after the last position [B, H, d, d])``."""
    b, t, _ = y.shape
    h, d = _heads(cfg)
    kern = _kernels(p)
    mixed = _short_conv(
        p["conv_kernel"].astype(jnp.float32), jnp.concatenate(
            [y @ kern["q_proj"], y @ kern["k_proj"], y @ kern["v_proj"]], -1))
    q, k, v = (x.reshape(b, t, h, d) for x in jnp.split(mixed, 3, axis=-1))
    q, k = _l2norm(q) / math.sqrt(d), _l2norm(k)
    f = (y @ kern["f_proj"] + p["dt_bias"].astype(jnp.float32)) \
        .reshape(b, t, h, d)
    g = float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * f)
    beta = jax.nn.sigmoid(y @ kern["b_proj"])
    o, state = kda_recurrence(q, k, v, g, beta)
    o = _rms(o, cfg["rms_norm_eps"]) * p["o_norm"].astype(jnp.float32)
    return _head_gate(kern, y, o) @ kern["o_proj"], state


def _swiglu(p, y):
    p = _kernels(p)
    return (jax.nn.silu(y @ p["gate_proj"]) * (y @ p["up_proj"])) \
        @ p["down_proj"]


def router_weights(cfg: Mapping, moe, y):
    """``y`` [..., E] float32 -> [..., published experts]: each token's
    sigmoid score at its chosen experts, over their sum, times the scaling
    factor; zero elsewhere. The choice by the published steps: a group's
    score is the sum of its two largest score + bias, the ``topk_group``
    best groups stay, and the ``num_experts_per_tok`` largest score + bias
    among their experts are chosen."""
    s = jax.nn.sigmoid(y @ moe["router"]["kernel"].astype(jnp.float32))
    c = s + moe["bias"].astype(jnp.float32)
    groups = c.reshape(*c.shape[:-1], cfg["n_group"], -1)
    score = jax.lax.top_k(groups, 2)[0].sum(-1)
    kth = jax.lax.top_k(score, cfg["topk_group"])[0][..., -1:]
    # (-inf where the published code writes 0.0: see the module docstring.)
    c = jnp.where((score >= kth)[..., None], groups, -jnp.inf) \
        .reshape(c.shape)
    kth = jax.lax.top_k(c, cfg["num_experts_per_tok"])[0][..., -1:]
    w = jnp.where(c >= kth, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * float(cfg["routed_scaling_factor"])


def _experts(cfg: Mapping, moe, y):
    """Every held expert on every token, one expert at a time; a token
    keeps the outputs of the experts its router chose, weighted. Then the
    shared expert, on every token."""
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


def _block(cfg: Mapping, published: int, x, lp):
    """``(the block's output, a KDA layer's state after the last position
    or None)``."""
    eps = cfg["rms_norm_eps"]
    n = _rms_norm(x, lp["input_norm"], eps)
    if kind_of(cfg, published) == KDA:
        mixed, state = _kda(cfg, lp["kda"], n)
    else:
        mixed, state = _latent_attention(cfg, lp["attn"], n), None
    x = x + mixed
    y = _rms_norm(x, lp["post_attn_norm"], eps)
    if published < cfg["first_k_dense_replace"]:
        return x + _swiglu(lp["mlp"], y), state
    return x + _experts(cfg, lp["moe"], y), state


def _head(x, kernel):
    """``x @ kernel`` in float32, a block of the vocabulary's columns at a
    time, written where it belongs."""
    v = kernel.shape[1]
    blocks = next(n for n in (8, 4, 2, 1) if v % n == 0)
    width = v // blocks

    def one(i, out):
        w = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ w.astype(jnp.float32), i * width, x.ndim - 1)

    return jax.lax.fori_loop(
        0, blocks, one, jnp.zeros(x.shape[:-1] + (v,), jnp.float32))


def _walk(cfg: Mapping, params, tokens):
    """``(the last block's output, the KDA layers' states after the last
    position, in the layers' order)``."""
    x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
    states = []
    for j, published in enumerate(layers_held(cfg)):
        x, state = _block(cfg, published, x, params[f"layers_{j}"])
        if state is not None:
            states.append(state)
    return x, states


def logits(cfg: Mapping, params, tokens, rows=None):
    """``tokens`` [B, T] -> logits [B, T, vocabulary]; with ``rows`` (a
    list of positions) [B, len(rows), vocabulary], of those alone."""
    with jax.default_matmul_precision("highest"):
        x, _ = _walk(cfg, params, tokens)
        x = _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        if rows is not None:
            x = x[:, jnp.asarray(rows)]
        return _head(x, params["lm_head"]["kernel"])


def kda_states(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> the matrix state every KDA layer holds after
    position ``T - 1``, float32 ``[KDA layers, B, H, d, d]``: what a
    served sequence's seat holds once ``tokens`` went through it. No
    ``correct`` reads it (``serve_cell.check_logits`` compares logits);
    ``chip_ling.py`` does, because no row of logits tells a state held
    in bfloat16 from the float32 one (PERF.md section 6, PR 59)."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(_walk(cfg, params, tokens)[1])


def loss(cfg: Mapping, params, tokens):
    """Mean next-token cross-entropy, one sequence at a time. The
    router's load-balance term is the trainer's and is left out."""

    def one(seq):
        lg = logits(cfg, params, seq[None])[0, :-1]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        label = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
        return (lse - label).mean()

    return jax.lax.map(one, tokens).mean()
