"""The ``longcat_flash`` family: LongCat-Flash-Chat (560B-A27B), a decoder
whose published layer is **two** latent-attention sublayers, **two** dense
feed-forwards and **one** routed layer beside them on a shortcut, and whose
router chooses 12 of 768 outputs of which 256 are identity experts that
return their input and cost no product. Same interface as ``joyai.py``
(its counts over the experts *held*: the configuration holds one chip's
share of each layer's routed experts, ``experts_held``; ``latent_attn_bytes``
and ``latent_attn_flops`` for the absorbed latent kernel's readers), with
its pools counted by attention sublayer: two a published layer.

Program side: ``raytpu/models/mixtral.py`` (``LongcatFlashConfig``, whose
``n_layer`` counts sublayers; ``Mixtral`` is its training forward,
``MoEFFN`` its routed layer), ``raytpu/models/mla.py``
(``LatentAttention``), ``raytpu/ops/mla_attention.py`` (the absorbed paged
kernel), served by the llama family's walk over one latent pool a sublayer.

The plain reference below is written from the layer equations of the
published ``config.json``, the published modelling code's conventions for
its keys and section 2 of the technical report (arXiv:2509.01322), in
straightforward ``jax.numpy`` and float32, matrix products at
``jax.default_matmul_precision("highest")``, the attention in the
**expanded** form only, so that the program's absorbed decode is compared
with something that is not itself. One published layer ``l`` over the
stream ``x``, ``eps`` = ``rms_norm_eps``, every norm a scale of its own:

    MLA_j(h):                                  # j = 0, 1: parameters of their own
      c_q  = sqrt(hidden / q_lora_rank)  * RMSNorm(h W_qa)   # mla_scale_q_lora: 2
      q    = c_q W_qb          -> heads x [q_nope | q_pe]
      kv   = h W_kva           -> [c | k_pe]
      c_kv = sqrt(hidden / kv_lora_rank) * RMSNorm(c)        # mla_scale_kv_lora: 3.4641
      [k_nope | v] per head = c_kv W_kvb ; k_pe ONE key for all heads, NOT scaled
      rope(q_pe), rope(k_pe): interleaved pairs (2j, 2j+1) at angle p theta^(-2j/d)
      scores = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope), causal softmax
      out = concat(P v) W_o ; no bias anywhere

    Router(h): s = softmax(h W_r) in float32 over n_routed (published) +
               zero_expert_num outputs; choose the moe_topk largest of s + b ;
               w_i = routed_scaling_factor * s_i for the chosen
               (the scores WITHOUT b, NOT renormalised)
    MoE(h) = sum_{chosen i < n_routed} w_i SwiGLU_i(h)
             + (sum_{chosen i >= n_routed} w_i) h          # identity experts

    x1 = x  + MLA_0(N_in0(x))
    h1 = N_post0(x1) ;  s = MoE(h1) ;  x2 = x1 + SwiGLU_dense0(h1)
    x3 = x2 + MLA_1(N_in1(x2))
    out = x3 + SwiGLU_dense1(N_post1(x3)) + s

Embedding, ``num_layers`` such layers, final RMSNorm, untied head. On one
chip of the deployment ``MoE`` sums over the chosen ``i`` in the share held
(``experts_held``) and keeps the identity term whole, since the chip owns
its tokens; what the experts of other chips would add is left out, here
as in the program. Each held expert is applied to every token, one expert
at a time in a Python loop, with the token's weight for it (zero where it
did not choose it). No cache, no sort, no absorbed form, no kernel.

Attention is computed a block of query rows at a time, a dense SwiGLU a
block of its width at a time and the head a block of the vocabulary's
columns at a time, so that no float32 copy of a 75 M-parameter matrix
stands beside a full chip; ``logits(..., rows=...)`` gives chosen
positions alone.

It reads the program's parameter tree and nothing else of the program:
``embed_tokens``; published layer ``l`` is ``layers_<2l>`` (``input_norm``,
``attn/{q_a_proj, q_a_norm, q_b_proj, kv_a_proj, kv_a_norm, kv_b_proj,
o_proj}``, ``post_attn_norm``, ``mlp/{gate,up,down}_proj`` and
``moe/{router, bias, wg, wi, wo}``, experts stacked on the first axis, the
held ones only) and ``layers_<2l+1>`` (the same without ``moe``);
``final_norm``, ``lm_head``.
"""

from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp

from perfbench import roofline
# Imported here, not where it is first used: a tree without the model
# fails when the family is loaded, before JAX has started a device.
from raytpu.models.mixtral import (LongcatFlashConfig, Mixtral,
                                   make_train_step)

SERVE_MODEL = "longcat_flash"
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


def routed_width(cfg: Mapping) -> int:
    """The routed experts the router scores: the published count."""
    return int(cfg.get("published_n_routed_experts",
                       cfg["n_routed_experts"]))


def router_width(cfg: Mapping) -> int:
    """The router's outputs: routed and identity experts."""
    return routed_width(cfg) + int(cfg["zero_expert_num"])


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``LongcatFlashConfig`` for a configuration file: two
    sublayers a published layer."""
    assert cfg["attention_method"] == "MLA" \
        and cfg["zero_expert_type"] == "identity" \
        and not cfg["attention_bias"] \
        and cfg["mla_scale_q_lora"] and cfg["mla_scale_kv_lora"]
    train = cfg.get("train", {})
    fields = dict(
        vocab_size=vocab_rows_held(cfg),
        block_size=cfg["max_position_embeddings"],
        n_layer=2 * cfg["num_layers"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_attention_heads"], n_embd=cfg["hidden_size"],
        n_inter=cfg["expert_ffn_hidden_size"],
        n_expert=routed_width(cfg), experts_held=experts_held(cfg),
        n_zero_expert=cfg["zero_expert_num"],
        n_expert_per_tok=cfg["moe_topk"], norm_topk_prob=False,
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        scoring="softmax",
        choice_bias=float(cfg["assumed"]["e_score_correction_bias_std"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        dense_inter=cfg["ffn_hidden_size"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_interleave=True,
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        dtype=DTYPES[cfg["compute_dtype"]][0],
        param_dtype=DTYPES[cfg["param_dtype"]][0], scan_layers=False,
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return LongcatFlashConfig(**fields)


def train_parts(pcfg):
    model = Mixtral(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, min(pcfg.block_size, 128)),
                           jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


# ---- counts, from the configuration file ----------------------------------------


def vocab_rows_held(cfg: Mapping) -> int:
    """The rows of the vocabulary held here: a multiple of 128."""
    return int(cfg["vocab_size"])


def _attn_params(cfg: Mapping) -> int:
    """One sublayer's latent attention: the five matrices, the two norms."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (e * qr + qr + qr * h * (nope + rope) + e * (kr + rope) + kr
            + kr * h * (nope + vd) + h * vd * e)


def _expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def _layer_params(cfg: Mapping, experts: float) -> float:
    """One published layer with ``experts`` of its routed experts: two
    sublayers of attention, two block norms and a dense SwiGLU each, and
    the router, its bias and the routed experts."""
    e = cfg["hidden_size"]
    sublayer = _attn_params(cfg) + 2 * e + 3 * e * cfg["ffn_hidden_size"]
    return (2 * sublayer + e * router_width(cfg) + router_width(cfg)
            + experts * _expert_params(cfg))


def _outside_layers(cfg: Mapping) -> int:
    """Embedding, untied output head, final norm."""
    return 2 * vocab_rows_held(cfg) * cfg["hidden_size"] + cfg["hidden_size"]


def param_count(cfg: Mapping) -> int:
    """Parameters as the program holds them: the held experts only."""
    return _outside_layers(cfg) + cfg["num_layers"] * _layer_params(
        cfg, experts_held(cfg)[1])


def active_param_count(cfg: Mapping) -> float:
    """Parameters one token uses here on average: of its ``moe_topk``
    choices a layer, the share that falls on a held expert (the identity
    experts have no parameters)."""
    here = cfg["moe_topk"] * experts_held(cfg)[1] / router_width(cfg)
    return _outside_layers(cfg) + cfg["num_layers"] * _layer_params(cfg, here)


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    return roofline.train_flops_per_token(
        active_param_count(cfg), pools(cfg), cfg["hidden_size"], seq_len)


def pools(cfg: Mapping) -> int:
    """Latent pools, one an attention sublayer: two a published layer."""
    return 2 * cfg["num_layers"]


def latent_row(cfg: Mapping) -> int:
    """Values a token's cache row holds in one pool, as published: the
    latent and the one roped key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def kv_shape(cfg: Mapping):
    """``(layers, kv_heads, head_dim, itemsize)`` as the other families
    give it, for a cache of one row a token a pool: ``layers`` counts the
    pools (attention sublayers), one "head" of the row's published width.
    The row is read once, as keys and as values (``families/joyai.py``)."""
    return (pools(cfg), 1, latent_row(cfg), DTYPES[cfg["compute_dtype"]][1])


def latent_attn_bytes(cfg: Mapping, page_size: int, live_pages: int) -> float:
    """Pool bytes the latent kernel must read when the decode steps
    counted read ``live_pages`` pages in one pool (the step records'
    sum): each page's rows once, at the published width, in every pool."""
    n, _, row, itemsize = kv_shape(cfg)
    return float(n) * live_pages * page_size * row * itemsize


def latent_attn_flops(cfg: Mapping, live_tokens: int) -> float:
    """FLOPs of the absorbed form over ``live_tokens`` cached positions of
    one pool, one query token a sequence: every head's score over the row
    (latent + roped key) and its weighted sum of the latent, two a
    multiply-add, in every pool. ``W_uk`` on the query and ``W_uv`` on the
    result are outside the kernel and not counted."""
    return (float(pools(cfg)) * live_tokens * cfg["num_attention_heads"]
            * 2.0 * (latent_row(cfg) + cfg["kv_lora_rank"]))


def moe_shape(cfg: Mapping):
    """``(routed layers, experts held, choices per token, hidden, one
    expert's width, bytes an element of an expert matrix as multiplied)``:
    one routed layer a published layer, over the experts held here, which
    are the ones the program counts (``moe_assignments``,
    ``moe_experts_touched``). Of a token's ``moe_topk`` choices those that
    fall on identity experts or on another chip's are in neither."""
    return (cfg["num_layers"], experts_held(cfg)[1], cfg["moe_topk"],
            cfg["hidden_size"], cfg["expert_ffn_hidden_size"],
            DTYPES[cfg["compute_dtype"]][1])


def expert_ffn_flops(cfg: Mapping, assignments: int) -> float:
    """FLOPs of the routed expert matrices for ``assignments`` (token,
    expert) pairs computed here: three products of hidden x width each.
    An identity expert multiplies nothing and is in no assignment."""
    _, _, _, hidden, width, _ = moe_shape(cfg)
    return assignments * 3 * 2.0 * hidden * width


def expert_ffn_bytes(cfg: Mapping, experts_touched: int) -> float:
    """Weight bytes the routed layer must read when ``experts_touched``
    (expert, layer) pairs received a token: three matrices each, once."""
    _, _, _, hidden, width, itemsize = moe_shape(cfg)
    return experts_touched * 3.0 * hidden * width * itemsize


# ---- the plain reference -----------------------------------------------------------


def _kernel(p):
    return p["kernel"].astype(jnp.float32)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _rope(cfg: Mapping, x):
    """``x`` [..., T, D] at positions 0..T-1, interleaved: the values are
    read as adjacent pairs ``(2j, 2j+1)``, laid out [evens | odds], and the
    two halves rotated by ``p theta^(-2j/D)``."""
    t, d = x.shape[-2], x.shape[-1]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    freqs = float(cfg["rope_theta"]) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
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
    """Expanded latent attention of the normed ``y`` [B, T, E], the two
    latents scaled as ``mla_scale_*`` say, the roped key not."""
    b, t, e = y.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    q_scale = math.sqrt(e / cfg["q_lora_rank"]) \
        if cfg["mla_scale_q_lora"] else 1.0
    kv_scale = math.sqrt(e / rank) if cfg["mla_scale_kv_lora"] else 1.0
    c_q = q_scale * _rms_norm(y @ _kernel(a["q_a_proj"]), a["q_a_norm"], eps)
    q = (c_q @ _kernel(a["q_b_proj"])).reshape(b, t, h, -1)
    q = q.transpose(0, 2, 1, 3)
    kva = y @ _kernel(a["kv_a_proj"])
    c_kv = kv_scale * _rms_norm(kva[..., :rank], a["kv_a_norm"], eps)
    k_pe = _rope(cfg, kva[..., rank:])                     # one key, [B,T,r]
    kv = (c_kv @ _kernel(a["kv_b_proj"])).reshape(b, t, h, -1)
    kv = kv.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], _rope(cfg, q[..., nope:])], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, None], (b, h, t, k_pe.shape[-1]))], -1)
    o = _attend(q, k, kv[..., nope:])
    return o.transpose(0, 2, 1, 3).reshape(b, t, -1) @ _kernel(a["o_proj"])


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
    """``y`` [..., E] float32 -> [..., router outputs]: each token's
    softmax score, times the scaling factor, at the ``moe_topk`` outputs
    whose score + bias is largest; zero elsewhere. Not renormalised."""
    s = jax.nn.softmax(y @ _kernel(moe["router"]), axis=-1)
    choice = s + moe["bias"].astype(jnp.float32)
    kth = jax.lax.top_k(choice, cfg["moe_topk"])[0][..., -1:]
    return jnp.where(choice >= kth, s, 0.0) \
        * float(cfg["routed_scaling_factor"])


def _moe(cfg: Mapping, moe, y):
    """The routed layer of ``y``: every held expert on every token, one
    expert at a time, weighted by the token's weight for it (zero where
    it was not chosen), plus the identity experts' term."""
    first, count = experts_held(cfg)
    w = router_weights(cfg, moe, y)
    out = w[..., routed_width(cfg):].sum(-1, keepdims=True) * y
    for i in range(count):
        wg, wi, wo = (moe[name][i].astype(jnp.float32)
                      for name in ("wg", "wi", "wo"))
        out = out + w[..., first + i, None] \
            * ((jax.nn.silu(y @ wg) * (y @ wi)) @ wo)
    return out


def _layer(cfg: Mapping, x, first, second):
    """One published layer: ``first`` and ``second`` are its two
    sublayers' parameters; the routed layer's are ``first``'s."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, first["attn"],
                       _rms_norm(x, first["input_norm"], eps))
    h = _rms_norm(x, first["post_attn_norm"], eps)
    shortcut = _moe(cfg, first["moe"], h)
    x = x + _swiglu(first["mlp"], h)
    x = x + _attention(cfg, second["attn"],
                       _rms_norm(x, second["input_norm"], eps))
    h = _rms_norm(x, second["post_attn_norm"], eps)
    return x + _swiglu(second["mlp"], h) + shortcut


def hidden_states(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> final-norm hidden states [B, T, E], float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
        for l in range(cfg["num_layers"]):
            x = _layer(cfg, x, params[f"layers_{2 * l}"],
                       params[f"layers_{2 * l + 1}"])
        return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


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
