"""The ``exaone_moe`` family: K-EXAONE-236B-A23B, a decoder whose layers
are window attention (128 positions, roped) three to every full one (no
positional signal at all), each head's q and k normed, a leading dense
layer and after it routed layers of 128 experts chosen by sigmoid score
plus a correction bias beside one shared expert, and one
multi-token-prediction module through which the served model drafts for
itself. Same interface as ``gpt2.py``, ``olmoe.py``, ``mellum.py`` and
``joyai.py``, its counts over the experts *held* (``experts_held``), plus
one function of its own:

**``draft_logits(cfg, params, tokens)``**: the prediction module's logits
for every position, teacher-forced: row ``i`` is the module's distribution
of token ``i + 2`` given tokens ``0 .. i + 1`` (the model's residual stream
at ``i`` beside the embedding of token ``i + 1``). What the served
engine's drafts are drawn from, and what ``tests/test_exaone_moe.py`` and
``chip_kexaone.py`` hold them to. The last row has no token to follow it
and is left out: ``[B, T - 1, V]``.

Program side: ``raytpu/models/mixtral.py`` (``ExaoneMoeConfig``;
``Mixtral`` its training forward, ``MoEFFN`` its routed layer,
``PredictionModule`` and ``draft_rows`` the module), ``raytpu/models/
llama.py`` (``LlamaAttention``: ``qk_head_norm``, ``rope_kinds``,
``decode_rows``), served by the llama family's walks over two kinds of
pool and the module's own, ``raytpu/inference/sampling.py``
(``speculative``).

The plain reference below is written from the layer equations (ISSUE 42,
"The equations"; ``transformers`` conventions for the keys of the
published ``config.json``) in straightforward ``jax.numpy`` and float32,
matrix products at ``jax.default_matmul_precision("highest")``. Where
``config.json`` is silent the family's convention decides, and the
configuration file lists each under ``assumed``: pre-norm blocks; RMSNorm
over each head's values of q and of k before rope, one scale vector each a
layer; rope on window layers only; the module in DeepSeek-V3's form
(arXiv:2412.19437 section 2.2). For layer ``i`` of kind ``layer_types[i]``,
``eps`` = ``rms_norm_eps``: ``x' = RMSNorm(x)``; ``q = x' W_q`` (heads x
``head_dim``), ``k = x' W_k``, ``v = x' W_v`` (kv heads x ``head_dim``), no
bias; each head of q and of k RMSNorm'd; on a window layer rope at
``rope_theta`` over the two halves of each head, angle ``p theta^(-2j/d)``;
query head r reads kv head ``r // (heads / kv heads)``; scores over
``sqrt(head_dim)``; position p sees ``j <= p`` on a full layer and ``p -
sliding_window < j <= p`` on a window layer; softmax; ``W_o``; residual.
``x' = RMSNorm(x)``; layer ``i < first_k_dense_replace``: SwiGLU of
``intermediate_size``. The others: ``s = sigmoid(x' W_r)`` over all
``published_num_experts`` in float32; the ``num_experts_per_tok`` experts
are the largest of ``s + b`` (``e_score_correction_bias``; ``n_group`` 1:
no groups); their weights are ``s`` without ``b``, over their sum
(``norm_topk_prob``), times ``routed_scaling_factor``; every held expert
(SwiGLU of ``moe_intermediate_size``) applied to every token one at a time
with that weight as a mask, plus the shared expert unweighted. What the
experts of other chips would add is left out, here as in the program.
Final RMSNorm, untied head. The module, for position ``i``: ``u_i = W_eh
[RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]`` with ``h_i`` the residual
stream after the last block (before the final norm), one block of a routed
layer's shape with full attention over all ``u``, a final norm of its own,
the model's head. No cache, no sort, no kernel, no drafting.

Attention is computed a block of query rows at a time, experts upcast one
at a time, the head a block of the vocabulary's columns at a time.

It reads the program's parameter tree and nothing else of the program:
``embed_tokens``, per layer ``layers_<i>/{input_norm, attn/{q_proj,
k_proj, v_proj, o_proj, q_norm, k_norm}, post_attn_norm}`` and
``mlp/{gate,up,down}_proj`` (dense) or ``moe/{router, bias, wg, wi, wo,
shared/{gate,up,down}_proj}`` (experts stacked on the first axis, the held
ones only), ``final_norm``, ``lm_head``, and ``mtp/{enorm, hnorm, eh_proj,
block/<a routed layer's>, final_norm}``.
"""

from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from perfbench import roofline
# Imported here, not where it is first used: a tree without the model
# fails when the family is loaded, before JAX has started a device.
from raytpu.models.mixtral import ExaoneMoeConfig, Mixtral, make_train_step

SERVE_MODEL = "exaone_moe"
DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}
WINDOW, FULL = "sliding_attention", "full_attention"
# The most float32 score entries one block of query rows may hold.
SCORE_ENTRIES = 1 << 25


# ---- the program's side ----------------------------------------------------


def layer_types(cfg: Mapping) -> Sequence[str]:
    """The kinds of the layers held: the published list's first
    ``num_hidden_layers`` entries (the file keeps the list whole)."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def experts_held(cfg: Mapping):
    """``(first, count)`` of the routed experts this chip holds."""
    first, count = cfg["experts_held"]
    assert count == cfg["num_experts"], cfg["experts_held"]
    return int(first), int(count)


def router_width(cfg: Mapping) -> int:
    """The experts the router scores: the published count."""
    return int(cfg.get("published_num_experts", cfg["num_experts"]))


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``ExaoneMoeConfig`` for a configuration file."""
    kinds = layer_types(cfg)
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1 \
        and cfg["scoring_func"] == "sigmoid" \
        and cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"] \
        and cfg["rope_parameters"]["rope_type"] == "default" \
        and cfg["num_nextn_predict_layers"] == 1 \
        and cfg["mtp_layer_types"] == [FULL] \
        and all(w == (cfg["sliding_window"] if k == WINDOW else 0)
                for k, w in zip(kinds, cfg["sliding_windows"])) \
        and list(cfg["mlp_layer_types"][:len(kinds)]) == [
            "dense" if i < cfg["first_k_dense_replace"] else "sparse"
            for i in range(len(kinds))]
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
        layer_types=kinds, window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        scoring=cfg["scoring_func"],
        choice_bias=float(cfg["assumed"]["e_score_correction_bias_std"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        n_shared=cfg["num_shared_experts"],
        first_dense=cfg["first_k_dense_replace"],
        dense_inter=cfg["intermediate_size"],
        mtp_layers=cfg["num_nextn_predict_layers"],
        dtype=DTYPES[cfg["compute_dtype"]][0],
        param_dtype=DTYPES[cfg["param_dtype"]][0], scan_layers=False,
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return ExaoneMoeConfig(**fields)


def train_parts(pcfg):
    model = Mixtral(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, min(pcfg.block_size, 128)),
                           jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


# ---- counts, from the configuration file ----------------------------------------


def vocab_rows_held(cfg: Mapping) -> int:
    """The slice of the vocabulary held here, a multiple of 128."""
    return int(cfg["vocab_size"])


def _attn_params(cfg: Mapping) -> int:
    """A layer's attention: the four projections and the two head norms."""
    e, h, kv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    return e * (h + 2 * kv) * d + h * d * e + 2 * d


def _expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_params(cfg: Mapping, dense: bool, experts: float) -> float:
    """A block with ``experts`` of its routed experts: attention, the two
    block norms, and the dense SwiGLU or the router, its bias, the shared
    expert and the routed ones."""
    e = cfg["hidden_size"]
    outside = _attn_params(cfg) + 2 * e
    if dense:
        return outside + 3 * e * cfg["intermediate_size"]
    return (outside + e * router_width(cfg) + router_width(cfg)
            + cfg["num_shared_experts"] * _expert_params(cfg)
            + experts * _expert_params(cfg))


def _outside_layers(cfg: Mapping) -> int:
    """Embedding, untied output head, final norm."""
    return 2 * vocab_rows_held(cfg) * cfg["hidden_size"] + cfg["hidden_size"]


def _module_params(cfg: Mapping, experts: float) -> float:
    """The prediction module: its two input norms, ``W_eh``, one routed
    block and its final norm. Embedding and head are the model's."""
    e = cfg["hidden_size"]
    return cfg["num_nextn_predict_layers"] * (
        3 * e + 2 * e * e + _layer_params(cfg, False, experts))


def _params(cfg: Mapping, experts: float) -> float:
    return (_outside_layers(cfg) + _module_params(cfg, experts) + sum(
        _layer_params(cfg, i < cfg["first_k_dense_replace"], experts)
        for i in range(cfg["num_hidden_layers"])))


def param_count(cfg: Mapping) -> int:
    """Parameters as the program holds them: the held experts only, the
    prediction module among them."""
    return int(_params(cfg, experts_held(cfg)[1]))


def active_param_count(cfg: Mapping) -> float:
    """Parameters one token uses here: of its ``num_experts_per_tok``
    experts a layer, the share that is held."""
    return _params(cfg, cfg["num_experts_per_tok"] * experts_held(cfg)[1]
                   / router_width(cfg))


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    return roofline.train_flops_per_token(
        active_param_count(cfg), cfg["num_hidden_layers"],
        cfg["hidden_size"], seq_len)


def kv_shape(cfg: Mapping):
    """``(pools, kv_heads, head_dim, itemsize)``: a K and a V pool for
    every layer held and one pair more for the prediction module."""
    return (cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            DTYPES[cfg["compute_dtype"]][1])


def layers_by_kind(cfg: Mapping):
    """``(full pools, window pools)``: the layers held by kind, the
    module's full-attention pool counted with the full layers'."""
    kinds = layer_types(cfg)
    return (kinds.count(FULL) + cfg["num_nextn_predict_layers"],
            kinds.count(WINDOW))


def paged_attn_bytes_by_kind(cfg: Mapping, page_size: int,
                             live_pages_full: int,
                             live_pages_window: int) -> float:
    """Pool bytes the paged-attention kernel must read when the decode
    steps counted read ``live_pages_full`` pages in one full layer and
    ``live_pages_window`` in one window layer (the step records' sums):
    the K and the V rows of those pages, in every pool of the kind (a
    verify step's two query rows read a page once)."""
    _, kv, d, itemsize = kv_shape(cfg)
    full, window = layers_by_kind(cfg)
    return (full * roofline.paged_attn_bytes(
        live_pages_full, page_size, kv, d, itemsize)
        + window * roofline.paged_attn_bytes(
            live_pages_window, page_size, kv, d, itemsize))


def routed_layers(cfg: Mapping) -> int:
    """Routed layers a step runs: the model's and the module's one."""
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            + cfg["num_nextn_predict_layers"])


def moe_shape(cfg: Mapping):
    """``(routed layers, experts held, experts per token, hidden, one
    expert's width, bytes an element of an expert matrix as multiplied)``:
    over the experts held here, which are the ones the program counts
    (``moe_assignments``, ``moe_experts_touched``), the module's routed
    layer among the layers."""
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
    """``x`` [..., T, D] at positions 0..T-1, the two halves of each head
    rotated by angle ``p theta^(-2j/D)``."""
    t, d = x.shape[-2], x.shape[-1]
    freqs = float(cfg["rope_parameters"]["rope_theta"]) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window):
    """``q`` [B, H, T, D], ``k`` and ``v`` [B, KV, T, D], a block of query
    rows at a time: head r reads kv head ``r // (H / KV)``; row p sees
    keys ``j <= p``, and with a ``window`` only ``j > p - window``."""
    b, h, t, d = q.shape
    k, v = (jnp.repeat(x, h // k.shape[1], axis=1) for x in (k, v))
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
        seen = j <= p
        if window:
            seen &= j > p - window
        s = qi @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(one, (jnp.arange(blocks), qb))
    return out.transpose(1, 2, 0, 3, 4).reshape(
        b, h, blocks * rows, d)[:, :, :t]


def _attention(cfg: Mapping, a, y, kind: str):
    """Attention of the normed ``y`` [B, T, E] in a layer of ``kind``."""
    b, t, _ = y.shape
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]

    def heads(name, n):
        x = y @ a[name]["kernel"].astype(jnp.float32)
        return x.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    q = _rms_norm(heads("q_proj", h), a["q_norm"], eps)
    k = _rms_norm(heads("k_proj", kv), a["k_norm"], eps)
    if kind == WINDOW:  # the full layers see no positions
        q, k = _rope(cfg, q), _rope(cfg, k)
    o = _attend(q, k, heads("v_proj", kv),
                cfg["sliding_window"] if kind == WINDOW else None)
    return o.transpose(0, 2, 1, 3).reshape(b, t, h * d) \
        @ a["o_proj"]["kernel"].astype(jnp.float32)


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
    the shared expert, on every token, unweighted."""
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


def _block(cfg: Mapping, x, lp, kind: str, dense: bool):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, lp["attn"],
                       _rms_norm(x, lp["input_norm"], eps), kind)
    y = _rms_norm(x, lp["post_attn_norm"], eps)
    return x + (_swiglu(lp["mlp"], y) if dense
                else _experts(cfg, lp["moe"], y))


def residual_stream(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> the residual stream after the last block,
    before the final norm, [B, T, E] float32."""
    x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
    for i, kind in enumerate(layer_types(cfg)):
        x = _block(cfg, x, params[f"layers_{i}"], kind,
                   i < cfg["first_k_dense_replace"])
    return x


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
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(residual_stream(cfg, params, tokens),
                      params["final_norm"], cfg["rms_norm_eps"])
        if rows is not None:
            x = x[:, jnp.asarray(rows)]
        return _head(x, params["lm_head"]["kernel"])


def draft_logits(cfg: Mapping, params, tokens, rows=None):
    """``tokens`` [B, T] -> the prediction module's logits [B, T - 1,
    vocabulary], teacher-forced: row ``i`` is its distribution of token
    ``i + 2``, from the model's residual stream at ``i`` and the
    embedding of token ``i + 1``. ``rows``: of those positions alone."""
    eps, m = cfg["rms_norm_eps"], params["mtp"]
    with jax.default_matmul_precision("highest"):
        h = residual_stream(cfg, params, tokens)[:, :-1]
        e = params["embed_tokens"]["embedding"][tokens[:, 1:]] \
            .astype(jnp.float32)
        u = jnp.concatenate([_rms_norm(e, m["enorm"], eps),
                             _rms_norm(h, m["hnorm"], eps)], -1) \
            @ m["eh_proj"]["kernel"].astype(jnp.float32)
        u = _rms_norm(_block(cfg, u, m["block"], FULL, False),
                      m["final_norm"], eps)
        if rows is not None:
            u = u[:, jnp.asarray(rows)]
        return _head(u, params["lm_head"]["kernel"])


def loss(cfg: Mapping, params, tokens):
    """Mean next-token cross-entropy of the model's own logits, one
    sequence at a time. The router's load-balance term and the module's
    prediction loss are the trainer's and are left out."""

    def one(seq):
        lg = logits(cfg, params, seq[None])[0, :-1]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        label = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
        return (lse - label).mean()

    return jax.lax.map(one, tokens).mean()
