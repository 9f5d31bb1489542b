"""The ``lfm2_moe`` family: LFM2-24B-A2B, a decoder whose layers are gated
short convolutions three to every full-attention one (32 query heads on 8
kv heads of 64, each head's q and k normed, rope at theta 1e6), two
leading dense layers and after them routed layers of 64 experts of which a
token takes 4 by sigmoid score plus a bias, no shared expert, the output
head tied to the embedding. A conv layer keeps no keys and values: a served
sequence holds ``conv_L_cache - 1`` rows of the convolution's input there
(its *state*) and nothing that grows. Same interface as ``gpt2.py``,
``olmoe.py``, ``mellum.py``, ``joyai.py`` and ``exaone_moe.py``, plus one
optional function of its own:

**``state_bytes_per_seq(cfg)``**: bytes one sequence holds in the state
arrays of every conv layer held, whatever its length
(``cache_resident_vs_all_kv_pct`` reads it beside ``kv_shape``, whose
layers are the attention layers alone).

Program side: ``raytpu/models/short_conv.py`` (``ShortConv``),
``raytpu/models/mixtral.py`` (``Lfm2MoeConfig``; ``Mixtral`` its training
forward, ``MoEFFN`` its routed layer), ``raytpu/models/llama.py``
(``LlamaAttention``: ``qk_head_norm``; the serving walks, which give a
conv layer its state array and the sequences' seats),
``raytpu/inference/kv_cache.py`` (seats and state arrays).

The plain reference below is written from the layer equations (ISSUE 47,
"The layer equations": the published ``Lfm2Moe`` modelling;
``transformers`` conventions for the keys of the published
``config.json``) in straightforward ``jax.numpy`` and float32, matrix
products at ``jax.default_matmul_precision("highest")``. ``n =
RMSNorm(x)``, eps ``norm_eps``, no bias anywhere. Block ``i``: ``h = x +
Op_i(RMSNorm(x))``, ``y = h + FFN_i(RMSNorm(h))``. ``Op`` of a ``conv``
layer: ``[B | C | u] = W_in n`` (hidden to three times hidden, in that
order); ``v_t = B_t * u_t``; ``c_t = sum_{j=0..L-1} w_j * v_{t-(L-1)+j}``
(depthwise, causal, ``L = conv_L_cache``, zeros left of position 0,
``conv_bias`` false), computed as ``L`` shifted adds; ``Op = W_out (C_t *
c_t)``. ``Op`` of a ``full_attention`` layer: ``q = n W_q`` (heads x
``head_dim``), ``k = n W_k``, ``v = n W_v`` (kv heads x ``head_dim``);
each head of q and of k RMSNorm'd; rope at ``rope_theta`` over the two
halves of each head, angle ``p theta^(-2j/d)``; query head r reads kv head
``r // (heads / kv heads)``; scores over ``sqrt(head_dim)``; position p
sees ``j <= p``; softmax; ``W_o``. ``FFN`` of layer ``i <
num_dense_layers``: SwiGLU of ``intermediate_size``. Of the others: ``s =
sigmoid(n W_r)`` over all ``num_experts`` in float32; the
``num_experts_per_tok`` experts are the largest of ``s + b``
(``use_expert_bias``); their weights are ``s`` without ``b``, over their
sum + 1e-6 (``norm_topk_prob``), times ``routed_scaling_factor``; every
expert (SwiGLU of ``moe_intermediate_size``) applied to every token one at
a time with that weight as a mask. Final RMSNorm; the head is the
embedding. No cache, no state carried, no sort, no kernel.

Departures from the published code, each also under ``assumed`` in the
configuration file: the head tied to the embedding (the family's
convention; the catalog's row has no key for it); the expert bias a seeded
normal and not zeros; seeded weights; ``head_dim`` is ``hidden_size /
num_attention_heads`` (64; no key of its own).

Attention is computed a block of query rows at a time, experts upcast one
at a time, the head a block of the vocabulary's rows at a time.

It reads the program's parameter tree and nothing else of the program:
``embed_tokens``, per layer ``layers_<i>/{input_norm, post_attn_norm}``,
``conv/{in_proj, kernel, out_proj}`` or ``attn/{q_proj, k_proj, v_proj,
o_proj, q_norm, k_norm}``, and ``mlp/{gate,up,down}_proj`` (dense) or
``moe/{router, bias, wg, wi, wo}`` (experts stacked on the first axis),
``final_norm``.
"""

from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from perfbench import roofline
# Imported here, not where it is first used: a tree without the model
# fails when the family is loaded, before JAX has started a device.
from raytpu.models.mixtral import Lfm2MoeConfig, Mixtral, make_train_step

SERVE_MODEL = "lfm2_moe"
DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}
CONV, FULL = "conv", "full_attention"
# The most float32 score entries one block of query rows may hold.
SCORE_ENTRIES = 1 << 25


# ---- the program's side ----------


def layer_types(cfg: Mapping) -> Sequence[str]:
    """The kinds of the layers held: the published list's first
    ``num_hidden_layers`` entries (the file keeps the list whole)."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def head_dim(cfg: Mapping) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``Lfm2MoeConfig`` for a configuration file."""
    assert cfg["use_expert_bias"] and not cfg["conv_bias"] \
        and cfg["rope_parameters"]["rope_type"] == "default" \
        and set(cfg["layer_types"]) == {CONV, FULL}
    train = cfg.get("train", {})
    fields = dict(
        vocab_size=vocab_rows_held(cfg),
        block_size=cfg["max_position_embeddings"],
        n_layer=cfg["num_hidden_layers"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], n_embd=cfg["hidden_size"],
        head_dim=head_dim(cfg), n_inter=cfg["moe_intermediate_size"],
        n_expert=cfg["num_experts"],
        n_expert_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        topk_sum_eps=float(cfg["assumed"]["norm_topk_sum_eps"]),
        norm_eps=cfg["norm_eps"], layer_types=layer_types(cfg),
        conv_taps=cfg["conv_L_cache"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        choice_bias=float(cfg["assumed"]["expert_bias_std"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        first_dense=cfg["num_dense_layers"],
        dense_inter=cfg["intermediate_size"],
        tie_embeddings=bool(cfg["assumed"]["tie_word_embeddings"]),
        dtype=DTYPES[cfg["compute_dtype"]][0],
        param_dtype=DTYPES[cfg["param_dtype"]][0], scan_layers=False,
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return Lfm2MoeConfig(**fields)


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


def _conv_params(cfg: Mapping) -> int:
    """A conv operator: in (hidden to three times), out, and the taps."""
    e = cfg["hidden_size"]
    return 3 * e * e + e * e + cfg["conv_L_cache"] * e


def _attn_params(cfg: Mapping) -> int:
    """An attention operator: the four projections and two head norms."""
    e, h, kv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], head_dim(cfg))
    return e * (h + 2 * kv) * d + h * d * e + 2 * d


def _expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_params(cfg: Mapping, kind: str, dense: bool, experts: float
                  ) -> float:
    """A block with ``experts`` of its routed experts: the operator of its
    kind, the two block norms, and the dense SwiGLU or the router, its
    bias and the routed experts."""
    e = cfg["hidden_size"]
    outside = 2 * e + (_conv_params(cfg) if kind == CONV
                       else _attn_params(cfg))
    if dense:
        return outside + 3 * e * cfg["intermediate_size"]
    return (outside + e * cfg["num_experts"] + cfg["num_experts"]
            + experts * _expert_params(cfg))


def _params(cfg: Mapping, experts: float, kinds: Sequence[str]) -> float:
    """Embedding (the head is tied to it), final norm and the layers."""
    tied = 1 if cfg["assumed"]["tie_word_embeddings"] else 2
    return (tied * vocab_rows_held(cfg) * cfg["hidden_size"]
            + cfg["hidden_size"] + sum(
                _layer_params(cfg, kind, i < cfg["num_dense_layers"],
                              experts) for i, kind in enumerate(kinds)))


def param_count(cfg: Mapping) -> int:
    """Parameters as the program holds them."""
    return int(_params(cfg, cfg["num_experts"], layer_types(cfg)))


def active_param_count(cfg: Mapping) -> float:
    """Parameters one token uses: ``num_experts_per_tok`` experts a layer."""
    return _params(cfg, cfg["num_experts_per_tok"], layer_types(cfg))


def published_param_counts(cfg: Mapping):
    """``(all, a token's)`` of the model uncut: every entry of
    ``layer_types``."""
    return (_params(cfg, cfg["num_experts"], cfg["layer_types"]),
            _params(cfg, cfg["num_experts_per_tok"], cfg["layer_types"]))


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    return roofline.train_flops_per_token(
        active_param_count(cfg), layer_types(cfg).count(FULL),
        cfg["hidden_size"], seq_len)


def kv_shape(cfg: Mapping):
    """``(pools, kv_heads, head_dim, itemsize)``: a K and a V pool for
    every attention layer held; a conv layer has none."""
    return (layer_types(cfg).count(FULL), cfg["num_key_value_heads"],
            head_dim(cfg), DTYPES[cfg["compute_dtype"]][1])


def state_bytes_per_seq(cfg: Mapping) -> int:
    """Bytes one sequence holds in the conv layers' state arrays:
    ``conv_L_cache - 1`` rows of the hidden size a layer."""
    return (layer_types(cfg).count(CONV) * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * DTYPES[cfg["compute_dtype"]][1])


def routed_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def moe_shape(cfg: Mapping):
    """``(routed layers, experts, experts per token, hidden, one expert's
    width, bytes an element of an expert matrix as multiplied)``."""
    return (routed_layers(cfg), cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], DTYPES[cfg["compute_dtype"]][1])


def expert_ffn_flops(cfg: Mapping, assignments: int) -> float:
    """FLOPs of the routed expert matrices for ``assignments`` (token,
    expert) pairs: three products of hidden x width each."""
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


def _attend(q, k, v):
    """``q`` [B, H, T, D], ``k`` and ``v`` [B, KV, T, D], a block of query
    rows at a time: head r reads kv head ``r // (H / KV)``; row p sees
    keys ``j <= p``."""
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
        s = qi @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(j <= p, s, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(one, (jnp.arange(blocks), qb))
    return out.transpose(1, 2, 0, 3, 4).reshape(
        b, h, blocks * rows, d)[:, :, :t]


def _attention(cfg: Mapping, a, y):
    """Attention of the normed ``y`` [B, T, E]."""
    b, t, _ = y.shape
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                head_dim(cfg))

    def heads(name, n):
        x = y @ a[name]["kernel"].astype(jnp.float32)
        return x.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    q = _rope(cfg, _rms_norm(heads("q_proj", h), a["q_norm"],
                             cfg["norm_eps"]))
    k = _rope(cfg, _rms_norm(heads("k_proj", kv), a["k_norm"],
                             cfg["norm_eps"]))
    o = _attend(q, k, heads("v_proj", kv))
    return o.transpose(0, 2, 1, 3).reshape(b, t, h * d) \
        @ a["o_proj"]["kernel"].astype(jnp.float32)


def _short_conv(cfg: Mapping, p, y):
    """The gated short convolution of the normed ``y`` [B, T, E]: the
    depthwise causal convolution as ``conv_L_cache`` shifted adds."""
    gate_b, gate_c, u = jnp.split(
        y @ p["in_proj"]["kernel"].astype(jnp.float32), 3, axis=-1)
    v = gate_b * u
    taps = p["kernel"].astype(jnp.float32)        # [L, E], oldest first
    last = cfg["conv_L_cache"] - 1
    mixed = taps[last] * v
    for back in range(1, last + 1):               # v_{t - back}: zeros
        shifted = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :-back]
        mixed = mixed + taps[last - back] * shifted
    return (gate_c * mixed) @ p["out_proj"]["kernel"].astype(jnp.float32)


def _swiglu(p, y):
    p = {k: v["kernel"].astype(jnp.float32) for k, v in p.items()}
    return (jax.nn.silu(y @ p["gate_proj"]) * (y @ p["up_proj"])) \
        @ p["down_proj"]


def router_weights(cfg: Mapping, moe, y):
    """``y`` [..., E] float32 -> [..., experts]: each token's sigmoid
    score at the ``num_experts_per_tok`` experts whose score + bias is
    largest, over their sum + 1e-6, times the scaling factor; zero
    elsewhere."""
    s = jax.nn.sigmoid(y @ moe["router"]["kernel"].astype(jnp.float32))
    choice = s + moe["bias"].astype(jnp.float32)
    kth = jax.lax.top_k(choice, cfg["num_experts_per_tok"])[0][..., -1:]
    w = jnp.where(choice >= kth, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True)
                 + float(cfg["assumed"]["norm_topk_sum_eps"]))
    return w * float(cfg["routed_scaling_factor"])


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


def _block(cfg: Mapping, x, lp, kind: str, dense: bool):
    eps = cfg["norm_eps"]
    n = _rms_norm(x, lp["input_norm"], eps)
    x = x + (_short_conv(cfg, lp["conv"], n) if kind == CONV
             else _attention(cfg, lp["attn"], n))
    y = _rms_norm(x, lp["post_attn_norm"], eps)
    return x + (_swiglu(lp["mlp"], y) if dense
                else _experts(cfg, lp["moe"], y))


def _head(x, embedding):
    """``x @ embedding.T`` in float32, a block of the vocabulary's rows at
    a time, written where it belongs."""
    v = embedding.shape[0]
    blocks = next(n for n in (8, 4, 2, 1) if v % n == 0)
    width = v // blocks

    def one(i, out):
        w = jax.lax.dynamic_slice_in_dim(embedding, i * width, width, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ w.astype(jnp.float32).T, i * width, x.ndim - 1)

    return jax.lax.fori_loop(
        0, blocks, one, jnp.zeros(x.shape[:-1] + (v,), jnp.float32))


def logits(cfg: Mapping, params, tokens, rows=None):
    """``tokens`` [B, T] -> logits [B, T, vocabulary]; with ``rows`` (a
    list of positions) [B, len(rows), vocabulary], of those alone."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
        for i, kind in enumerate(layer_types(cfg)):
            x = _block(cfg, x, params[f"layers_{i}"], kind,
                       i < cfg["num_dense_layers"])
        x = _rms_norm(x, params["final_norm"], cfg["norm_eps"])
        if rows is not None:
            x = x[:, jnp.asarray(rows)]
        head = params["embed_tokens"]["embedding"] \
            if cfg["assumed"]["tie_word_embeddings"] \
            else params["lm_head"]["kernel"].T
        return _head(x, head)


def loss(cfg: Mapping, params, tokens):
    """Mean next-token cross-entropy, one sequence at a time. The
    router's load-balance term is the trainer's and is left out."""

    def one(seq):
        lg = logits(cfg, params, seq[None])[0, :-1]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        label = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
        return (lse - label).mean()

    return jax.lax.map(one, tokens).mean()
