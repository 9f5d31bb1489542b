"""The ``mellum`` family: Mellum2-12B-A2.5B, a decoder whose attention
layers are of two kinds (three window layers of 1,024 positions to every
full one, a rotary embedding a kind, YaRN on the full layers) and whose
feed-forward is, in every layer, a routed-expert layer. Same interface as
``gpt2.py`` and ``olmoe.py``, plus one optional function of its own.

**``paged_attn_bytes_by_kind``** (optional; a family whose layers are all
alike leaves it out): the pool bytes the paged-attention kernel must read
in one decode step, counted by kind of layer. The benchmark's older
reader ``paged_attn_roofline`` reckons ``layers x every live page of the
whole context``; a window layer reads its window's pages only (9 of a
sequence's 180-350 pages of 128 tokens in this family's cell), so that
reader would count several times the bytes really moved and read far
over 100 %. A cell of this family therefore stays out of that metric's
``workloads`` and reports ``paged_attn_kinds_roofline``, whose reader
takes the page counts a kind from the program's step records
(``live_pages_full``, ``live_pages_window``) and the bytes from here.

Program side: ``raytpu/models/mixtral.py`` (``MellumConfig``; ``Mixtral``
is its training forward, ``MoEFFN`` its routed layer), served by the
llama family's three walks over two kinds of KV pool.

The plain reference below is written from the layer equations of the
published ``config.json`` (``transformers`` conventions for its keys) in
straightforward ``jax.numpy`` and float32, matrix products at
``jax.default_matmul_precision("highest")``. For layer ``i`` of kind
``layer_types[i]``: RMSNorm; q (hidden -> heads x head_dim, where
head_dim is a key of its own and not hidden / heads), k and v (hidden ->
kv heads x head_dim), no bias; rotary positions on the two halves of each
head of q and k, with the inverse frequencies of the layer's kind (plain
``theta^(-2i/d)`` on window layers; on full layers YaRN's blend of the
plain frequency and the plain frequency over ``factor`` by a linear ramp
between the pairs that turn ``beta_fast`` and ``beta_slow`` times in
``original_max_position_embeddings``, cos and sin both scaled by
``attention_factor``); query head ``r`` attends kv head ``r //
(heads / kv heads)``; scores over ``sqrt(head_dim)``; position ``p`` sees
keys ``j <= p`` on a full layer and ``p - sliding_window < j <= p`` on a
window layer; softmax; output projection; residual. Then RMSNorm, a
float32 router over all experts, softmax, the ``num_experts_per_tok``
largest kept by a mask and divided by their sum (``norm_topk_prob``),
every expert (SwiGLU of width ``moe_intermediate_size``) applied to every
token one expert at a time, residual. A final norm and an untied head.
``intermediate_size`` is no layer's width (``mlp_layer_types`` is
``sparse`` throughout). No sort, no grouping, no cache, no kernel.

Attention is computed a block of query rows at a time (the scores of a
block are ``[heads, rows, T]``), so that a context of 40,000 positions
fits beside the weights, and ``logits(..., rows=...)`` gives the logits
of chosen positions alone: every position still goes through every
layer (a later one's keys depend on it), only the head is restricted.

It reads the program's parameter tree and nothing else of the program:
``embed_tokens``, per layer ``layers_<i>/{input_norm, attn/{q,k,v,o}_proj,
post_attn_norm, moe/{router,wg,wi,wo}}`` (experts stacked on the first
axis), ``final_norm``, ``lm_head``. Weights are upcast where they are
used, one expert at a time.
"""

from __future__ import annotations

import math
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import roofline
# Imported here, not where it is first used: a tree without the model
# fails when the family is loaded, before JAX has started a device.
from raytpu.models.mixtral import MellumConfig, Mixtral, make_train_step

SERVE_MODEL = "mellum"
DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}
WINDOW, FULL = "sliding_attention", "full_attention"
# The most float32 score entries one block of query rows may hold.
SCORE_ENTRIES = 1 << 25


# ---- the program's side ----------------------------------------------------


def layer_types(cfg: Mapping) -> Sequence[str]:
    """The kinds of the layers held: the published list's first
    ``num_hidden_layers`` entries (the file keeps the list whole)."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``MellumConfig`` for a configuration file."""
    from raytpu.models.llama import Rope

    def rope(kind):
        r = cfg["rope_parameters"][kind]
        if r["rope_type"] == "default":
            return Rope(theta=float(r["rope_theta"]))
        assert r["rope_type"] == "yarn", r
        return Rope(theta=float(r["rope_theta"]),
                    yarn_factor=float(r["factor"]),
                    original_max_position=int(
                        r["original_max_position_embeddings"]),
                    beta_fast=float(r["beta_fast"]),
                    beta_slow=float(r["beta_slow"]),
                    attention_factor=r.get("attention_factor"))

    assert set(cfg["mlp_layer_types"]) == {"sparse"} \
        and cfg["use_sliding_window"] and not cfg["attention_bias"] \
        and not cfg["tie_word_embeddings"] and cfg["hidden_act"] == "silu"
    train = cfg.get("train", {})
    fields = dict(
        vocab_size=vocab_rows_held(cfg),
        block_size=cfg["max_position_embeddings"],
        n_layer=cfg["num_hidden_layers"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], n_embd=cfg["hidden_size"],
        head_dim=cfg["head_dim"], n_inter=cfg["moe_intermediate_size"],
        n_expert=cfg["num_experts"],
        n_expert_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], norm_eps=cfg["rms_norm_eps"],
        layer_types=layer_types(cfg), window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_parameters"][WINDOW]["rope_theta"]),
        full_rope=rope(FULL), window_rope=rope(WINDOW),
        dtype=DTYPES[cfg["compute_dtype"]][0],
        param_dtype=DTYPES[cfg["param_dtype"]][0], scan_layers=False,
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return MellumConfig(**fields)


def train_parts(pcfg):
    model = Mixtral(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, min(pcfg.block_size, 128)),
                           jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


# ---- counts, from the configuration file ----------------------------------------


def vocab_rows_held(cfg: Mapping) -> int:
    """98304 is a multiple of 128 already: every row is a published one."""
    return int(cfg["vocab_size"])


def _widths(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _layer_params(cfg: Mapping, experts: int) -> int:
    """One layer with ``experts`` of its experts: the four attention
    projections, the two block norms, the router and three matrices an
    expert."""
    e, h, kv, d = _widths(cfg)
    return (e * (h + 2 * kv) * d + h * d * e + 2 * e
            + e * cfg["num_experts"]
            + experts * 3 * e * cfg["moe_intermediate_size"])


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


def layers_by_kind(cfg: Mapping):
    """``(full layers, window layers)`` of the layers held."""
    kinds = layer_types(cfg)
    return kinds.count(FULL), kinds.count(WINDOW)


def paged_attn_bytes_by_kind(cfg: Mapping, page_size: int,
                             live_pages_full: int,
                             live_pages_window: int) -> float:
    """Pool bytes the paged-attention kernel must read when the decode
    steps counted read ``live_pages_full`` pages in one full layer and
    ``live_pages_window`` in one window layer (the step records' sums):
    the K and the V rows of those pages, in every layer of the kind."""
    _, kv, d, itemsize = kv_shape(cfg)
    full, window = layers_by_kind(cfg)
    return (full * roofline.paged_attn_bytes(
        live_pages_full, page_size, kv, d, itemsize)
        + window * roofline.paged_attn_bytes(
            live_pages_window, page_size, kv, d, itemsize))


def moe_shape(cfg: Mapping):
    """``(layers, experts, experts per token, hidden, one expert's width,
    bytes an element of an expert matrix as multiplied)``."""
    return (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], DTYPES[cfg["compute_dtype"]][1])


def expert_ffn_flops(cfg: Mapping, assignments: int) -> float:
    """FLOPs of the expert matrices for ``assignments`` (token, expert)
    pairs: three products of hidden x width each, two a multiply-add."""
    _, _, _, hidden, width, _ = moe_shape(cfg)
    return assignments * 3 * 2.0 * hidden * width


def expert_ffn_bytes(cfg: Mapping, experts_touched: int) -> float:
    """Weight bytes the expert layer must read when ``experts_touched``
    (expert, layer) pairs received a token: three matrices each, once."""
    _, _, _, hidden, width, itemsize = moe_shape(cfg)
    return experts_touched * 3.0 * hidden * width * itemsize


# ---- the plain reference -----------------------------------------------------------


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def inv_frequencies(cfg: Mapping, kind: str) -> np.ndarray:
    """The ``head_dim / 2`` inverse frequencies of a layer kind's rotary
    embedding and the factor its cos and sin are scaled by."""
    r, d = cfg["rope_parameters"][kind], cfg["head_dim"]
    theta = float(r["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if r["rope_type"] == "default":
        return plain, 1.0
    factor, orig = float(r["factor"]), r["original_max_position_embeddings"]

    def dim(turns):
        return d * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(dim(r["beta_fast"])), 0), d - 1)
    high = min(max(math.ceil(dim(r["beta_slow"])), 0), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0, 1)
    scale = r.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return (plain / factor) * ramp + plain * (1.0 - ramp), float(scale)


def _rope(cfg, kind, x):
    """``x`` [B, H, T, D] at positions 0..T-1."""
    freqs, scale = inv_frequencies(cfg, kind)
    t, d = x.shape[-2], x.shape[-1]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window: Optional[int]):
    """``q`` [B, H, T, D] against ``k``, ``v`` [B, H, T, D], a block of
    query rows at a time: row p sees keys ``j <= p``, and with a
    ``window`` only those with ``j > p - window``."""
    b, h, t, d = q.shape
    rows = 1 << max(3, int(math.log2(max(8, SCORE_ENTRIES // (h * t)))))
    rows = min(rows, 1 << (t - 1).bit_length())
    blocks = -(-t // rows)
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, blocks * rows - t), (0, 0)))
    qb = qb.reshape(b, h, blocks, rows, d).transpose(2, 0, 1, 3, 4)
    j = jnp.arange(t)

    def one(args):
        i, qi = args
        # (The last block's padding rows stand at the last position: a
        # row that sees no key would be a softmax over nothing.)
        p = jnp.minimum(i * rows + jnp.arange(rows), t - 1)[:, None]
        seen = j <= p
        if window is not None:
            seen &= j > p - window
        s = qi @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(one, (jnp.arange(blocks), qb))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, blocks * rows, d)[
        :, :, :t]


def router_weights(cfg: Mapping, moe, y):
    """``y`` [..., E] float32 -> [..., experts]: each token's softmax
    score at its ``num_experts_per_tok`` largest experts over their sum
    (``norm_topk_prob``), zero elsewhere."""
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


def _block(cfg: Mapping, kind: str, x, lp):
    b, t, _ = x.shape
    _, h, kv, d = _widths(cfg)
    eps = cfg["rms_norm_eps"]

    def heads(z, n):
        return z.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    a = _f32(lp["attn"])
    y = _rms_norm(x, lp["input_norm"], eps)
    q = _rope(cfg, kind, heads(y @ a["q_proj"]["kernel"], h))
    k = _rope(cfg, kind, heads(y @ a["k_proj"]["kernel"], kv))
    v = heads(y @ a["v_proj"]["kernel"], kv)
    k, v = (jnp.repeat(z, h // kv, axis=1) for z in (k, v))
    y = _attend(q, k, v, cfg["sliding_window"] if kind == WINDOW else None)
    y = y.transpose(0, 2, 1, 3).reshape(b, t, h * d)
    x = x + y @ a["o_proj"]["kernel"]
    return x + _experts(cfg, lp["moe"],
                        _rms_norm(x, lp["post_attn_norm"], eps))


def hidden_states(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> final-norm hidden states [B, T, E], float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
        for i, kind in enumerate(layer_types(cfg)):
            x = _block(cfg, kind, x, params[f"layers_{i}"])
        return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


def logits(cfg: Mapping, params, tokens, rows=None):
    """``tokens`` [B, T] -> logits [B, T, vocabulary]; with ``rows`` (a
    list of positions) [B, len(rows), vocabulary], of those alone."""
    x = hidden_states(cfg, params, tokens)
    if rows is not None:
        x = x[:, jnp.asarray(rows)]
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
