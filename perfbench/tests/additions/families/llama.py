"""A second family, as a later ``model_config`` PR would add one: this
file, a configuration that names it, nothing else. It lives with the
tests (``test_rehearsal.py`` runs a train cell and a served cell of a
tiny Llama through it) and is no part of the benchmark; a real family
file goes to ``perfbench/families/``.

Program side: ``raytpu/models/llama.py`` (grouped-query attention, rotary
positions, RMS norms, a SwiGLU feed-forward, an untied output head).
The reference below is the published forward pass ("LLaMA: Open and
Efficient Foundation Language Models", Touvron et al. 2023; field names
of the ``transformers`` ``config.json``) in plain float32 ``jax.numpy``:
no kernel, no cache. It reads the program's parameter tree
(``embed_tokens``, ``layers/{input_norm,attn/{q,k,v,o}_proj,
post_attn_norm,mlp/{gate,up,down}_proj}`` stacked, ``final_norm``,
``lm_head``).
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

from perfbench import roofline

SERVE_MODEL = "llama"
DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}


def program_config(cfg: Mapping, overrides: Mapping = ()):
    from raytpu.models.llama import LlamaConfig

    train = cfg.get("train", {})
    fields = dict(
        vocab_size=cfg["vocab_size"],
        block_size=cfg["max_position_embeddings"],
        n_layer=cfg["num_hidden_layers"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], n_embd=cfg["hidden_size"],
        n_inter=cfg["intermediate_size"], rope_theta=cfg["rope_theta"],
        dtype=DTYPES[cfg["compute_dtype"]][0],
        remat=train.get("remat", "dots"),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return LlamaConfig(**fields)


def train_parts(pcfg):
    from raytpu.models.llama import Llama, make_train_step

    model = Llama(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, pcfg.block_size), jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


def vocab_rows_held(cfg: Mapping) -> int:
    return int(cfg["vocab_size"])


def _shape(cfg):
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return e, h, cfg["num_key_value_heads"], e // h


def param_count(cfg: Mapping) -> int:
    e, h, kv, d = _shape(cfg)
    per_layer = e * (h + 2 * kv) * d + h * d * e \
        + 3 * e * cfg["intermediate_size"] + 2 * e
    return 2 * cfg["vocab_size"] * e \
        + cfg["num_hidden_layers"] * per_layer + e


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    return roofline.train_flops_per_token(
        param_count(cfg), cfg["num_hidden_layers"], cfg["hidden_size"],
        seq_len)


def kv_shape(cfg: Mapping):
    _, _, kv, d = _shape(cfg)
    return (cfg["num_hidden_layers"], kv, d,
            DTYPES[cfg["compute_dtype"]][1])


# ---- the plain reference --------------------------------------------------------


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, theta):
    """``x`` [B, H, T, D]: rotate the two halves of each head."""
    t, d = x.shape[-2], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_states(cfg: Mapping, params, tokens):
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    b, t = tokens.shape
    _, h, kv, d = _shape(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    with jax.default_matmul_precision("highest"):
        x = f32["embed_tokens"]["embedding"][tokens]
        causal = jnp.tril(jnp.ones((t, t), bool))

        def heads(y, n):
            return y.reshape(b, t, n, d).transpose(0, 2, 1, 3)

        def block(x, lp):
            a = lp["attn"]
            y = _rms_norm(x, lp["input_norm"], eps)
            q = _rope(heads(y @ a["q_proj"]["kernel"], h), theta)
            k = _rope(heads(y @ a["k_proj"]["kernel"], kv), theta)
            v = heads(y @ a["v_proj"]["kernel"], kv)
            k, v = (jnp.repeat(z, h // kv, axis=1) for z in (k, v))
            s = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(d)
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            y = (w @ v).transpose(0, 2, 1, 3).reshape(b, t, h * d)
            x = x + y @ a["o_proj"]["kernel"]
            m = lp["mlp"]
            y = _rms_norm(x, lp["post_attn_norm"], eps)
            y = jax.nn.silu(y @ m["gate_proj"]["kernel"]) \
                * (y @ m["up_proj"]["kernel"])
            return x + y @ m["down_proj"]["kernel"], None

        x, _ = jax.lax.scan(block, x, f32["layers"])
        return _rms_norm(x, f32["final_norm"], eps)


def logits(cfg: Mapping, params, tokens):
    x = hidden_states(cfg, params, tokens)
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"]["kernel"].astype(jnp.float32)


def loss(cfg: Mapping, params, tokens):
    lg = logits(cfg, params, tokens)[:, :-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    label = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return (lse - label).mean()
