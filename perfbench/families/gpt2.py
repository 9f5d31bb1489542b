"""The ``gpt2`` family: everything the benchmark knows about one model
family, found by the ``family`` a configuration file names.

A family file holds (``README.md`` lists the interface): how the
program's config object is made from a configuration file, the
program's model and train step, the model's counts (parameters, model
FLOPs, KV heads), and the family's plain reference. A later PR adds a
family by adding ``families/<family>.py``; no cell, reader or test of
the benchmark names a family.

The plain reference is the published forward pass and next-token loss in
straightforward ``jax.numpy`` and float32. No kernel, no cache, no
batching tricks: learned positions, pre-norm blocks of multi-head causal
attention and a ``gelu_new`` feed-forward, a final norm and the tied
embedding as output head ("Language Models are Unsupervised Multitask
Learners", Radford et al. 2019, and the ``transformers`` GPT-2
``config.json`` fields the configuration files copy). Layers run in a
``lax.scan`` over the stacked parameters, matrix products at
``jax.default_matmul_precision("highest")`` because a TPU otherwise
multiplies float32 in bf16 passes.

The reference reads the parameter tree the program's GPT-2 holds (flax
names: ``wte``, ``wpe``, ``h/{ln_1,attn/{c_attn,c_proj},ln_2,
mlp/{c_fc,c_proj}}`` with a leading layer axis, ``ln_f``) and nothing
else of the program. Departure from the program noted: the program's
layer norms use flax's default epsilon 1e-6, the published configuration
says 1e-5; the reference follows the configuration file. The difference
is 1e-5 of a unit variance and far inside the bf16 tolerance.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

from perfbench import roofline

SERVE_MODEL = "gpt2"  # what ``LLMDeployment(model=...)`` calls the family


# ---- the program's side ----------------------------------------------------


def program_config(cfg: Mapping, overrides: Mapping = ()):
    """The program's ``GPT2Config`` for a configuration file."""
    from raytpu.models.gpt2 import GPT2Config

    train = cfg.get("train", {})
    fields = dict(
        vocab_size=vocab_rows_held(cfg),
        block_size=cfg["n_positions"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], n_embd=cfg["n_embd"], dropout=0.0,
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[cfg["compute_dtype"]],
        remat=train.get("remat", True),
        loss_chunk=train.get("loss_chunk", 0))
    fields.update(dict(overrides))
    return GPT2Config(**fields)


def train_parts(pcfg):
    """``(init(key) -> params, make_step(optimizer) -> step)`` of the
    program's model, ``step(params, opt_state, tokens) -> (params,
    opt_state, loss)``."""
    from raytpu.models.gpt2 import GPT2, make_train_step

    model = GPT2(pcfg)

    def init(key):
        return model.init(
            key, jnp.zeros((1, pcfg.block_size), jnp.int32))["params"]

    return init, lambda optimizer: make_train_step(model, optimizer)


# ---- counts, from the configuration file ----------------------------------------


def vocab_rows_held(cfg: Mapping) -> int:
    """Rows of the output head: the published vocabulary padded to a
    multiple of 128 (``assumed.vocab_rows_held``)."""
    return int(cfg["assumed"]["vocab_rows_held"])


def param_count(cfg: Mapping) -> int:
    """Parameters as the program holds them: tied embedding with the rows
    held, learned positions, and per layer 12·E² weights plus 13·E biases
    and norm scales."""
    e, l_ = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * e
    per_layer = (e * 3 * e + 3 * e) + (e * e + e) \
        + (e * inner + inner) + (inner * e + e) + 4 * e
    return (vocab_rows_held(cfg) * e + cfg["n_positions"] * e
            + l_ * per_layer + 2 * e)


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    """Model FLOPs of one training token: every parameter is active."""
    return roofline.train_flops_per_token(
        param_count(cfg), cfg["n_layer"], cfg["n_embd"], seq_len)


def kv_shape(cfg: Mapping):
    """``(layers, heads whose K and V the cache holds, head size, bytes
    an element)`` of the KV pool: GPT-2 has no grouped queries, every
    head keeps its own; the pool is held in the compute type."""
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    return (cfg["n_layer"], cfg["n_head"], cfg["n_embd"] // cfg["n_head"],
            itemsize)


# ---- the plain reference -----------------------------------------------------------


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def hidden_states(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> final-norm hidden states [B, T, E], float32."""
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    b, t = tokens.shape
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = f32["wte"]["embedding"][tokens] + f32["wpe"]["embedding"][:t]
        causal = jnp.tril(jnp.ones((t, t), bool))

        def block(x, lp):
            h = _layer_norm(x, lp["ln_1"], eps)
            q, k, v = jnp.split(_dense(h, lp["attn"]["c_attn"]), 3, axis=-1)
            q, k, v = (a.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)
                       for a in (q, k, v))
            s = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(q.shape[-1])
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            y = (w @ v).transpose(0, 2, 1, 3).reshape(b, t, -1)
            x = x + _dense(y, lp["attn"]["c_proj"])
            h = _layer_norm(x, lp["ln_2"], eps)
            h = _gelu_new(_dense(h, lp["mlp"]["c_fc"]))
            return x + _dense(h, lp["mlp"]["c_proj"]), None

        x, _ = jax.lax.scan(block, x, f32["h"])
        return _layer_norm(x, f32["ln_f"], eps)


def logits(cfg: Mapping, params, tokens):
    """``tokens`` [B, T] -> logits [B, T, rows of the embedding]."""
    x = hidden_states(cfg, params, tokens)
    with jax.default_matmul_precision("highest"):
        return x @ params["wte"]["embedding"].astype(jnp.float32).T


def loss(cfg: Mapping, params, tokens):
    """Mean next-token cross-entropy over ``tokens`` [B, T], one sequence
    at a time so the [T, V] logits of a whole batch never exist at once."""
    emb = params["wte"]["embedding"].astype(jnp.float32)

    def one(seq):
        x = hidden_states(cfg, params, seq[None])[0, :-1]
        with jax.default_matmul_precision("highest"):
            lg = x @ emb.T
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        label = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
        return (lse - label).mean()

    return jax.lax.map(one, tokens).mean()
