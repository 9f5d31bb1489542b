"""GPT-2 in Flax — the flagship training model.

The reference's GPT-2 benchmark path is torch + DDP/DeepSpeed driven by
Ray Train (``BASELINE.json`` north star; examples under
``doc/source/train/examples/deepspeed/``). This is the TPU-first redesign:
bf16 params/activations with fp32 loss/optimizer math, flash attention
(:mod:`raytpu.ops.flash_attention`), `jax.checkpoint` rematerialization per
block (``remat=True`` keeps the flash kernel's residuals across the
boundary and recomputes the rest: :func:`remat_block`), `lax.scan` over
layers (one compiled block body instead of n_layer
unrolled copies → fast compiles, same XLA code), and parameter names chosen
to match ``TRANSFORMer_RULES`` (c_attn/c_proj/c_fc → TP column/row splits).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # padded to a multiple of 128 for the MXU
    block_size: int = 1024
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    # Rematerialization policy per block (memory <-> recompute-FLOPs knob):
    #   False/"none": save all activations (fastest when HBM allows)
    #   True/"full":  save the flash kernel's residuals (remat_block: q, k,
    #                 v, o, lse) and nothing else: the backward of a block
    #                 recomputes its norms, output projection, c_fc and gelu
    #                 (~+1/5 FLOPs) but neither the kernel nor the qkv
    #                 projection and transposes that fed it
    #   "dots":       save matmul outputs only, recompute elementwise/norm/
    #                 attention-score work (few % extra FLOPs; the v5e sweet
    #                 spot — batch 16 no-remat OOMs 16.9G/15.75G HBM because
    #                 lax.scan stacks every layer's activations)
    remat: Any = True
    scan_layers: bool = True
    attn_impl: Optional[str] = None  # None=auto, "reference", "interpret", "tpu"
    # Paged-attention impl of ``step`` against the KV page pool: None is
    # the kernel on a TPU and the reference elsewhere; "kernel"/
    # "interpret"/"reference" pin it (see raytpu.ops.paged_attention).
    paged_attn: Optional[str] = None
    # Cross-entropy chunking: 0 = one [B,T,V] fp32 logits buffer (1.6 GB at
    # batch 8 / 50k vocab); N>0 = flash-xent style, logits computed N rows at
    # a time and recomputed in backward, so peak HBM holds one chunk.
    loss_chunk: int = 0

    @classmethod
    def small(cls) -> "GPT2Config":  # 124M
        return cls()

    @classmethod
    def tiny(cls) -> "GPT2Config":
        return cls(vocab_size=512, block_size=128, n_layer=2, n_head=2,
                   n_embd=128)

    @property
    def serving(self) -> "Serving":
        """How ``InferenceEngine`` serves this family."""
        return Serving(gpt2_prefill, gpt2_step, serving_params,
                       kv_heads=self.n_head,
                       head_dim=self.n_embd // self.n_head)

    @property
    def n_params_approx(self) -> int:
        c = self
        per_block = 12 * c.n_embd * c.n_embd
        return c.vocab_size * c.n_embd + c.block_size * c.n_embd + \
            c.n_layer * per_block + 2 * c.n_embd


def remat_block(block, remat):
    """``block`` under the config field ``remat``, for every family:
    ``False``/``"none"`` is the block itself; ``True``/``"full"`` saves
    the flash kernel's residuals (q, k, v, o and the log-sum-exp:
    ``flash_attention.RESIDUAL_NAMES``) and recomputes the rest, so the
    backward of a layer re-runs the norms, the output projection and
    the feed-forward but neither the kernel nor what fed it;
    ``"dots"`` saves the matmul outputs."""
    if not remat or remat == "none":
        return block
    if remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        from raytpu.ops.flash_attention import RESIDUAL_NAMES

        policy = jax.checkpoint_policies.save_only_these_names(
            *RESIDUAL_NAMES)
    return nn.remat(block, prevent_cse=False, policy=policy)


class CausalSelfAttention(nn.Module):
    """MHA with training (``__call__``), cache-emitting ``prefill``, and
    paged ``step`` entry points — setup()-style so all three share the
    c_attn/c_proj params (attribute names keep the param tree identical
    to the old compact version). No rope: GPT-2's positions live in
    ``wpe``, so a step just embeds at the absolute positions and
    attends; KV heads == query heads."""

    config: GPT2Config

    def setup(self):
        c = self.config
        self.c_attn = nn.Dense(3 * c.n_embd, dtype=c.dtype)
        self.c_proj = nn.Dense(c.n_embd, dtype=c.dtype)
        if c.dropout > 0:
            self.drop = nn.Dropout(c.dropout)

    def __call__(self, x, deterministic: bool = True):
        y, _, _ = self.prefill(x)
        if self.config.dropout > 0:
            y = self.drop(y, deterministic=deterministic)
        return y

    def prefill(self, x):
        """[B, T, E] -> (out, k [B, T, H, D], v [B, T, H, D]); k/v are
        the cache-resident halves for positions 0..T-1 (no dropout —
        inference path)."""
        c = self.config
        b, t, e = x.shape
        h = c.n_head
        qkv = self.c_attn(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        k_cache = k.reshape(b, t, h, e // h)
        v_cache = v.reshape(b, t, h, e // h)
        q = q.reshape(b, t, h, e // h).transpose(0, 2, 1, 3)
        k = k_cache.transpose(0, 2, 1, 3)
        v = v_cache.transpose(0, 2, 1, 3)
        from raytpu.ops.flash_attention import flash_attention

        y = flash_attention(q, k, v, causal=True, force=c.attn_impl)
        y = y.transpose(0, 2, 1, 3).reshape(b, t, e)
        return self.c_proj(y), k_cache, v_cache

    def step(self, x, k_pages, v_pages, dests, block_tables, positions):
        """``x`` [B * T, E] at ``[B, T]`` positions against the paged
        cache; same contract as
        :meth:`raytpu.models.llama.LlamaAttention.step` minus rope
        (``positions`` here only drive the causal mask — the wpe lookup
        upstream already positioned the embeddings)."""
        c = self.config
        b, t = positions.shape
        h, e = c.n_head, x.shape[-1]
        q, k, v = jnp.split(self.c_attn(x), 3, axis=-1)
        from raytpu.ops.paged_attention import (paged_attention,
                                                scatter_kv_slots)

        # A pool row is a token's K (or V) as c_attn wrote it: [B * T, E].
        k_pages = scatter_kv_slots(k_pages, dests.reshape(b * t), k)
        v_pages = scatter_kv_slots(v_pages, dests.reshape(b * t), v)
        o = paged_attention(q.reshape(b, t, h, e // h), k_pages, v_pages,
                            block_tables, positions, force=c.paged_attn)
        return self.c_proj(o.reshape(b * t, e)), k_pages, v_pages


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        c = self.config
        x = nn.Dense(4 * c.n_embd, dtype=c.dtype, name="c_fc")(x)
        x = nn.gelu(x, approximate=True)
        x = nn.Dense(c.n_embd, dtype=c.dtype, name="c_proj")(x)
        if c.dropout > 0:
            x = nn.Dropout(c.dropout)(x, deterministic=deterministic)
        return x


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        c = self.config
        x = x + CausalSelfAttention(c, name="attn")(
            nn.LayerNorm(dtype=c.dtype, name="ln_1")(x), deterministic)
        x = x + MLP(c, name="mlp")(
            nn.LayerNorm(dtype=c.dtype, name="ln_2")(x), deterministic)
        return x


class GPT2(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 return_hidden: bool = False):
        c = self.config
        b, t = tokens.shape
        pos = jnp.arange(t)[None]
        x = nn.Embed(c.vocab_size, c.n_embd, dtype=c.dtype, name="wte")(tokens)
        x = x + nn.Embed(c.block_size, c.n_embd, dtype=c.dtype,
                         name="wpe")(pos)

        block = remat_block(Block, c.remat)
        if c.scan_layers:
            x, _ = nn.scan(
                lambda mdl, carry, _: (mdl(carry, deterministic), None),
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=c.n_layer,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block(c, name="h"), x, None)
        else:
            for i in range(c.n_layer):
                x = block(c, name=f"h_{i}")(x, deterministic)

        x = nn.LayerNorm(dtype=c.dtype, name="ln_f")(x)
        if return_hidden:
            return x
        # Weight-tied LM head. The matmul runs in the model compute dtype
        # (bf16 → MXU speed; ~27% of total model FLOPs live here) with fp32
        # accumulation, so the softmax downstream still sees fp32 logits.
        wte = self.variables["params"]["wte"]["embedding"].astype(c.dtype)
        logits = jax.lax.dot_general(
            x, wte, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits


def gpt2_loss_fn(model: GPT2, params, tokens):
    """Next-token cross-entropy; fp32 loss math.

    logsumexp form — never materializes the full [B, T, V] log-softmax
    (1.6 GB fp32 at the bench shape), only the logits the head already
    produced plus two [B, T] reductions. With ``config.loss_chunk > 0`` even
    the logits are never fully materialized: the weight-tied head runs
    chunk-by-chunk under `jax.checkpoint` (flash-xent), trading one extra
    head matmul in backward (~9% model FLOPs) for the whole logits buffer.
    """
    c = model.config
    targets = tokens[:, 1:]
    if c.loss_chunk:
        x = model.apply({"params": params}, tokens, return_hidden=True)
        return _chunked_xent(x[:, :-1], targets,
                             params["wte"]["embedding"], c)
    logits = model.apply({"params": params}, tokens)
    logits = logits[:, :-1]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return (lse - label_logits).mean()


def _chunked_xent(x, targets, wte, c: GPT2Config):
    """Mean next-token NLL with the LM head computed ``loss_chunk`` rows at
    a time; `jax.checkpoint` makes backward recompute each chunk's logits so
    peak HBM holds one [chunk, V] fp32 buffer instead of [B, T, V]."""
    b, t, e = x.shape
    n = b * t
    chunk = min(c.loss_chunk, n)
    xf = x.reshape(n, e)
    tf = targets.reshape(n)
    pad = (-n) % chunk
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        tf = jnp.pad(tf, (0, pad))
    mask = (jnp.arange(n + pad) < n).astype(jnp.float32)
    xs = xf.reshape(-1, chunk, e)
    ts = tf.reshape(-1, chunk)
    ms = mask.reshape(-1, chunk)
    w = wte.astype(c.dtype)

    @jax.checkpoint
    def chunk_nll(xc, tc, mc):
        logits = jax.lax.dot_general(
            xc, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        label = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return ((lse - label) * mc).sum()

    def body(acc, xtm):
        return acc + chunk_nll(*xtm), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts, ms))
    return total / n


def make_train_step(model: GPT2, optimizer):
    """(params, opt_state, tokens) -> (params, opt_state, loss); pure — jit
    it with shardings from :func:`raytpu.parallel.sharding.tree_shardings`."""

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: gpt2_loss_fn(model, p, tokens))(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates)
        return params, opt_state, loss

    return train_step


def init_params(model: GPT2, config: GPT2Config, seed: int = 0,
                batch: int = 2):
    tokens = jnp.zeros((batch, config.block_size), jnp.int32)
    return model.init(jax.random.PRNGKey(seed), tokens)["params"]


# ---------------------------------------------------------------------------
# Inference forward paths (used by raytpu.inference.engine) — pure
# functions over the trained param tree, layers looped in Python (the
# engine jits the whole step; see raytpu.models.llama for the pattern).
# ---------------------------------------------------------------------------

def layer_params(params, i: int):
    """Layer ``i`` params from either layout: scanned (stacked under
    "h" with a leading layer axis) or unrolled ("h_{i}")."""
    if "h" in params:
        return jax.tree_util.tree_map(lambda p: p[i], params["h"])
    return params[f"h_{i}"]


def cast_leaves(params, dtype, cast):
    """``params`` with every leaf whose path ``cast`` accepts (it is given
    the path's dict keys, outermost first) held in ``dtype``. A leaf
    already in ``dtype`` is passed through as the same array, so a tree
    that needs nothing costs nothing. The rest are converted one leaf at
    a time where they live; a shape standing in for an array (a program
    that is only compiled) has its dtype changed."""
    dtype = jnp.dtype(dtype)

    def one(path, leaf):
        if leaf.dtype == dtype or not cast([k.key for k in path]):
            return leaf
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(leaf.shape, dtype,
                                        sharding=leaf.sharding)
        return leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(one, params)


# The modules whose leaves the forwards below use in ``config.dtype``:
# the four ``nn.Dense`` (flax's ``promote_dtype`` casts kernel and bias)
# and the two embeddings.
_SERVED_IN_COMPUTE_DTYPE = ("c_attn", "c_proj", "c_fc", "wte", "wpe")


LANES = 128  # a TPU tile's minor dimension


def lookup_table(table):
    """``table`` [rows, width] with its rows zero-padded to a whole
    number of lane tiles, for a program that gathers rows from it: the
    device keeps such an array row-major. ``None`` where the width is
    whole already (the table serves as it is). A shape standing in for
    an array gives a shape."""
    rows, width = table.shape
    pad = -width % LANES
    if not pad:
        return None
    if isinstance(table, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct((rows, width + pad), table.dtype,
                                    sharding=table.sharding)
    return jnp.pad(table, ((0, 0), (0, pad)))


def serving_params(config: GPT2Config, params):
    """The working copy of ``params`` to serve from: every leaf that
    :func:`gpt2_prefill` and :func:`gpt2_step` cast to
    ``config.dtype`` is in it already, so
    their casts are no-ops and no step converts a weight; the values are
    the ones they would have computed, so the logits are the same bits.
    The layer norms' ``scale`` and ``bias`` stay as given:
    ``nn.LayerNorm(dtype=...)`` normalises, scales and shifts in float32
    and casts the result, so rounding them first would change it. Make
    it once (``InferenceEngine`` does), not per call.

    One leaf the given tree has not: where ``n_embd`` is not a whole
    number of 128-lane tiles (GPT-2 XL's 1,600 is 12.5), ``wte`` and
    ``wpe`` each gain a ``lookup`` beside their ``embedding``
    (:func:`lookup_table`), the same rows zero-padded to whole tiles,
    which the forwards gather their token and position rows from. The
    device holds an ``embedding`` of such a width column-major, as the
    tied head's product reads it, and a program that gathered rows from
    it re-laid the whole table first, in every call (PERF.md section 6,
    PR 56). At a whole width the tree has no such leaf and the
    forwards gather from ``embedding``."""
    working = cast_leaves(params, config.dtype,
                          lambda keys: keys[-2] in _SERVED_IN_COMPUTE_DTYPE)
    for name in ("wte", "wpe"):
        lookup = lookup_table(working[name]["embedding"])
        if lookup is not None:
            working = {**working, name: {**working[name], "lookup": lookup}}
    return working


def _embedded(c: GPT2Config, table, ids):
    """Rows ``ids`` of an embedding module's ``table`` in ``c.dtype``:
    from its ``lookup`` leaf where the working copy holds one
    (:func:`serving_params`), else from ``embedding``."""
    if "lookup" in table:
        return table["lookup"][ids][..., :c.n_embd]
    return table["embedding"].astype(c.dtype)[ids]


def _tied_logits(c: GPT2Config, params, x):
    wte = params["wte"]["embedding"].astype(c.dtype)
    contract = ((x.ndim - 1,), (1,))
    return jax.lax.dot_general(x, wte, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class Serving:
    """How a family is served: what ``InferenceEngine`` asks of a config
    (its ``serving`` attribute) so that it need know no family. The two
    entry points have one contract, ``fn(config, params, *inputs,
    k_caches, v_caches)`` -> fp32 logits, then the two lists of pools
    (``[num_pages, page_size, kv_heads * head_dim]`` a layer) with the
    call's rows written, then, where ``expert_counts`` is set, the int32
    tokens each expert of each layer received. What a pool row holds is
    known to the family and to ``raytpu.ops.paged_attention`` alone.
    ``params`` here is the working copy ``params(config, given)`` made:
    the given tree's leaves as the forwards use them, and it may hold a
    leaf the given tree has not (GPT-2's ``lookup`` tables, where the
    hidden width is not whole lane tiles: :func:`serving_params`). The
    forwards take the tree as given too, from any other caller.

    ``prefill(config, params, tokens [1, T], dests [T] (a kind),
    k_caches, v_caches[, states, seats])`` -> logits [T, V]: a whole
    prompt from position 0 through flash attention, no pool read.
    ``step(config, params, tokens [B, T], positions [B, T], dests [B, T]
    (a kind), block_tables [B, P] (a kind), k_caches, v_caches[, states,
    seats])`` -> logits [B, T, V]: ``T`` consecutive positions a sequence
    against the pools, every row written before any attends. The engine
    builds its chunk program from it at ``[1, T]`` and its decode
    program at ``[B, 1]`` (``[B, 2]`` where the model drafts for itself).

    The cache's shape by kind of layer: ``kv_heads`` and ``head_dim`` are
    every pool's row, and ``layer_windows`` (empty: every layer full)
    gives each layer ``None``, a full layer whose pool grows with the
    sequence, or its window in tokens, a window layer whose pool keeps a
    sequence's newest positions only. Over two kinds the engine gives
    ``dests`` and ``block_tables`` as a pair of arrays, full first.

    A layer of one pool (``kv_row``): a latent-attention layer holds one
    row a token, ``kv_row`` features wide as held, from which keys and
    values are both read, and no V pool. The programs then take and give
    back ``k_caches`` one pool a layer and ``v_caches`` empty.
    ``kv_heads`` and ``head_dim`` are then the query's, and size no pool.
    Such a layer may attend a chunk in another algebraic form than a
    decode row (``chunk_parts(T, start, page_size)``: ``None`` where the
    chunk program's ``T`` rows from ``start`` on attend as a decode row
    does, else ``(rows of a segment, parts)``, the parts the cached rows
    and the chunk's own are expanded and attended in); the engine asks
    it only to say so in the step's record.

    A latent layer with an indexer (``indexer``: ``(index_row,
    index_topk)``) holds a second pool under the same block tables and
    ``dests``: one index key a token, ``index_row`` features wide, from
    which a query's indexer chooses the ``index_topk`` cached positions
    it attends. The programs then take and give back the latent pools as
    ``k_caches`` and the index-key pools as ``v_caches``, one of each a
    layer, of unequal row width. A page is a page of both, so whatever
    deals in pages (the prefix cache, preemption) deals in both at once.

    A layer that keeps a state (``layer_states``: one entry a layer of
    the model, ``None`` for a layer with a pool, else what a sequence
    keeps there: one shape in the cache's dtype, a short convolution's
    ``(taps - 1, width)``, or a tuple of :class:`State`, an array each,
    where a layer keeps more than one thing or another type than the
    cache's: a KDA layer's float32 matrices and its convolutions' tails;
    :func:`state_specs` reads either): it has no pool, ``k_caches`` and
    ``v_caches`` hold the other layers' alone, in their order, and the
    two entry points take two arguments more after them, ``states`` (one
    ``[seats + 1, *shape]`` array for each thing a layer keeps, layer by
    layer, donated like the pools) and ``seats`` (int32
    ``[B]``, ``[1]`` for ``prefill``: each sequence's row of every
    state array; 0, the scratch row, for a padding row), and return
    ``states`` written after the pools: ``(logits, k_caches, v_caches,
    states[, count])``. A program writes at a sequence's seat the state
    after its last live row (not after the bucket's last), and the
    program that holds a sequence's position 0 starts from zeros whatever
    the seat holds: nothing else clears a seat between two sequences."""

    prefill: Callable
    step: Callable
    params: Callable  # (config, params) -> the working copy to serve from
    kv_heads: int
    head_dim: int
    expert_counts: Optional[Tuple[int, int]] = None  # (layers, experts)
    # The router scores identity experts beside the real ones: a count's
    # rows are two values longer, the live (token, choice) pairs of the
    # layer that chose an identity and all its live pairs.
    expert_pairs: bool = False
    layer_windows: Tuple[Optional[int], ...] = ()
    kv_row: Optional[int] = None
    chunk_parts: Optional[Callable] = None
    indexer: Optional[Tuple[int, int]] = None  # (index_row, index_topk)
    layer_states: Tuple[Optional[tuple], ...] = ()
    # Not None: the family drafts for itself (a prediction module), and
    # an engine built with drafting on runs these and not the two above.
    drafting: Optional["Drafting"] = None

    @property
    def state_arrays(self) -> Tuple[Tuple["State", ...], ...]:
        """``layer_states`` as the cache takes it (``state_shapes``): of
        each layer that keeps a state, its arrays."""
        return tuple(state_specs(e) for e in self.layer_states if e)


class State(NamedTuple):
    """One array of what a sequence keeps in a layer without a pool: a
    row ``shape`` of ``dtype`` (``None``: the cache's own) at its seat."""

    shape: Tuple[int, ...]
    dtype: Any = None


def state_specs(entry) -> Tuple[State, ...]:
    """An entry of ``Serving.layer_states`` as the arrays it stands for:
    none for a layer with a pool, one for a bare shape. The one place
    that tells the two forms apart."""
    if entry is None:
        return ()
    if isinstance(entry[0], int):
        return (State(tuple(entry)),)
    return tuple(State(*spec) for spec in entry)


@dataclasses.dataclass(frozen=True)
class Drafting:
    """How a family with a prediction module is served when it drafts for
    itself: a decode step verifies one drafted token beside the last
    emitted one and yields one or two.

    The model's two walks, ``prefill`` and ``step``, as
    :class:`Serving`'s but for one more value at the end, the residual
    stream before the final norm (the module's input); a decode step is
    ``step`` at tokens [B, 2] -> logits [B, 2, V]. The module's two,
    ``draft_prefill`` and ``draft_step``, take ``(config, params, hidden,
    next_tokens, row, *the walk's cache inputs, k_caches, v_caches)``:
    the model's ``hidden`` of every position beside the token that
    follows it, and return ``(the module's logits of row ``row`` of the
    prompt [V] (``draft_prefill``) or of row ``row[b]`` of each sequence
    [B, V] (``draft_step``), k_caches, v_caches, the module's expert
    count)``. The module's
    ``pools`` full-attention pools follow the model's in ``k_caches`` and
    ``v_caches``, behind the full layers' tables and dests; its expert
    counts follow the model's."""

    prefill: Callable
    step: Callable
    draft_prefill: Callable
    draft_step: Callable
    pools: int = 1


def write_prompt_rows(k_caches, v_caches, dests, ks, vs):
    """The pools with a whole prompt's K and V, ``[1, T, KV, D]`` a layer
    as an attention module's ``prefill`` returns them, written as T pool
    rows at ``dests`` [T] (or at a ``dests`` a layer, where the pools are
    of two kinds); padding's rows go to the scratch page."""
    from raytpu.ops.paged_attention import scatter_kv_slots

    per_layer = dests if isinstance(dests, list) else [dests] * len(ks)
    t = per_layer[0].shape[0]
    return ([scatter_kv_slots(kc, d, k.reshape(t, -1))
             for kc, d, k in zip(k_caches, per_layer, ks)],
            [scatter_kv_slots(vc, d, v.reshape(t, -1))
             for vc, d, v in zip(v_caches, per_layer, vs)])


def _serve(c: GPT2Config, params, x, cache_args, whole: bool = False):
    """The serving walk, written once: the blocks over the embedded
    ``x``, ``ln_f`` and the tied head. Layer ``i`` attends
    through ``CausalSelfAttention.step(h, *cache_args(i))`` over ``x``
    [B * T, E], a row a position, or through ``prefill`` of a ``whole``
    prompt from position 0 over ``x`` [1, T, E], which returns
    its output and the layer's K and V (rows, or the pools it wrote).
    Returns ``(fp32 logits, K list, V list)``."""
    attn, mlp, ln = CausalSelfAttention(c), MLP(c), nn.LayerNorm(dtype=c.dtype)
    method = "prefill" if whole else "step"
    ks, vs = [], []
    for i in range(c.n_layer):
        lp = layer_params(params, i)
        h = ln.apply({"params": lp["ln_1"]}, x)
        y, k, v = attn.apply({"params": lp["attn"]}, h, *cache_args(i),
                             method=method)
        ks.append(k)
        vs.append(v)
        x = x + y
        h = ln.apply({"params": lp["ln_2"]}, x)
        x = x + mlp.apply({"params": lp["mlp"]}, h)
    x = ln.apply({"params": params["ln_f"]}, x)
    return _tied_logits(c, params, x), ks, vs


def gpt2_prefill(config: GPT2Config, params, tokens, dests, k_caches,
                 v_caches):
    """Whole-prompt forward: ``tokens`` [1, T] from position 0 (flash
    attention), its K and V written to the pools at ``dests`` [T] ->
    (fp32 logits [T, V], k_caches, v_caches)."""
    c = config
    x = _embedded(c, params["wte"], tokens) + _embedded(
        c, params["wpe"], jnp.arange(tokens.shape[1]))[None]
    logits, ks, vs = _serve(c, params, x, lambda i: (), whole=True)
    ks, vs = write_prompt_rows(k_caches, v_caches, dests, ks, vs)
    return logits[0], ks, vs


def gpt2_step(config: GPT2Config, params, tokens, positions, dests,
              block_tables, k_caches, v_caches):
    """The paged forward (``Serving.step``): ``tokens`` [B, T] at absolute
    ``positions`` [B, T] -> (fp32 logits [B, T, V], updated k_caches,
    v_caches); positions feed both the wpe lookup and the causal mask.
    The walk runs over the ``B * T`` rows (see
    :func:`raytpu.models.llama.llama_step`)."""
    c = config
    x = _embedded(c, params["wte"], tokens.reshape(-1)) + _embedded(
        c, params["wpe"], positions.reshape(-1))
    logits, ks, vs = _serve(c, params, x, lambda i: (
        k_caches[i], v_caches[i], dests, block_tables, positions))
    return logits.reshape(*tokens.shape, -1), ks, vs
