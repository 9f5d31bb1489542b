"""InferenceEngine: bucketed static-shape prefill + jitted decode step.

The TPU compile-once discipline, concretely:

- **Prefill** pads each prompt to the smallest length *bucket* (powers
  of two up to ``max_model_len``) and runs one sequence at a time, so
  XLA sees one program per bucket regardless of prompt length.
- **The KV pools** (``self.cache.k`` / ``.v``: one
  ``[num_pages, page_size, kv_heads * head_dim]`` array a layer) are
  given to all three programs *donated*. A program writes its new rows
  into the buffers it received and returns them, so the call consumes
  the arrays it was passed and every call site rebinds ``cache.k`` /
  ``cache.v`` on its next line, before anything else can read the cache.
- **Decode** pads the batch to the smallest batch *bucket* (powers of
  two up to ``max_num_seqs``). Tokens/positions/slots/block tables are
  data, not shapes, so changing batch *composition* never recompiles —
  only the first time a bucket size appears. Dummy rows point at the
  scratch page (page 0) at position 0 so padding attends to
  one masked-garbage slot and pollutes nothing.
- **A decode step's launch sends the device what changed since the
  last one.** The small rows (tokens, positions, dests, context lengths,
  seats) are built over the batch from the cache's kept tables
  (``_decode_inputs``: no Python call a sequence) and go over with the
  call itself, as numpy arrays (``_hand``; what more than one program
  reads is put once, ``_put``: the positions, a verify step's rows).
  A kind's block tables stay on the device from step to
  step and are put again only when the batch's membership, the table
  width or one of the batch's rows of that kind has changed
  (``_batch_tables``, ``cache.table_version``): a row changes once every
  ``page_size`` steps. The sampler's per-request rows follow the same
  rule (``_batch_rows``). The step record counts both (``host_puts``,
  ``tables_reused``).
- **Two kinds of layer** (``serving.layer_windows``: window layers among
  full ones) are two kinds of pool behind the one cache manager, and
  the chunk and decode programs take ``dests`` and block tables as a
  pair, full first (:mod:`raytpu.inference.kv_cache`). Before a program
  writes a sequence's positions the engine slides its window table on
  (``cache.slide``); a model of one kind gets the single arrays and the
  programs it always had.
- **A layer that keeps a state** (``serving.layer_states``: a short
  convolution's newest input rows; a delta-rule layer's float32 matrices
  and its convolutions' tails, two arrays of two dtypes) has no pool. The
  pools are the other layers' alone, K and V or one latent pool, and such
  a layer has a state array ``[max_num_seqs + 1, *shape]`` for each thing
  it keeps (``self.cache.state``, layer by layer), given to the three
  programs donated behind the pools and rebound with them. A sequence's row of
  every state array is its *seat* (``cache.seat``: taken and given back
  with its pages, so admission stops when either runs out, and a
  preempted sequence recomputes both); a program is given its sequences'
  seats as data, a padding row seat 0, the scratch row as page 0 is the
  scratch page. There is one seat map: what a drafting model's module
  leaves between steps (below) lies at the same seats.

The jitted callables are constructed exactly once, the three programs
by the one ``_build_program`` and the sampler by ``_build_sampler``. A
family has two entry points (``serving.prefill``, ``serving.step``): the
prefill program is the first, and the chunk and the decode programs are
both the second, ``step`` over ``[B, T]`` positions, at ``[1, T]`` and at
``[B, 1]`` (``_chunk_of``, ``_decode_of``: each keeps its program's own
inputs and result). The per-iteration loop
(:meth:`InferenceEngine.step`) only *calls* them. A lint test pins
this: ``jax.jit`` may appear in ``_build_*``
constructors only. The compile counters increment inside the traced
function body, which Python executes only during tracing — i.e. exactly
once per XLA compile — giving tests and the bench an honest recompile
count.

Sampling runs on the device (:func:`raytpu.inference.sampling.sample`):
a program's logits stay there and a step brings back ``int32[bucket]``
token ids. A row's draw is keyed by its request's seed and its own
position, so batched output == solo output.

**One decode step is in flight** (:meth:`InferenceEngine.step`): the ids
stay on the device as the next decode's ``tokens``, which is dispatched
before they are fetched, so the chip does not idle a host round trip a
step. ``Sequence.cached_len`` counts the positions whose write is
dispatched, and the tokens a call of ``step`` returns are those of the
decode the call before dispatched.

**A model that drafts for itself** (``serving.drafting``: a prediction
module; on unless the engine is built with ``drafting=False``) decodes
two positions a step: the token a sequence stands at and the module's
draft for the next, verified by speculative sampling
(:func:`raytpu.inference.sampling.speculative`), so a step yields a
sequence one token or two and ``cached_len`` advances by as many. Three
programs go out back to back with no host round trip between them:
``_decode_fn`` (the model over ``[bucket, 2]`` positions; its first
result is ``[bucket, 2, V]``), ``_accept_fn`` (accept or resample: ``int32
[bucket, 2]`` ids come back, -1 where the draft was not kept) and
``_draft_fn`` (the module over the kept tokens, which writes its own pool
and leaves the next draft and its logits on the device, a row a
sequence's seat: ``_draft_state``). The prefill and chunk programs hold
the module too and sample the first token inside, so every decoding
sequence has a draft. The module's pool is one more full-attention pool
behind the full layers' tables; a rejected draft's rows, there and in
the model's pools, are written again by the next step.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import operator
import time
from typing import (Any, Callable, Dict, List, Optional,
                    Sequence as SequenceT, Tuple)

import numpy as np

from raytpu.inference.kv_cache import PagedKVCache
from raytpu.inference.prefix_cache import PrefixCache
from raytpu.inference.sampling import (SamplingParams, draft_token, sample,
                                       speculative)
from raytpu.inference.scheduler import RUNNING, Scheduler, Sequence
from raytpu.util import compile_cache, task_events, tracing
from raytpu.util.metrics import Counter, Gauge, Histogram
from raytpu.util.profiler import profiling_enabled
from raytpu.util.stepprof import cost_analysis_flops, step_profiler

_running_gauge = Gauge("raytpu_infer_running_requests",
                       "Sequences currently decoding")
_waiting_gauge = Gauge("raytpu_infer_waiting_requests",
                       "Requests queued for admission")
_kv_util_gauge = Gauge("raytpu_infer_kv_page_utilization",
                       "Fraction of KV pages in use")
_state_seats_gauge = Gauge("raytpu_infer_state_seats_in_use",
                           "Sequences that hold a seat in the state arrays")
_prefill_tps_gauge = Gauge("raytpu_infer_prefill_tokens_per_s",
                           "Prefill throughput of the last engine step")
_decode_tps_gauge = Gauge("raytpu_infer_decode_tokens_per_s",
                          "Decode throughput of the last engine step")
_prefill_tokens_total = Counter("raytpu_infer_prefill_tokens_total",
                                "Prompt tokens prefilled")
_decode_tokens_total = Counter("raytpu_infer_decode_tokens_total",
                               "Tokens decoded")
_drafted_total = Counter("raytpu_infer_drafted_tokens_total",
                         "Drafted tokens a decode step verified")
_draft_accepted_total = Counter("raytpu_infer_draft_accepted_total",
                                "Drafted tokens the verification kept")
_moe_pairs_total = Counter(
    "raytpu_infer_moe_pairs_total",
    "Live (token, choice) pairs routed by a router with identity experts")
_moe_zero_pairs_total = Counter(
    "raytpu_infer_moe_zero_pairs_total",
    "Of those pairs, the ones that chose an identity expert")
_dsa_scored_total = Counter(
    "raytpu_infer_dsa_rows_scored_total",
    "Cached positions an indexer scored, a layer's, over the queries run")
_dsa_selected_total = Counter(
    "raytpu_infer_dsa_rows_selected_total",
    "Of those positions, the ones the queries' attention then read")
_gc_pause_total = Counter(
    "raytpu_host_gc_pause_seconds_total",
    "Seconds this process's interpreter was held by the cycle collector")
_gc_collections_total = Counter(
    "raytpu_host_gc_collections_total",
    "Collections of the cycle collector in this process",
    tag_keys=("generation",))
_ttft_hist = Histogram(
    "raytpu_infer_ttft_seconds",
    "Time from request admission to its first sampled token",
    boundaries=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0))


@dataclasses.dataclass(frozen=True)
class StepOutput:
    """One newly sampled token for one request."""

    request_id: str
    token_id: int
    finished: bool = False
    finish_reason: Optional[str] = None


@dataclasses.dataclass
class _Flight:
    """A decode step that is dispatched and not yet fetched: its ordinal
    (the engine's count of decodes dispatched, from 1: the step records'
    ``dispatched`` and ``fetched``), its batch,
    row for row, the ids its sampler leaves on the device (``int32
    [bucket]``: the next step's ``tokens`` as they lie), a routed model's
    counts beside them, the program's name and bucket key, when its
    launch began, and its FLOPs where the profiler is on."""

    n: int
    seqs: List[Sequence]
    ids: Any
    experts: list
    program: Tuple[str, str]
    launched: float
    flops: Optional[float] = None


# Where ``ks`` and ``vs`` stand in the three programs' arguments: given
# donated, so each program updates the pools in the buffers it received.
# The state arrays of a model whose layers keep a state stand behind them.
_POOLS = (1, 2)
_POOLS_AND_STATE = (1, 2, 3)


def _pow2_buckets(lo: int, hi: int) -> List[int]:
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


def _width(block_tables) -> int:
    """Columns of a program's block tables: of the one array, or of the
    pair a model of two kinds of layer is given (both as wide)."""
    import jax

    return jax.tree_util.tree_leaves(block_tables)[0].shape[1]


def _rows(x, axis: int):
    """A program's rows as the family's ``step`` takes them, ``[B, T]``:
    ``x`` (``positions``, or ``dests``: the one array or the pair by
    kind) with an axis of one put in. A chunk's rows are one sequence's
    (``[T]`` -> ``[1, T]``, axis 0), a decode's one a sequence (``[B]``
    -> ``[B, 1]``, axis 1)."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.numpy.expand_dims(a, axis), x)


def _chunk_of(step):
    """The chunk program's forward, ``(config, params, tokens [1, T],
    positions [T], dests [T], block_tables [1, P], *held) -> (logits
    [1, T, V], ...)``, from a family's ``step``."""
    def chunk(cfg, params, tokens, positions, dests, block_tables, *held):
        return step(cfg, params, tokens, _rows(positions, 0),
                    _rows(dests, 0), block_tables, *held)

    return chunk


def _decode_of(step):
    """The decode program's forward, ``(config, params, tokens [B],
    positions [B], dests [B], block_tables [B, P], context_lens [B],
    *held) -> (logits [B, V], ...)``, from a family's ``step``.
    ``context_lens`` is ``positions + 1`` and read by nothing: a row's
    query sees slots ``0 .. position``."""
    def decode(cfg, params, tokens, positions, dests, block_tables,
               context_lens, *held):
        logits, *rest = step(cfg, params, tokens[:, None],
                             _rows(positions, 1), _rows(dests, 1),
                             block_tables, *held)
        return (logits[:, 0], *rest)

    return decode


def _bucket_for(n: int, buckets: SequenceT[int]) -> int:
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"{n} exceeds largest bucket {buckets[-1]}")


class InferenceEngine:
    """Continuous-batching decode loop over a paged KV cache.

    Drive it with :meth:`add_request` + :meth:`step` (one scheduler
    iteration per call — the serve replica's loop), or use
    :meth:`generate` to run a closed batch to completion.

    What it serves is the config's to say: ``model_config.serving``
    (:class:`raytpu.models.gpt2.Serving`) names the family's two entry
    points, its working-copy rule and the pools' head shape; the engine
    knows no family, and what a pool row holds it leaves to them.

    The engine serves from a *working copy* of ``params``, made once at
    construction by the family's ``serving_params`` (beside its forwards
    in :mod:`raytpu.models`): the leaves the forwards use in
    ``model_config.dtype`` (matmul kernels, biases, embeddings) are held
    in it, the norms' leaves, which enter float32 arithmetic, as given.
    The three programs take that tree, so a step holds no conversion of
    a weight; a leaf already in the compute type is the caller's own
    array. The tree given is not kept: float32 weights under bf16
    compute cost half their bytes once the caller lets go of them, and
    a caller that wants the original keeps it. The copy may hold a leaf
    the given tree has not (GPT-2's lookup tables at a width that is not
    whole lane tiles: :func:`raytpu.models.gpt2.serving_params`);
    ``stats()["relaid_param_bytes"]`` has the bytes of such leaves.
    ``stats()["param_bytes"]``
    has the copy's bytes by dtype, ``stats()["kv_pool_bytes"]`` the
    bytes of the 2 x layers KV pools (``kv_pool_bytes_by_kind``: of the
    full and of the window layers'), or of a latent-attention model's
    one pool a layer (``serving.kv_row``: ``cache.v`` is then empty, or
    holds the index keys of a model whose attention chooses its rows,
    ``serving.indexer``),
    ``stats()["state_bytes"]`` those of the state arrays of the layers
    that keep a state and no pool (``serving.layer_states``).

    A program that fails while it runs (not while it is traced or
    compiled) has consumed the pools it was given and returned none:
    ``step()`` raises and the engine is left without a cache. There is
    nothing to resume from; the serve replica's loop logs the error,
    ends every stream with it and stops (``LLMDeployment._step_loop``).
    """

    def __init__(self, model_config, params, *, page_size: int = 16,
                 num_pages: Optional[int] = None, max_num_seqs: int = 8,
                 max_model_len: Optional[int] = None,
                 prefill_buckets: Optional[SequenceT[int]] = None,
                 decode_buckets: Optional[SequenceT[int]] = None,
                 prefill_chunk: Optional[int] = None,
                 chunk_buckets: Optional[SequenceT[int]] = None,
                 enable_prefix_cache: Optional[bool] = None,
                 tp: int = 1, mesh=None, drafting: Optional[bool] = None):
        import jax

        served = getattr(model_config, "serving", None)
        if served is None:
            raise TypeError(
                f"{type(model_config).__name__} does not say how it is "
                f"served: the engine asks a model config for `serving` "
                f"(the family's prefill and step entry points, its "
                f"working-copy rule, kv_heads, head_dim)")
        if served.layer_states and (tp > 1 or mesh is not None):
            raise ValueError(
                "a model with layers that keep a state is served on one "
                "device: its state arrays are not sharded yet")
        # A routed-expert family: its programs return a fourth value.
        if served.expert_counts and (tp > 1 or mesh is not None):
            raise ValueError(
                "a routed-expert model is served on one device: sharding "
                "its expert layer (tp, ep) in the engine is not there yet")
        if served.kv_row and (tp > 1 or mesh is not None):
            raise ValueError(
                "a model of latent pools is served on one device: every "
                "head reads the whole row (and an indexer's heads the "
                "whole index key), so a pool has no head axis to shard on")

        if served.layer_states and enable_prefix_cache:
            raise ValueError(
                "a model with layers that keep a state is served without "
                "the prefix cache: the state at a prefix's end is in none "
                "of its pages")

        # Self-drafting: on for a family that has a prediction module,
        # unless asked off; such an engine runs the one-position programs.
        if drafting and served.drafting is None:
            raise ValueError(
                f"drafting=True: {type(model_config).__name__} has no "
                f"prediction module to draft with (`serving.drafting`)")
        self._drafting = served.drafting if drafting is not False else None
        if self._drafting and served.layer_states:
            raise ValueError(
                "drafting over layers that keep a state is not there yet: "
                "a rejected draft's row has moved the state on")
        # Tokens each expert received, a row a routed layer; the module's
        # routed layers after the model's.
        self._expert_tokens = None
        if served.expert_counts:
            layers, experts = served.expert_counts
            self._expert_tokens = np.zeros(
                (layers + (self._drafting.pools if self._drafting else 0),
                 experts), np.int64)
        # Of a router with identity experts (``serving.expert_pairs``):
        # the live (token, choice) pairs that chose one, and all of them.
        self._moe_pairs = ({"moe_zero_pairs": 0, "moe_pairs": 0}
                           if served.expert_pairs else None)

        # Rows a query's attention keeps of those its indexer scores
        # (``serving.indexer``); None: it reads every cached row.
        self._index_topk = served.indexer[1] if served.indexer else None
        # How the family attends a chunk (``Serving.chunk_parts``), and
        # the chunks and parts that went another way than a decode row.
        self._chunk_parts = served.chunk_parts
        self._chunks_expanded = 0
        self._chunk_segments_expanded = 0

        self._config = model_config
        # The working copy, made once and before anything else takes
        # memory: what the family's forwards cast to the compute type is
        # in it already, so no step converts a weight. The tree given is
        # not kept; a caller that wants it keeps it.
        self._params = served.params(model_config, params)
        given_shapes = {
            jax.tree_util.keystr(path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}
        self._param_bytes: Dict[str, int] = {}
        # Of leaves the copy holds in another shape than given, or that
        # the tree given has not (a family's lookup tables).
        self._relaid_param_bytes = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._params)[0]:
            name = str(leaf.dtype)
            nbytes = leaf.size * leaf.dtype.itemsize
            self._param_bytes[name] = self._param_bytes.get(name, 0) + nbytes
            if given_shapes.get(jax.tree_util.keystr(path)) != leaf.shape:
                self._relaid_param_bytes += nbytes
        self.max_model_len = min(max_model_len or model_config.block_size,
                                 model_config.block_size)
        self.page_size = page_size
        # Static per-sequence page capacity: every decode gathers
        # [B, P*page_size] — P is a SHAPE, so it must not depend on
        # which sequences happen to be in the batch.
        self.max_pages_per_seq = -(-self.max_model_len // page_size)
        if num_pages is None:
            num_pages = max_num_seqs * self.max_pages_per_seq + 1
        # Chunked prefill: at most this many prompt tokens per engine
        # step per sequence, so a long prompt never stalls in-flight
        # decodes. Default = max_model_len, i.e. one-shot prefill (the
        # chunk path still runs for prefix-hit tails, which start at a
        # nonzero offset).
        self.prefill_chunk = min(prefill_chunk or self.max_model_len,
                                 self.max_model_len)
        # Window layers: pools of their own, a seat for every sequence
        # slot and one chunk's burst. Their pages are never shared, so
        # such a model is served without the prefix cache (the default
        # then) and an engine asked for one says why not.
        window = next((w for w in served.layer_windows if w), None)
        window_pages = None
        if window is not None:
            if enable_prefix_cache:
                raise ValueError(
                    "a model with window layers is served without the "
                    "prefix cache: a prompt's window pages are given back "
                    "as the window slides on, so there is nothing to share")
            if tp > 1 or mesh is not None:
                raise ValueError("a model with window layers is served on "
                                 "one device: its pools are not sharded yet")
            enable_prefix_cache = False
            window_pages = PagedKVCache.window_pool_pages(
                window, page_size, max_num_seqs, self.prefill_chunk)
        elif enable_prefix_cache is None:
            enable_prefix_cache = not served.layer_states
        # The module's pools come after the model's: full-attention ones.
        # A layer that keeps a state has no pool, and a sequence a seat
        # in its state array; a drafting engine seats the module's state.
        module_pools = self._drafting.pools if self._drafting else 0
        state_shapes = served.state_arrays
        self.cache = PagedKVCache(
            model_config.n_layer - len(state_shapes) + module_pools,
            num_pages, page_size,
            served.kv_heads, served.head_dim, dtype=model_config.dtype,
            layer_windows=served.layer_windows and (
                *served.layer_windows, *(None,) * module_pools),
            window_pages=window_pages,
            window_burst=self.prefill_chunk, latent_row=served.kv_row,
            index_row=served.indexer[0] if served.indexer else None,
            state_shapes=state_shapes,
            seats=max_num_seqs if state_shapes or self._drafting else 0)
        # Tensor parallelism: shard the weights with the parallel-layer
        # rule table and the KV pools along their last dimension, whole
        # heads to a shard (a head's features are contiguous). Each jit
        # site then compiles to one SPMD program. XLA partitions
        # everything in it but the attention kernels, which it cannot;
        # those run once per shard over their slice of the heads
        # (``ops.flash_attention.per_shard``), and find the mesh because
        # step() sets it around every call.
        self.mesh = mesh
        if self.mesh is None and tp > 1:
            from raytpu.parallel.mesh import build_mesh
            devices = jax.devices()
            if len(devices) < tp:
                raise ValueError(
                    f"tp={tp} needs {tp} devices, have {len(devices)}")
            self.mesh = build_mesh({"tp": tp}, devices[:tp])
        self._kv_sharding = None
        self._repl_sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from raytpu.parallel.sharding import shard_params
            tp_size = dict(self.mesh.shape).get("tp", 1)
            if tp_size > 1 and served.kv_heads % tp_size:
                raise ValueError(f"n_kv_head={served.kv_heads} not "
                                 f"divisible by tp={tp_size}")
            self._params = shard_params(self._params, self.mesh)
            self._kv_sharding = NamedSharding(
                self.mesh, PartitionSpec(None, None, "tp"))
            self._repl_sharding = NamedSharding(self.mesh, PartitionSpec())
            self.cache.k = [jax.device_put(a, self._kv_sharding)
                            for a in self.cache.k]
            self.cache.v = [jax.device_put(a, self._kv_sharding)
                            for a in self.cache.v]
        # What stats() says of the pools, read once: a step in flight
        # has consumed the arrays another thread would look at.
        self._kv_pool_bytes = sum(
            a.nbytes for a in self.cache.k + self.cache.v)
        self._two_kinds = len(self.cache.kinds) > 1
        # A layer's K and V, its one latent pool, or that and its index
        # keys' (``cache.v`` empty, or a pool a layer).
        second = self.cache.v or [None] * len(self.cache.k)
        self._kv_pool_bytes_by_kind = {
            name: sum(k.nbytes + (v.nbytes if v is not None else 0)
                      for k, v, of in zip(self.cache.k, second,
                                          self.cache.layer_kinds)
                      if of == kind)
            for kind, name in enumerate(("full", "window"))}
        # A token's bytes in the pools of every layer, as held (a latent
        # row is held on whole tiles).
        self._kv_token_bytes = self.cache.token_bytes
        self._state_bytes = sum(a.nbytes for a in self.cache.state)
        self._devices = sorted(f"{d.platform}:{d.id}"
                               for d in self.cache.k[0].devices())
        self.prefix_cache = (PrefixCache(self.cache)
                             if enable_prefix_cache else None)
        self.scheduler = Scheduler(self.cache, max_num_seqs=max_num_seqs,
                                   max_model_len=self.max_model_len,
                                   prefix_cache=self.prefix_cache,
                                   step_positions=2 if self._drafting else 1)
        self.prefill_buckets = sorted(prefill_buckets or _pow2_buckets(
            min(16, self.max_model_len), self.max_model_len))
        # A chunk's length buckets: pinned (``chunk_buckets``: fewer
        # programs for a mix whose prompts end anywhere, their last
        # chunks padded further) or powers of two up to the chunk.
        self.chunk_buckets = sorted(chunk_buckets or _pow2_buckets(
            min(16, self.prefill_chunk), self.prefill_chunk))
        if self.chunk_buckets[-1] < self.prefill_chunk:
            raise ValueError(f"chunk_buckets {self.chunk_buckets} hold no "
                             f"chunk of prefill_chunk={self.prefill_chunk}")
        self.decode_buckets = sorted(decode_buckets or _pow2_buckets(
            1, max_num_seqs))
        # Block-table width buckets: decode/chunk pass tables trimmed
        # to the batch's actual max page count (bucketed so the trim
        # adds at most log2(P_max) programs per batch bucket) instead
        # of always paying for the longest-ever sequence.
        self.page_buckets = _pow2_buckets(1, self.max_pages_per_seq)
        # Resolved paged-attention impl ("tpu"/"interpret"/"reference"):
        # informational (``stats()``).
        from raytpu.ops.paged_attention import resolve_paged_impl
        self.paged_attn_impl = resolve_paged_impl(
            getattr(model_config, "paged_attn", None))
        self._prefill_compiles: Dict[int, int] = {}
        self._chunk_compiles: Dict[str, int] = {}
        self._decode_compiles: Dict[str, int] = {}
        self._sample_compiles: Dict[str, int] = {}
        self._carry_compiles: Dict[str, int] = {}
        self._accept_compiles: Dict[str, int] = {}
        self._draft_compiles: Dict[str, int] = {}
        # What ``ops.grouped_matmul`` noted while a program was traced,
        # under the program's name and bucket key: how many of its
        # expert products go through the grouped kernel on these devices.
        self._grouped_calls: Dict[Tuple[str, Any], int] = {}
        # The decode batch the sampler's per-request rows were last put
        # for: (its sequences, the rows on the device, how many of them
        # are stochastic). Remade when the batch's membership changes.
        self._sampled_batch = ([], None, 0)
        # The decode batch whose block tables the device holds, at the
        # width they were put at, and a kind of pool the version of the
        # batch's rows then (``cache.table_version``) and the array: what
        # a step passes again while none of these has moved
        # (:meth:`_batch_tables`).
        self._tabled_batch: Tuple[list, int] = ([], 0)
        self._tables_kept: Dict[int, Tuple[int, Any]] = {}
        # Arrays handed from the host to the device so far (``_put``),
        # and the block tables put and passed again, over kinds and steps.
        self._host_puts = self._table_puts = self._table_reuses = 0
        # The decode step dispatched and not yet fetched (see ``step``),
        # when the last fetch ended, and over the engine's life the
        # decodes dispatched ahead of the fetch before them, those built
        # from the host's tokens with none in flight, and the rows
        # fetched for a sequence that had ended meanwhile.
        self._flight: Optional[_Flight] = None
        self._fetched_at = 0.0
        # Decodes dispatched so far: the newest one's ordinal.
        self._dispatched = 0
        self._decodes_ahead = self._decodes_drained = 0
        self._ahead_rows_dropped = 0
        # One record per step(): its phases' stamps and what it ran.
        self.recorder = tracing.StepRecorder(keep=("decodes",))
        # Called, if set, in ``infer.decode.wait`` once a decode step's
        # programs are on their way to the device, before the host
        # blocks on the ids of the step in flight: while the chip works
        # the host is free, and nowhere else in a step is it.
        self.on_launch: Optional[Callable[[], None]] = None
        self._prefill_tokens = 0
        self._decode_tokens = 0
        self._arrival_ts: Dict[str, float] = {}
        self._ttft_window = collections.deque(maxlen=256)
        # Request ids whose PREFILL_START was emitted but not yet paired
        # with PREFILL_END (chunked prefills span steps; preemption-
        # resume prefills are excluded — RESUMED covers them).
        self._prefill_announced: set = set()
        self._hbm_tick = 0
        self._jnp = jax.numpy
        self._jax = jax
        compile_cache.enable()
        # One XLA program per key: a prompt's length bucket; a chunk's
        # length (a decode's batch) bucket x the trimmed table width.
        if self._drafting is not None:
            # What the module leaves on the device between steps, the
            # next draft and the logits it was drawn from: a row a
            # sequence's seat (``cache.seat``; padding rows name row 0).
            self._draft_state = (
                self._put(np.zeros(max_num_seqs + 1, np.int32)),
                self._put(np.zeros((max_num_seqs + 1,
                                    model_config.vocab_size), np.float32)))
            self._drafted = self._draft_accepted = 0
            (self._prefill_fn, self._chunk_fn, self._decode_fn,
             self._accept_fn, self._draft_fn) = self._build_drafting(
                 jax, self._drafting)
        else:
            self._prefill_fn = self._build_program(
                jax, "_prefill", served.prefill, self._prefill_compiles,
                lambda tokens, dests: tokens.shape[1])
            self._chunk_fn = self._build_program(
                jax, "_chunk", _chunk_of(served.step), self._chunk_compiles,
                lambda tokens, positions, dests, block_tables:
                f"{tokens.shape[1]}x{_width(block_tables)}")
            self._decode_fn = self._build_program(
                jax, "_decode", _decode_of(served.step),
                self._decode_compiles,
                lambda tokens, positions, dests, block_tables, context_lens:
                f"{tokens.shape[0]}x{_width(block_tables)}")
            self._sample_fn = self._build_sampler(
                jax, self._sample_compiles)
            self._carry_fn = self._build_carry(jax, self._carry_compiles)

    # ---- compiled steps (the ONLY jax.jit call sites) ---------------

    @contextlib.contextmanager
    def _traced(self, name, compiles, bucket):
        """Around the body of a program while it is traced (trace-time
        only): counts the XLA compile under ``compiles[bucket]`` and notes
        the products the grouped kernel takes of this program."""
        from raytpu.ops.grouped_matmul import kernel_calls

        compiles[bucket] = compiles.get(bucket, 0) + 1
        with kernel_calls(self._devices[0].split(":")[0]) as grouped:
            yield
        self._grouped_calls[name, bucket] = grouped[0]

    def _build_program(self, jax, name, fwd, compiles, bucket_key):
        """One of the three jitted programs, ``(params, ks, vs, *inputs)
        -> (logits, ks, vs[, expert count])``: the family's entry point
        ``fwd`` on the donated pools. Of a model whose layers keep a
        state, ``(params, ks, vs, states, seats, *inputs) -> (logits, ks,
        vs, states[, expert count])``, the state arrays donated too.
        ``compiles`` counts its traces under ``bucket_key(*inputs)``;
        ``name`` is what a trace and the compile cache know the program
        by."""
        cfg, kv_sh = self._config, self._kv_sharding
        carried = 2 if self.cache.state else 0  # states and seats

        def program(params, ks, vs, *inputs):
            state, inputs = inputs[:carried], inputs[carried:]
            with self._traced(name, compiles, bucket_key(*inputs)):
                logits, ks2, vs2, *more = fwd(
                    cfg, params, *inputs, ks, vs, *state)
            if kv_sh is not None:
                # Pin the pool sharding through the update: the pools
                # must come back kv-head-sharded, never resharded.
                ks2 = [jax.lax.with_sharding_constraint(x, kv_sh)
                       for x in ks2]
                vs2 = [jax.lax.with_sharding_constraint(x, kv_sh)
                       for x in vs2]
            return (logits, ks2, vs2, *more)

        program.__name__ = name
        return jax.jit(program, donate_argnums=_POOLS_AND_STATE if carried
                       else _POOLS)

    def _build_sampler(self, jax, compiles):
        """The jitted sampler, ``(logits, temperature, top_k, seed,
        position) -> ids``: :func:`sampling.sample` over a program's
        logits where they lie, a decode's ``[bucket, vocab]`` or the one
        row of a prefill's. ``compiles`` counts its traces under the
        logits' shape."""

        def _sample(logits, temperature, top_k, seed, position):
            # Trace-time only, as in ``_build_program``.
            shape = "x".join(str(n) for n in logits.shape)
            compiles[shape] = compiles.get(shape, 0) + 1
            return sample(logits.reshape(-1, logits.shape[-1]),
                          temperature, top_k, seed, position)

        return jax.jit(_sample)

    def _build_carry(self, jax, compiles):
        """The jitted hand-over of a step's tokens where the batch has
        moved since the step in flight, ``(ids in flight, source, fresh)
        -> tokens``, ``int32[bucket]`` each: row ``i`` takes the
        in-flight id of row ``source[i]``, or where that is -1 (a
        sequence that has just joined, a padding row) ``fresh[i]``, the
        last token the host knows. One program a decode bucket, entered
        with the bucket's first decode (:meth:`_run_decode`); ids of
        another bucket are fetched first (:meth:`step`). ``compiles``
        counts its traces under the bucket."""
        jnp = self._jnp

        def _carry(ids, source, fresh):
            # Trace-time only, as in ``_build_program``.
            key = str(ids.shape[0])
            compiles[key] = compiles.get(key, 0) + 1
            return jnp.where(source >= 0, ids[jnp.maximum(source, 0)], fresh)

        return jax.jit(_carry)

    def _build_drafting(self, jax, drafting):
        """The five jitted programs of a model that drafts for itself
        (see the module's docstring), in place of the three and the
        sampler. ``state`` is ``_draft_state``, ``(draft [slots], the
        module's logits it was drawn from [slots, V])``, read and written
        at a sequence's slot; the pools are donated to the three that
        write them."""
        cfg, jnp = self._config, self._jnp
        traced = self._traced

        def _build_prompt(name, model, module, compiles, bucket_key):
            """A prompt's program: the model's walk, the first token
            sampled from its row ``row`` (at ``position``) where the
            prompt gives none to follow it (``next_tokens`` -1), the
            module over every position beside the token that follows,
            and the draft for ``position + 2`` left at ``slot``."""

            def program(params, ks, vs, state, drafted, *inputs):
                next_tokens, row, position, slot, *rows = drafted
                with traced(name, compiles, bucket_key(*inputs)):
                    logits, ks, vs, count, hidden = model(
                        cfg, params, *inputs, ks, vs)
                    last = logits.reshape(-1, logits.shape[-1])[row]
                    first = sample(last[None], *rows, position[None])
                    own, ks, vs, more = module(
                        cfg, params, hidden,
                        jnp.where(next_tokens < 0, first[0], next_tokens),
                        row, *inputs[1:], ks, vs)
                    draft = draft_token(own[None], *rows,
                                        position[None] + 2)
                return (logits, ks, vs,
                        jnp.concatenate([count, more[None]]), first,
                        (state[0].at[slot].set(draft[0]),
                         state[1].at[slot].set(own)))

            program.__name__ = name
            return jax.jit(program, donate_argnums=_POOLS)

        def draft_chunk(cfg, params, hidden, next_tokens, row, positions,
                        dests, block_tables, ks, vs):
            # One sequence's rows, and the module's logits of its one row.
            own, *rest = drafting.draft_step(
                cfg, params, hidden, next_tokens, row[None],
                _rows(positions, 0), _rows(dests, 0), block_tables, ks, vs)
            return (own[0], *rest)

        def _decode(params, ks, vs, state, slots, tokens, positions, dests,
                    block_tables):
            with traced("_decode", self._decode_compiles,
                        f"{tokens.shape[0]}x{_width(block_tables)}"):
                return drafting.step(
                    cfg, params,
                    jnp.stack([tokens, state[0][slots]], axis=1),
                    positions[:, None]
                    + jnp.arange(2, dtype=positions.dtype),
                    dests, block_tables, ks, vs)

        def _accept(logits, state, slots, positions, *rows):
            shape = "x".join(str(n) for n in logits.shape)
            self._accept_compiles[shape] = \
                self._accept_compiles.get(shape, 0) + 1
            return speculative(logits, state[1][slots], state[0][slots],
                               *rows, positions + 1)

        def _draft(params, ks, vs, state, slots, hidden, ids, kept,
                   positions, dests, block_tables, *rows):
            with traced("_draft", self._draft_compiles,
                        f"{ids.shape[0]}x{_width(block_tables)}"):
                own, ks, vs, count = drafting.draft_step(
                    cfg, params, hidden, ids, kept - 1,
                    positions[:, None]
                    + jnp.arange(2, dtype=positions.dtype),
                    dests, block_tables, ks, vs)
                draft = draft_token(own, *rows, positions + kept + 1)
            return ks, vs, count, (state[0].at[slots].set(draft),
                                   state[1].at[slots].set(own))

        return (
            _build_prompt("_prefill", drafting.prefill,
                           drafting.draft_prefill, self._prefill_compiles,
                           lambda tokens, dests: tokens.shape[1]),
            _build_prompt("_chunk", _chunk_of(drafting.step), draft_chunk,
                           self._chunk_compiles,
                           lambda tokens, positions, dests, block_tables:
                           f"{tokens.shape[1]}x{_width(block_tables)}"),
            jax.jit(_decode, donate_argnums=_POOLS), jax.jit(_accept),
            jax.jit(_draft, donate_argnums=_POOLS))

    def _sampling_rows(self, seqs: SequenceT[Sequence], bucket: int):
        """What the sampler takes of each request, a row a sequence on
        the device (``temperature``, ``top_k``, ``seed``; padding rows
        greedy), and how many of the rows are stochastic."""
        temperature = np.zeros(bucket, dtype=np.float32)
        top_k = np.zeros(bucket, dtype=np.int32)
        seed = np.zeros(bucket, dtype=np.uint32)
        for i, seq in enumerate(seqs):
            temperature[i] = seq.sampling.temperature
            top_k[i] = min(seq.sampling.top_k, np.iinfo(np.int32).max)
            seed[i] = seq.sampling.seed & 0xFFFFFFFF
        return (self._put((temperature, top_k, seed)),
                int(np.count_nonzero(temperature > 0.0)))

    def _put(self, x):
        """Host arrays → device arrays, in one call however many: ``x``
        is an array or a tuple of them (of tuples: what a program takes a
        kind of pool; None stays None). Under a tp mesh, inputs are
        committed replicated — jit rejects a mix of mesh-sharded params
        and default-device-committed arrays."""
        self._host_puts += len(self._jax.tree_util.tree_leaves(x))
        return self._jax.device_put(x, self._repl_sharding)

    def _hand(self, x):
        """Host arrays (``x`` as :meth:`_put` takes it) for a jitted call
        that reads them once, counted as what they are, transfers from
        the host. On one device the call is given them as they are: the
        dispatch's own transfer costs less than a put (on the v5e 1.0 ms
        a decode launch of five small rows against 1.4 as one put and 1.7
        as five; ``PERF.md``, PR 49). Under a tp mesh they are put, so
        that they stay committed replicated. What several programs read,
        or a later step, is :meth:`_put` either way."""
        if self._repl_sharding is not None:
            return self._put(x)
        self._host_puts += len(self._jax.tree_util.tree_leaves(x))
        return x

    @staticmethod
    def _same_batch(batch: SequenceT[Sequence],
                    seqs: SequenceT[Sequence]) -> bool:
        """Whether ``seqs`` are the sequences of ``batch``, row for row:
        a sequence is known by what it is, not by its name."""
        return len(batch) == len(seqs) \
            and all(map(operator.is_, batch, seqs))

    def _batch_rows(self, seqs: SequenceT[Sequence], bucket: int):
        """:meth:`_sampling_rows` of a decode's batch, put again only when
        its membership has changed since the last step."""
        batch, rows, stochastic = self._sampled_batch
        if not self._same_batch(batch, seqs):
            rows, stochastic = self._sampling_rows(seqs, bucket)
            self._sampled_batch = (list(seqs), rows, stochastic)
        return rows, stochastic

    def _batch_tables(self, seqs: SequenceT[Sequence], ids: List[str],
                      rows: np.ndarray, width: int):
        """A decode batch's block tables on the device, as a program
        takes them by kind. A kind's is the array the last step was given
        while the batch's membership, the ``width`` and what the cache
        holds in the batch's ``rows`` of that kind are what they were
        then: a row changes once every ``page_size`` steps. What has
        moved is gathered and put again, in one call."""
        cache, kept = self.cache, self._tables_kept
        if self._tabled_batch[1] != width \
                or not self._same_batch(self._tabled_batch[0], seqs):
            self._tabled_batch = (list(seqs), width)
            kept.clear()
        versions = [cache.table_version(rows, kind) for kind in cache.kinds]
        stale = [kind for kind in cache.kinds
                 if kept.get(kind, (None,))[0] != versions[kind]]
        if stale:
            fresh = self._put(tuple(
                cache.table_array(ids, width, batch=len(rows), kind=kind)
                for kind in stale))
            for kind, table in zip(stale, fresh):
                kept[kind] = (versions[kind], table)
        reused = len(versions) - len(stale)
        self.recorder.open.fields["tables_reused"] = reused
        self._table_puts += len(stale)
        self._table_reuses += reused
        return self._by_kind(lambda kind: kept[kind][1])

    def _call(self, fn, seats, *inputs):
        """Run one of the three programs and rebind what it consumed:
        the pools and, of a model whose layers keep a state, the state
        arrays, which it takes with its sequences' ``seats`` (on the
        device; else None). Returns ``(logits, what else it returned: a
        routed model's count)``."""
        cache = self.cache
        state = (cache.state, seats) if cache.state else ()
        logits, cache.k, cache.v, *more = fn(
            self._params, cache.k, cache.v, *state, *inputs)
        if cache.state:
            cache.state, *more = more
        return logits, more

    def _seats(self, ids: SequenceT[str], bucket: int):
        """The seats of a program's sequences, int32 ``[bucket]``, for
        the host's one put of its inputs; None where no layer keeps a
        state."""
        return self.cache.seats(ids, bucket) if self.cache.state else None

    def _by_kind(self, of):
        """``of(kind)`` as a program takes what it is given a kind of
        pool (``dests``, block tables): the one array, or over two kinds
        the pair, full first."""
        if not self._two_kinds:
            return of(0)
        return tuple(of(kind) for kind in self.cache.kinds)

    def _count_chosen(self, positions) -> None:
        """Add to the open step's record and to the running totals what
        the indexers of one layer did for queries at the absolute
        ``positions``: ``dsa_rows_scored``, the cached positions each
        scored (its own among them), and ``dsa_rows_selected``, the
        ``index_topk`` at most its attention then read. Known to the
        host: a query's context is its position. A model without an
        indexer has neither field."""
        if self._index_topk is None:
            return
        cached = np.asarray(positions, np.int64) + 1
        fields = self.recorder.open.fields
        for name, total, more in (
                ("dsa_rows_scored", _dsa_scored_total, int(cached.sum())),
                ("dsa_rows_selected", _dsa_selected_total,
                 int(np.minimum(cached, self._index_topk).sum()))):
            fields[name] = fields.get(name, 0) + more
            total.inc(more)

    def _count_experts(self, experts, *programs) -> None:
        """Add what the programs of a routed model returned beside their
        logits (int32 ``[layers, experts]``: live tokens each expert held
        here received, two columns more under ``serving.expert_pairs``;
        a verify step's model and module each give their
        layers') to the running total and to the open step's
        record, and what was noted of ``programs`` (name and bucket
        key) when they were traced: how many of their expert products go
        through the grouped kernel. A dense family's programs return
        nothing: ``experts`` is empty."""
        if not experts:
            return
        counts = np.concatenate([np.atleast_2d(np.asarray(count))
                                 for count in experts])
        fields = self.recorder.open.fields
        if self._moe_pairs is not None:
            # A row's last two values are the layer's pairs, not tokens.
            zero, pairs = counts[:, -2:].sum(axis=0).tolist()
            counts = counts[:, :-2]
            for name, total, more in (
                    ("moe_zero_pairs", _moe_zero_pairs_total, zero),
                    ("moe_pairs", _moe_pairs_total, pairs)):
                self._moe_pairs[name] += more
                fields[name] = fields.get(name, 0) + more
                total.inc(more)
        self._expert_tokens += counts
        fields["moe_assignments"] = (fields.get("moe_assignments", 0)
                                     + int(counts.sum()))
        fields["moe_experts_touched"] = (
            fields.get("moe_experts_touched", 0)
            + int(np.count_nonzero(counts)))
        fields["moe_expert_max"] = max(fields.get("moe_expert_max", 0),
                                       int(counts.max()))
        fields["moe_grouped_calls"] = (
            fields.get("moe_grouped_calls", 0)
            + sum(self._grouped_calls[one] for one in programs))

    # ---- request lifecycle ------------------------------------------

    def add_request(self, request_id: str, prompt: SequenceT[int],
                    sampling: Optional[SamplingParams] = None) -> Sequence:
        sampling = sampling or SamplingParams()
        prompt = list(prompt)
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if len(prompt) >= self.max_model_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= max_model_len "
                f"{self.max_model_len} leaves no room to generate")
        if self.cache.pages_for(len(prompt) + self.scheduler.step_positions) \
                > self.cache.total_pages:
            raise ValueError("prompt exceeds total KV-page capacity")
        seq = Sequence(request_id=request_id, prompt=prompt,
                       sampling=sampling)
        self._arrival_ts[request_id] = time.perf_counter()
        self.scheduler.add(seq)
        return seq

    def abort(self, request_id: str) -> bool:
        self._arrival_ts.pop(request_id, None)
        self._prefill_announced.discard(request_id)
        return self.scheduler.abort(request_id)

    def has_unfinished(self) -> bool:
        """Whether a call of :meth:`step` has anything to do: a request
        waits or runs, or a decode is in flight (of sequences that were
        all aborted since: its rows are still to be fetched and dropped)."""
        return self.scheduler.has_unfinished() or self._flight is not None

    # ---- the iteration ----------------------------------------------

    def step(self) -> List[StepOutput]:
        """One scheduler iteration: run every admitted prefill, then
        dispatch one padded decode step over all running sequences, its
        tokens sampled on the device, and fetch the ids of the decode
        the call before dispatched; retire finished sequences (freeing
        their pages). Leaves one record in ``self.recorder`` (see
        :meth:`step_log`).

        **One decode is in flight.** A call leaves its decode and its
        sampler on the device (``_flight``) and returns the tokens of
        the one before. The next call schedules, builds and dispatches
        its own decode behind it and only then blocks on the ids in
        flight: the chip goes from one step to the next with no host in
        between. The ids never leave the device on their way into the
        next decode: where the batch is the in-flight step's row for
        row they are its ``tokens`` as they lie, and where it has moved
        (a sequence joined after its prefill, left by length, which the
        scheduler knows a step early, or was ended by a stop token or an
        ``abort``) one small program gathers each row's id from the row
        it had in flight and takes a joiner's from the host
        (``_carry_fn``). Everything else a decode takes the host knows
        before the ids arrive, because ``Sequence.cached_len`` advances
        when a position's write is dispatched (``Sequence.in_flight``
        counts the tokens not fetched yet). So the tokens a call returns
        are those of the decode dispatched a call earlier, and a
        sequence's last token comes from a call that dispatches nothing
        for it.

        A row fetched for a sequence that has ended meanwhile is
        dropped: its write went to a slot of the sequence's own page,
        which whatever is dispatched later reuses later on the chip. The
        decode in flight is *drained*, fetched with nothing dispatched
        behind it, where there is nothing to dispatch (the batch's last
        tokens), where the batch has moved to another bucket (the
        hand-over is one program a bucket) and before an iteration in
        which the scheduler would preempt (``Scheduler.pages_short``: a
        preempted sequence re-prefills ``tokens``, which must hold every
        token whose KV was written); the decode after a drain, like an idle
        engine's first, is built from the host's tokens. A model that drafts for itself
        keeps nothing in flight (:meth:`_run_verify`: how far a sequence
        advances is the device's answer there)."""
        out: List[StepOutput] = []
        recorder = self.recorder
        compiled = self._programs_traced()
        preempted = self.scheduler.num_preemptions
        decoded = 0

        with recorder.step("infer.step", {
                "decodes": 0, "bucket": 0, "table_width": 0,
                "live_pages": 0, "live_pages_full": 0,
                "live_pages_window": 0, "window_pages_released": 0,
                "pages_owned_full": 0, "pages_owned_window": 0,
                "state_seats": 0, "state_bytes": 0,
                "sampled_stochastic": 0, "host_puts": 0, "tables_reused": 0,
                "ahead": 0, "ahead_rows_dropped": 0,
                "dispatched": 0, "fetched": 0, "carried": 0,
                "kv_bytes_per_token": self._kv_token_bytes} | (
                    {"drafted": 0, "accepted": 0, "emitted": 0}
                    if self._drafting else {}) | (
                    {"dsa_rows_scored": 0, "dsa_rows_selected": 0}
                    if self._index_topk else {})) as st:
            if self._flight is not None and self.scheduler.pages_short():
                # It would preempt: a victim re-prefills ``tokens``, which
                # the fetch completes, and an ended sequence's pages may
                # make the preemption needless.
                decoded += self._drain(out)
            with recorder.phase("infer.schedule"):
                waiting = len(self.scheduler.waiting)
                plan = self.scheduler.schedule()
                st.attrs["admitted"] = (
                    waiting + len(plan.preempted)
                    - len(self.scheduler.waiting))
            # The mesh is thread-local state and any thread may step.
            with (self._jax.set_mesh(self.mesh) if self.mesh is not None
                  else contextlib.nullcontext()):
                prefilled = 0
                for seq in plan.prefills:
                    prefilled += self._run_prefill(seq, out)
                decodes = plan.decodes
                if self._flight is not None and (
                        not decodes or _bucket_for(
                            len(decodes), self.decode_buckets)
                        != len(self._flight.ids)):
                    # The batch's last tokens; or ids of another bucket,
                    # which no program hands over.
                    decoded += self._drain(out)
                    decodes = [s for s in decodes if s.state == RUNNING]
                if decodes:
                    decoded += self._run_decode(decodes, out)
            st.attrs["compiled"] = self._programs_traced() - compiled
            st.attrs["preempted"] = \
                self.scheduler.num_preemptions - preempted
            if self._two_kinds:
                st.attrs["pages_owned_full"] = self.cache.used_pages()
                st.attrs["pages_owned_window"] = \
                    self.cache.window_pages_owned()
            if self.cache.total_seats:
                st.attrs["state_seats"] = seats = self.cache.seats_in_use()
                st.attrs["state_bytes"] = seats * self.cache.state_bytes
                _state_seats_gauge.set(seats)

            # Throughput gauges reflect THIS step — a step that moved no
            # tokens zeroes them, so autoscalers never read the last busy
            # step's value as live pressure.
            record = recorder.open
            if prefilled:
                self._prefill_tokens += prefilled
                _prefill_tokens_total.inc(prefilled)
                _prefill_tps_gauge.set(prefilled / max(record.seconds(
                    "infer.prefill", "infer.prefill_chunk"), 1e-9))
            else:
                _prefill_tps_gauge.set(0.0)
            if decoded:
                self._decode_tokens += decoded
                _decode_tokens_total.inc(decoded)
                _decode_tps_gauge.set(decoded / max(record.seconds(
                    "infer.decode"), 1e-9))
            else:
                _decode_tps_gauge.set(0.0)
            _running_gauge.set(len(self.scheduler.running))
            _waiting_gauge.set(len(self.scheduler.waiting))
            _kv_util_gauge.set(self.cache.utilization())
            collected = tracing.gc_unpublished()
            if collected is not None:
                counts, seconds = collected
                _gc_pause_total.inc(seconds)
                for generation, count in enumerate(counts):
                    if count:
                        _gc_collections_total.inc(
                            count, tags={"generation": str(generation)})
        return out

    def _programs_traced(self) -> int:
        """Programs traced (so compiled) so far: the three kinds and
        the sampler."""
        return (sum(self._prefill_compiles.values())
                + sum(self._chunk_compiles.values())
                + sum(self._decode_compiles.values())
                + sum(self._sample_compiles.values())
                + sum(self._carry_compiles.values())
                + sum(self._accept_compiles.values())
                + sum(self._draft_compiles.values()))

    def _run_prefill(self, seq: Sequence, out: List[StepOutput]) -> int:
        """Advance one sequence's prefill by (at most) one chunk.

        A sequence starting from zero whose whole prompt fits in one
        chunk takes the legacy full-prefill path (flash attention, one
        program per length bucket). Anything with prior cached context
        — a prefix-cache hit tail, or chunk 2..n of a long prompt —
        runs through the paged chunk path, which attends to the cached
        pages. The FINAL chunk's last logit samples the first token.
        """
        plen = seq.prefill_len
        start = seq.cached_len
        if task_events.request_events_enabled() and not seq.generated \
                and seq.request_id not in self._prefill_announced:
            self._prefill_announced.add(seq.request_id)
            task_events.emit_request(
                seq.request_id,
                task_events.RequestTransition.PREFILL_START,
                deployment=seq.deployment, tenant=seq.tenant,
                data={"prompt_tokens": len(seq.prompt), "cached": start})
        # The phase is the whole stall this prefill puts on the batch:
        # inputs, the call, the last row sampled, the first token out.
        whole = start == 0 and plen <= self.prefill_chunk
        with self.recorder.phase(
                "infer.prefill" if whole else "infer.prefill_chunk",
                {"request_id": seq.request_id}) as ph:
            arrived = self._arrival_ts.get(seq.request_id)
            if arrived is not None:  # None: resumed after a preemption
                ph.attrs["waited_s"] = ph.t0 - arrived
            n = self._prefill_step(seq, whole, out, ph.attrs)
        self.recorder.open.fields.setdefault("prefills", []).append(ph.attrs)
        if task_events.request_events_enabled() \
                and seq.cached_len >= plen \
                and seq.request_id in self._prefill_announced:
            self._prefill_announced.discard(seq.request_id)
            task_events.emit_request(
                seq.request_id,
                task_events.RequestTransition.PREFILL_END,
                deployment=seq.deployment, tenant=seq.tenant)
        return n

    def _register_prefix(self, seq: Sequence) -> None:
        """Index every fully-written full PROMPT page for sharing.
        (Pages holding generated tokens stay private.) Must run before
        sampling: emitting can finish the sequence and drop its block
        table."""
        if self.prefix_cache is not None:
            self.prefix_cache.register(
                seq.request_id, seq.prompt,
                min(seq.cached_len, len(seq.prompt)))

    def _prefill_step(self, seq: Sequence, whole: bool,
                      out: List[StepOutput], attrs: dict) -> int:
        """Run one prefill program for ``seq``: its whole prompt from
        zero (``whole``), or its next chunk against the pages already
        written. The two differ in their inputs alone."""
        start, plen = seq.cached_len, seq.prefill_len
        take = plen if whole else min(self.prefill_chunk, plen - start)
        bucket = _bucket_for(
            take, self.prefill_buckets if whole else self.chunk_buckets)
        tokens = np.zeros((1, bucket), dtype=np.int32)
        tokens[0, :take] = seq.tokens[start:start + take]
        # A chunk's rows are all in the window pools before any attends;
        # of a whole prompt's (flash attention, no pool read) only what
        # the decode after it will see.
        end = start + take
        self._count_chosen(np.arange(start, end))
        released = self.cache.slide(seq.request_id,
                                    end if whole else start, end)
        dests = self._by_kind(lambda kind: self.cache.chunk_dests(
            seq.request_id, start, take, bucket, kind))
        if whole:
            attrs.update(tokens=take, bucket=bucket)
            fn, inputs = self._prefill_fn, (tokens, dests)
            program = ("_prefill", bucket)
        else:
            attrs.update(tokens=take, bucket=bucket, start=start)
            positions = np.zeros(bucket, dtype=np.int32)
            positions[:take] = np.arange(start, start + take)
            # Trim to this sequence's allocated pages (bucketed) — the
            # reference gather pays O(table width), not O(P_max).
            p_used = _bucket_for(self.cache.num_seq_pages(seq.request_id),
                                 self.page_buckets)
            tables = self._by_kind(lambda kind: self.cache.table_array(
                [seq.request_id], p_used, kind=kind))
            fn, inputs = self._chunk_fn, (tokens, positions, dests, tables)
            program = ("_chunk", f"{bucket}x{p_used}")
            parts = self._chunk_parts and self._chunk_parts(
                bucket, start, self.cache.page_size)
            if parts:
                fields = self.recorder.open.fields
                fields["chunk_expanded"] = \
                    fields.get("chunk_expanded", 0) + parts[1]
                self._chunks_expanded += 1
                self._chunk_segments_expanded += parts[1]
        seats, *inputs = self._hand(
            (self._seats([seq.request_id], 1), *inputs))
        if self._drafting is None:
            logits, experts = self._call(fn, seats, *inputs)
        else:
            # The tokens that follow the rows', for the module; -1 where
            # a fresh prompt ends and the program samples the one to come.
            following = np.zeros((1, bucket), dtype=np.int32)
            known = seq.tokens[start + 1:end + 1]
            following[0, :len(known)] = known
            following[0, len(known):take] = -1
            rows, stochastic = self._sampling_rows([seq], 1)
            (logits, self.cache.k, self.cache.v, count, first,
             self._draft_state) = fn(
                self._params, self.cache.k, self.cache.v, self._draft_state,
                (*self._put((following, np.int32(take - 1),
                             np.int32(end - 1),
                             np.int32(self.cache.seat(seq.request_id)))),
                 *rows),
                *inputs)
            experts = [count]
        self._count_experts(experts, program)
        seq.cached_len = end
        # The chunk's burst goes back: what the next query will see stays.
        self.recorder.open.fields["window_pages_released"] += \
            released + self.cache.slide(seq.request_id, end, end)
        self._register_prefix(seq)
        if seq.cached_len >= plen and not seq.generated:
            # The last chunk of a fresh prompt: its last REAL row's logit
            # samples the first new token, at that row's position. A
            # preemption-resume prefill must NOT resample — the tail
            # token was already emitted; the next decode rewrites its KV.
            if self._drafting is None:
                last = logits[take - 1] if whole else logits[0, take - 1]
                rows, stochastic = self._sampling_rows([seq], 1)
                ids = self._sample_fn(last, *rows, self._put(
                    np.array([end - 1], dtype=np.int32)))
            else:
                ids = first  # sampled inside the program, the same way
            self.recorder.open.fields["sampled_stochastic"] += stochastic
            self._emit(seq, int(np.asarray(ids)[0]), out)
        return take

    def _decode_inputs(self, seqs: List[Sequence], depth: int):
        """What a decode step over ``seqs`` hands its programs, each
        writing ``depth`` positions a sequence from the one it stands at,
        built over the batch and not a sequence at a time: ``(ids,
        bucket, table width, tokens, positions, dests by kind, block
        tables by kind)``, host arrays of ``bucket`` rows but the tables,
        which are on the device (:meth:`_batch_tables`). The tokens are
        the last the host knows of each sequence: a decode behind a step
        in flight takes that step's ids in their place
        (:meth:`_tokens_ahead`). A padding row names the scratch page's
        first slots and position 0. Slides the window tables on to the
        step's positions and fills the step record's fields of what the
        step reads."""
        cache, fields = self.cache, self.recorder.open.fields
        b = len(seqs)
        bucket = _bucket_for(b, self.decode_buckets)
        ids = [s.request_id for s in seqs]
        rows = cache.rows(ids, bucket)
        tokens = np.zeros(bucket, dtype=np.int32)
        tokens[:b] = [(s.generated or s.prompt)[-1] for s in seqs]
        positions = np.zeros(bucket, dtype=np.int32)
        positions[:b] = [s.cached_len for s in seqs]
        # Trim the block tables to the batch's actual max page count
        # (bucketed): the reference gather then reads O(batch max
        # context), not O(longest-ever sequence).
        P = _bucket_for(cache.table_width(rows), self.page_buckets)
        newest = positions[:b] + (depth - 1)
        fields["window_pages_released"] += cache.slide_rows(
            ids, rows[:b], positions[:b], newest + 1)
        written = positions if depth == 1 else \
            positions[:, None] + np.arange(depth, dtype=np.int32)
        dests = self._by_kind(lambda kind: cache.slots(rows, written, kind))
        # What the paged kernel must read: the newest position's context.
        live_pages = int(cache.pages_read(newest).sum())
        fields.update(decodes=b, bucket=bucket, table_width=P,
                      live_pages=live_pages, live_pages_full=live_pages)
        if self._two_kinds:
            fields["live_pages_window"] += int(
                cache.pages_read(newest, 1).sum())
        self._count_chosen(written[:b])
        return (ids, bucket, P, tokens, positions, dests,
                self._batch_tables(seqs, ids, rows, P))

    def _tokens_ahead(self, before: _Flight, seqs: List[Sequence],
                      known: np.ndarray):
        """The tokens of a decode over ``seqs`` from the ids ``before``
        has in flight, on the device: the ids themselves where the batch
        is that step's row for row, else gathered a row from the row the
        sequence had there; a sequence that was not in it (it has just
        been prefilled) and a padding row keep the host's ``known``
        (whatever id the sampler gave a padding row in flight does as
        well: it writes the scratch page and counts in nothing)."""
        if self._same_batch(before.seqs, seqs):
            return before.ids
        self.recorder.open.fields["carried"] = 1
        row = {id(seq): i for i, seq in enumerate(before.seqs)}
        source = np.full(len(known), -1, dtype=np.int32)
        source[:len(seqs)] = [row.get(id(seq), -1) for seq in seqs]
        return self._carry_fn(before.ids, *self._hand((source, known)))

    def _run_decode(self, seqs: List[Sequence],
                    out: List[StepOutput]) -> int:
        """Dispatch the decode over ``seqs`` and its sampler, leave them
        in flight, and only then fetch and emit the step that was: this
        decode goes out *ahead* of that fetch, its tokens the ids in
        flight (:meth:`_tokens_ahead`). With none in flight (an idle
        engine's first decode, the one after a drain) the tokens are the
        host's. Returns the rows the fetched step decoded."""
        if self._drafting is not None:
            return self._run_verify(seqs, out)
        recorder = self.recorder
        fields = recorder.open.fields
        before = self._flight
        with recorder.phase("infer.decode") as dec:
            with recorder.phase("infer.decode.launch") as launch:
                put = self._host_puts
                ids, bucket, P, tokens, positions, dests, tables = \
                    self._decode_inputs(seqs, 1)
                if before is None:
                    # One form of ``tokens`` for the program's cache, the
                    # one the ids in flight have: an array on the device.
                    tokens = self._put(tokens)
                    self._decodes_drained += 1
                else:
                    tokens = self._tokens_ahead(before, seqs, tokens)
                    fields["ahead"] = 1
                    self._decodes_ahead += 1
                dec.attrs.update(batch=len(seqs), bucket=bucket)
                # The small rows go over with the call; the positions,
                # which the sampler behind it takes too, are put once.
                seats, dests, context_lens = self._hand(
                    (self._seats(ids, bucket), dests, positions + 1))
                inputs = (tokens, self._put(positions), dests, tables,
                          context_lens)
                logits, experts = self._call(self._decode_fn, seats, *inputs)
                for count in experts:
                    # Asked for now, it comes back beside the ids; left
                    # to the wait it is a transfer of its own, 0.5 ms.
                    count.copy_to_host_async()
                # The writes are dispatched: what the scheduler and the
                # next launch count from.
                for seq in seqs:
                    seq.cached_len += 1
                    seq.in_flight += 1
                self._dispatched += 1
                fields["dispatched"] = self._dispatched
                fields["host_puts"] = self._host_puts - put
            flops = None
            if profiling_enabled():
                # FLOPs from XLA's own cost model, computed once per
                # (batch bucket x table width) program — lower() reuses
                # the jit cache, so this never triggers a second compile.
                cache = self.cache
                flops = step_profiler("infer").ensure_flops(
                    ("decode", bucket, P),
                    lambda: cost_analysis_flops(
                        self._decode_fn, self._params, cache.k, cache.v,
                        *((cache.state, seats) if cache.state else ()),
                        *inputs))
            with recorder.phase("infer.decode.wait", cpu="wait_cpu_s"):
                # The chip is on the decode, or still on the one before.
                # The sampler goes out behind it, over the logits where
                # they lie and the positions the decode was given; the
                # requests' own rows are put again only when the batch's
                # membership has changed.
                rows, stochastic = self._batch_rows(seqs, bucket)
                sampled = self._sample_fn(logits, *rows, inputs[1])
                sampled.copy_to_host_async()
                fields["sampled_stochastic"] += stochastic
                if str(bucket) not in self._carry_compiles:
                    # The bucket's first decode enters its hand-over too:
                    # traffic that has warmed a decode program has warmed
                    # what follows it when the batch moves.
                    self._carry_fn(sampled, *self._hand((
                        np.arange(bucket, dtype=np.int32),
                        np.zeros(bucket, dtype=np.int32))))
                self._flight = _Flight(
                    self._dispatched, list(seqs), sampled, experts,
                    ("_decode", f"{bucket}x{P}"), launch.t0, flops)
                # The host blocked on the device and on the copy back of
                # the step before, after whatever its owner has for it
                # meanwhile.
                fetched = self._fetch(before)
            with recorder.phase("infer.decode.sample"):
                return self._give(before, fetched, out)

    def _drain(self, out: List[StepOutput]) -> int:
        """Fetch the decode in flight with none dispatched behind it,
        under the phases a decode step's fetch has. Returns the rows it
        decoded."""
        recorder = self.recorder
        flight, self._flight = self._flight, None
        with recorder.phase("infer.decode"):
            with recorder.phase("infer.decode.wait", cpu="wait_cpu_s"):
                tokens = self._fetch(flight)
            with recorder.phase("infer.decode.sample"):
                return self._give(flight, tokens, out)

    def _fetch(self, flight: Optional[_Flight]) -> List[int]:
        """Inside ``infer.decode.wait``: the owner's ``on_launch``, then
        the block on ``flight``'s ids and on a routed model's counts,
        which go into the record open now beside the decode's ordinal
        (``fetched``): the step under whose span that decode's kernels
        mostly ran, a call after its own."""
        if self.on_launch is not None:
            self.on_launch()
        if flight is None:
            return []
        ids = np.asarray(flight.ids)
        self.recorder.open.fields["fetched"] = flight.n
        self._count_experts(flight.experts, flight.program)
        now = time.perf_counter()
        if profiling_enabled() and flight.flops is not None:
            # From one fetch to the next, or from its launch where the
            # chip had been let idle before it: the real step.
            prof = step_profiler("infer")
            prof.observe_step(now - max(flight.launched, self._fetched_at),
                              flops=flight.flops)
            self._hbm_tick += 1
            if self._hbm_tick % 32 == 1:
                prof.observe_hbm()
        self._fetched_at = now
        return ids.tolist()

    def _give(self, flight: Optional[_Flight], tokens: List[int],
              out: List[StepOutput]) -> int:
        """Inside ``infer.decode.sample``: emit what ``flight`` sampled, a
        token a sequence. The row of a sequence that has ended since its
        dispatch (a stop token a step before, an ``abort``) is dropped
        and counted. Returns the rows it decoded, as a routed model's
        counts hold them: the dropped among them."""
        if flight is None:
            return 0
        dropped = 0
        for seq, token in zip(flight.seqs, tokens):
            seq.in_flight -= 1
            if seq.state == RUNNING:
                self._emit(seq, token, out)
            else:
                dropped += 1
        self.recorder.open.fields["ahead_rows_dropped"] += dropped
        self._ahead_rows_dropped += dropped
        return len(flight.seqs)

    def _run_verify(self, seqs: List[Sequence],
                    out: List[StepOutput]) -> int:
        """The decode step of a model that drafts for itself: two
        positions a sequence, the token it stands at and its draft, and
        one token or two out. Returns the tokens emitted."""
        recorder = self.recorder
        fields = recorder.open.fields
        with recorder.phase("infer.decode") as dec:
            with recorder.phase("infer.decode.launch"):
                put = self._host_puts
                # Both positions' rows: a padding row's go to the scratch
                # page's first two slots, and it names the scratch row of
                # the module's state.
                ids, bucket, P, tokens, positions, dests, tables = \
                    self._decode_inputs(seqs, 2)
                b = len(seqs)
                dec.attrs.update(batch=b, bucket=bucket)
                fields["drafted"] = b
                rows, stochastic = self._batch_rows(seqs, bucket)
                slots, tokens, positions, dests = self._put(
                    (self.cache.seats(ids, bucket), tokens, positions,
                     dests))
                state = self._draft_state
                with recorder.phase("infer.decode.verify"):
                    logits, ks, vs, count, hidden = self._decode_fn(
                        self._params, self.cache.k, self.cache.v, state,
                        slots, tokens, positions, dests, tables)
                with recorder.phase("infer.decode.accept"):
                    chosen, kept = self._accept_fn(
                        logits, state, slots, positions, *rows)
                    chosen.copy_to_host_async()
                with recorder.phase("infer.decode.draft"):
                    ks, vs, more, self._draft_state = self._draft_fn(
                        self._params, ks, vs, state, slots, hidden, chosen,
                        kept, positions, dests, tables, *rows)
                self.cache.k, self.cache.v = ks, vs
                for x in (count, more):
                    x.copy_to_host_async()
                # Nothing stays in flight: dispatched and fetched here.
                self._dispatched += 1
                fields["dispatched"] = fields["fetched"] = self._dispatched
                fields["host_puts"] = self._host_puts - put
            with recorder.phase("infer.decode.wait", cpu="wait_cpu_s"):
                # Every row draws twice (accept, then a token) and the
                # module once more; counted as the sampler's rows are.
                fields["sampled_stochastic"] += stochastic
                if self.on_launch is not None:
                    self.on_launch()
                chosen = np.asarray(chosen)
                key = f"{bucket}x{P}"
                self._count_experts((count, more), ("_decode", key),
                                    ("_draft", key))
            with recorder.phase("infer.decode.sample"):
                emitted = 0
                for seq, (first, second) in zip(seqs, chosen.tolist()):
                    seq.cached_len += 1
                    self._emit(seq, first, out)
                    emitted += 1
                    if second < 0:
                        continue
                    fields["accepted"] += 1
                    if seq.finish_reason is None:
                        # Kept, and the sequence had room for it.
                        seq.cached_len += 1
                        self._emit(seq, second, out)
                        emitted += 1
                fields["emitted"] = emitted
                self._drafted += b
                self._draft_accepted += fields["accepted"]
                _drafted_total.inc(b)
                _draft_accepted_total.inc(fields["accepted"])
        return emitted

    def _emit(self, seq: Sequence, token: int,
              out: List[StepOutput]) -> None:
        seq.generated.append(token)
        if len(seq.generated) == 1:
            t0 = self._arrival_ts.pop(seq.request_id, None)
            if t0 is not None:
                ttft = time.perf_counter() - t0
                _ttft_hist.observe(ttft)
                self._ttft_window.append(ttft)
            if task_events.request_events_enabled():
                task_events.emit_request(
                    seq.request_id,
                    task_events.RequestTransition.FIRST_TOKEN,
                    deployment=seq.deployment, tenant=seq.tenant)
        reason = None
        if token in seq.sampling.stop_token_ids:
            reason = "stop"
        elif len(seq.generated) >= seq.sampling.max_new_tokens:
            reason = "length"
        elif seq.num_tokens >= self.max_model_len:
            reason = "length"
        if reason is not None:
            self.scheduler.finish(seq, reason)
        out.append(StepOutput(request_id=seq.request_id, token_id=token,
                              finished=reason is not None,
                              finish_reason=reason))

    # ---- convenience + introspection --------------------------------

    def generate(self, prompts: SequenceT[SequenceT[int]],
                 sampling: Optional[SamplingParams] = None,
                 ) -> List[List[int]]:
        """Run a closed batch of prompts to completion; returns the
        generated token ids per prompt (continuously batched under the
        hood, but output-identical to one-at-a-time decoding)."""
        ids = [f"gen-{i}" for i in range(len(prompts))]
        for rid, prompt in zip(ids, prompts):
            self.add_request(rid, prompt, sampling)
        results: Dict[str, List[int]] = {rid: [] for rid in ids}
        while self.has_unfinished():
            for o in self.step():
                if o.request_id in results:
                    results[o.request_id].append(o.token_id)
        return [results[rid] for rid in ids]

    def note_idle(self) -> None:
        """Called by the stepping loop when there is no work: zero the
        throughput gauges so scrapes between bursts read true idle."""
        _prefill_tps_gauge.set(0.0)
        _decode_tps_gauge.set(0.0)
        _running_gauge.set(len(self.scheduler.running))
        _waiting_gauge.set(len(self.scheduler.waiting))
        _kv_util_gauge.set(self.cache.utilization())

    def ttft_quantile(self, q: float) -> float:
        """Recent-window TTFT quantile in seconds (0.0 when empty)."""
        if not self._ttft_window:
            return 0.0
        xs = sorted(self._ttft_window)
        return xs[min(len(xs) - 1, int(q * len(xs)))]

    def pressure(self) -> Dict[str, float]:
        """Load snapshot for engine-pressure autoscaling — plain floats
        so it crosses the serve wire untouched."""
        return {
            "waiting_requests": float(len(self.scheduler.waiting)),
            "running_requests": float(len(self.scheduler.running)),
            "kv_utilization": float(self.cache.utilization()),
            "ttft_p95_s": float(self.ttft_quantile(0.95)),
        }

    def step_log(self, since: float = 0.0) -> dict:
        """The steps that ended after ``since`` (seconds on the clock of
        the records' stamps, the process's monotonic performance
        counter), oldest first, as plain dicts under ``"steps"``:
        ``start``, ``end``, ``phases`` (``[name, t0, t1]`` in order of
        their start: ``infer.schedule``, ``infer.prefill`` or
        ``infer.prefill_chunk`` per prefill, ``infer.decode`` and inside
        it ``.launch`` (this step's decode built and dispatched),
        ``.wait`` (its sampler dispatched, the owner's ``on_launch``, the
        block on the ids of the decode *in flight*, the one the step
        before dispatched) and ``.sample`` (those ids emitted); a step
        that dispatches no decode and fetches one (the batch's last
        tokens) has an ``infer.decode`` of ``.wait`` and ``.sample``
        alone, as has one whose batch moved to another bucket before
        its own, and a preemption's drain one inside ``infer.schedule``;
        plus what the stepping
        loop put around the step), and what the step ran. A record
        says which decode each of its numbers is of: ``dispatched`` is
        the ordinal of the decode the step's launch dispatched (the
        engine's count of decodes dispatched, from 1; 0: it dispatched
        none) and ``fetched`` the ordinal of the decode whose ids its
        wait blocked on (0: it fetched none). A plain step fetches the
        decode the step before dispatched (``fetched == dispatched -
        1``); a drained step has ``fetched`` alone; a model that drafts
        for itself keeps nothing in flight (``dispatched == fetched``).
        Of decode ``dispatched`` are ``decodes``, ``bucket``,
        ``table_width``, ``live_pages*``, ``host_puts``,
        ``tables_reused``, ``dsa_rows_*`` (its share of them: a step's
        prefills add theirs), ``ahead`` (1 where it was dispatched
        before the ids of the step before were fetched, its tokens the
        ids in flight; 0 where it was built from the host's tokens, an
        idle engine's first decode and the one after a drain, or the
        step dispatched none) and ``carried`` (1 where those ids went
        through the hand-over program, ``_carry_fn``, because the batch
        had moved; 0 where they were taken as they lay or came from the
        host). Of decode ``fetched``, a call after its dispatch, are
        what comes back with a fetch: a routed model's ``moe_*`` counts
        (to which a step's prefills add theirs), ``emitted`` and
        ``ahead_rows_dropped`` (rows fetched for a sequence that had
        ended while they were in flight). The device's side of the
        pairing is the profiler's: one ``XLA Modules`` event an
        execution of ``jit__decode``, in the order of the ordinals.
        ``cpu_s`` is the stepping thread's CPU time over the step
        (``time.thread_time()`` at its open and close) and
        ``wait_cpu_s`` the part of it inside ``infer.decode.wait``;
        ``(end - start - the wait's seconds) - (cpu_s - wait_cpu_s)`` is
        the time the thread that feeds the chip was off the CPU outside
        the wait, blocked or without the interpreter. ``live_pages`` (pages its decode
        had to read of a sequence's whole context), ``live_pages_full``
        and ``live_pages_window`` (pages its decode read in one full
        layer, the same number, and in one window layer: the windows'
        spans; 0 for a model without window layers),
        ``window_pages_released`` (pages the window tables gave back in
        the step), ``pages_owned_full`` and ``pages_owned_window`` (pages
        sequences own in one pool of each kind when the step ends; both
        0 for a model without window layers), ``state_seats`` (sequences
        that hold a seat when the step ends: of a model whose layers keep
        a state, or that drafts for itself; else 0) and ``state_bytes``
        (what they hold in the state arrays: seats x
        ``PagedKVCache.state_bytes``), ``sampled_stochastic`` (rows
        whose token was drawn and not the argmax: how often the sampler's
        stochastic branch had work), for a model that drafts for itself
        ``drafted`` (drafts the decode verified: its sequences),
        ``accepted`` (drafts the verification kept) and ``emitted``
        (tokens the decode gave out: a sequence that finished on its
        first gives no second) and, inside ``infer.decode.launch``, the
        phases ``infer.decode.verify``, ``.accept`` and ``.draft`` (the
        three programs' launches; ``live_pages`` and ``live_pages_full``
        then count the pages of both positions' context in one full
        layer, and the module's pool is one more such layer),
        ``admitted``, ``compiled``
        (programs traced in it, the sampler's among them),
        ``preempted``, ``prefills`` (``request_id``, ``tokens``,
        ``bucket``, ``waited_s`` each) and ``error`` if it raised. A
        routed-expert model's steps also carry, over the step's programs,
        ``moe_assignments`` ((token, expert) pairs computed),
        ``moe_experts_touched`` (experts that received a token, summed
        over layers and programs) and ``moe_expert_max`` (the most
        tokens one expert of one layer received in one program), where
        the router has identity experts ``moe_zero_pairs`` and
        ``moe_pairs`` (live (token, choice) pairs that chose an identity
        expert, which costs no product, and all live pairs, held here or
        not) and
        ``moe_grouped_calls`` (expert products that went through
        ``ops.grouped_matmul``'s kernel and not ``ragged_dot``: two a
        routed layer of a decode program on a TPU); where
        the experts held here are a share of the router's, a pair whose
        expert is not held is in none of them (no row was computed for
        it). ``kv_bytes_per_token`` is what one token costs in the pools
        of every layer, as held. A model whose attention reads the rows
        an indexer chooses (``serving.indexer``) also carries
        ``dsa_rows_scored`` and ``dsa_rows_selected``: the cached
        positions one layer's indexers scored for the step's queries,
        decoded and prefilled, and those their attention then read.
        ``chunk_expanded``, where the family attended the step's chunk in
        another form than a decode row (``serving.chunk_parts``: a
        latent model's chunk over its break-even, expanded): the parts
        it took, the chunk's own rows one and one a segment of cached
        rows before them; absent where the chunk went as a decode row.
        ``"oldest_start"`` is the start of the oldest step still held,
        so a reader can tell a truncated log from a quiet engine.
        ``"pauses"`` holds what stopped this process's interpreter and
        ended after ``since``, on the steps' clock
        (:func:`raytpu.util.tracing.host_pauses`: ``["host.gc", t0, t1,
        {generation, collected, stepping}]`` a full or a long
        collection). Call
        it from the thread that steps, or under the lock that
        serialises ``step()``."""
        return self.recorder.log(since)

    def stats(self) -> dict:
        # Bucket keys as strings: the dict crosses the wire from serve
        # replicas and msgpack (strict_map_key) rejects int map keys.
        return {
            "prefill_compiles": {str(k): v for k, v
                                 in self._prefill_compiles.items()},
            "chunk_prefill_compiles": {str(k): v for k, v
                                       in self._chunk_compiles.items()},
            "decode_compiles": {str(k): v for k, v
                                in self._decode_compiles.items()},
            # The sampler's, by the shape of the logits it was given, and
            # the tokens' hand-over's, by the decode bucket.
            "sample_compiles": dict(self._sample_compiles),
            "carry_compiles": dict(self._carry_compiles),
            # Of a model that drafts for itself: the drafts verified so
            # far and kept (None: no drafting).
            "drafted_tokens": self._drafted if self._drafting else None,
            "draft_accepted": (self._draft_accepted if self._drafting
                               else None),
            # Of the steps the recorder's ring still holds.
            "decode_batch_hist": self.recorder.values("decodes"),
            # Block tables the decode steps put on the device, and those
            # they passed again as the device held them, over the kinds
            # of pool and the steps.
            "table_puts": self._table_puts,
            "table_reuses": self._table_reuses,
            # Decodes dispatched before the ids of the step before them
            # were fetched, those built from the host's tokens with none
            # in flight, and rows fetched for a sequence that had ended
            # while they were in flight (all 0 where the model drafts).
            "decodes_ahead": self._decodes_ahead,
            "decodes_drained": self._decodes_drained,
            "ahead_rows_dropped": self._ahead_rows_dropped,
            # Chunks the family attended expanded and the parts they
            # took (a record's ``chunk_expanded``, summed); 0 where every
            # chunk went as a decode row goes.
            "chunks_expanded": self._chunks_expanded,
            "chunk_segments_expanded": self._chunk_segments_expanded,
            "paged_attn_impl": self.paged_attn_impl,
            # Bytes of the tree the programs take, by dtype, over all
            # shards: all in the compute type but the norms' leaves.
            "param_bytes": dict(self._param_bytes),
            # Of those, the bytes of leaves held in another shape than
            # given (a table a step gathers rows from, its rows padded
            # to whole lane tiles); 0 where the copy is only a cast.
            "relaid_param_bytes": self._relaid_param_bytes,
            # Bytes of the 2 x layers KV pools, over all shards, and the
            # same by kind of layer (``window``: 0 without such layers).
            "kv_pool_bytes": self._kv_pool_bytes,
            "kv_pool_bytes_by_kind": dict(self._kv_pool_bytes_by_kind),
            # Of the layers that keep a state and no pool: the state
            # arrays' bytes; and the seats, of those or of a drafting
            # model's module (both 0 where no seat map is kept).
            "state_bytes": self._state_bytes,
            "state_seats": self.cache.seats_in_use(),
            "state_seats_total": self.cache.total_seats,
            "devices": list(self._devices),
            "num_preemptions": self.scheduler.num_preemptions,
            "running": len(self.scheduler.running),
            "waiting": len(self.scheduler.waiting),
            "kv_utilization": self.cache.utilization(),
            "prefill_tokens": self._prefill_tokens,
            "decode_tokens": self._decode_tokens,
            # Live tokens each expert of each layer has received,
            # [layers][experts]; None for a dense family.
            "expert_tokens": (self._expert_tokens.tolist()
                              if self._expert_tokens is not None else None),
            # Of a router with identity experts: the live (token, choice)
            # pairs that chose one and all live pairs; None without.
            "moe_zero_pairs": (self._moe_pairs["moe_zero_pairs"]
                               if self._moe_pairs else None),
            "moe_pairs": (self._moe_pairs["moe_pairs"]
                          if self._moe_pairs else None),
            "ttft_p95_s": self.ttft_quantile(0.95),
            "prefix_cache": (self.prefix_cache.stats()
                             if self.prefix_cache else None),
        }
