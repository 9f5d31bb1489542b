"""raytpu.inference — TPU-native LLM inference engine.

Reference analogues: vLLM's PagedAttention (SOSP '23) for KV-cache
memory management and Orca (OSDI '22) for iteration-level (continuous)
batching; Ray's Serve layer provides the replica/streaming transport
(``raytpu.serve``).

TPU twist running through every module: *static shapes everywhere*.
Prefill pads prompts to a small set of length buckets and decode pads
the batch to a fixed batch bucket, so XLA compiles ONE program per
bucket — never one per batch composition (recompiles cost tens of
seconds on TPU; padding costs microseconds — the same trade
``serve/batching.py``'s ``pad_batch_to_max`` already makes for
request batching).

Layout:

- :mod:`raytpu.inference.kv_cache` — paged KV cache: fixed-size pages
  preallocated as ``[num_pages, page_size, kv_heads * head_dim]`` JAX
  arrays (one per layer, written in place by the engine's programs,
  which are given them donated), per-sequence block tables with per-page
  refcounts (shared prefix pages), allocate / allocate_shared /
  extend / free, utilization accounting. Decode never reallocates. A
  model with window layers among full ones has two kinds of pool there
  and a block table a kind: a window layer's table slides, its pages
  left of the window given back (``slide``).
- :mod:`raytpu.inference.prefix_cache` — content-hash prompt-page
  cache: chained page hashes over token ids, retain-on-release of
  unreferenced prompt pages, LRU eviction under allocation pressure.
  A prefix hit turns a prefill into a block-table pointer copy.
- :mod:`raytpu.inference.scheduler` — Orca-style continuous-batching
  scheduler: admits waiting requests by KV-page budget each iteration
  (grafting prefix-cache hits), merges fresh prefills with in-flight
  decodes, preempts-to-recompute the youngest sequence under pressure.
- :mod:`raytpu.inference.sampling` — greedy / temperature / top-k
  sampling on the device, a row keyed by its request's seed and its own
  position, so sampled outputs are invariant to batch composition.
- :mod:`raytpu.inference.engine` — :class:`InferenceEngine`: bucketed
  static-shape prefill (full or chunked, interleaved with decodes),
  a single jit-compiled decode step, stop conditions, ``raytpu_infer_*``
  metrics (incl. TTFT) and ``infer.*`` tracing spans.
- :mod:`raytpu.inference.serving` — ``LLMDeployment``: a serve replica
  with a background stepping loop pumping the engine, streaming tokens
  through the existing ``ObjectRefGenerator`` path and exporting
  engine pressure for autoscaling.
"""

from raytpu.inference.kv_cache import PagedKVCache
from raytpu.inference.prefix_cache import PrefixCache
from raytpu.inference.sampling import SamplingParams
from raytpu.inference.scheduler import Scheduler, Sequence
from raytpu.inference.engine import InferenceEngine, StepOutput

__all__ = [
    "InferenceEngine", "LLMDeployment", "PagedKVCache", "PrefixCache",
    "SamplingParams", "Scheduler", "Sequence", "StepOutput",
]


def __getattr__(name):
    # Lazy: serving pulls in raytpu.serve (controller/replica machinery);
    # engine-only users (benchmarks, tests) shouldn't pay for it.
    if name == "LLMDeployment":
        from raytpu.inference.serving import LLMDeployment

        return LLMDeployment
    raise AttributeError(name)
