"""Inference-engine micro-bench: tokens/s and decode-compile counts for
staggered mixed-length requests on a tiny CPU Llama.

What it measures (and why those numbers, not raw latency, are the
story on TPU):

- **decode tokens/s** under continuous batching: staggered arrivals
  with different prompt/output lengths share decode iterations, so
  throughput should sit well above 1/step-latency.
- **compile counts**: the whole run — arrivals joining mid-flight,
  sequences finishing at different times, batch composition changing
  every few iterations — must compile the decode step once per batch
  bucket and the prefill once per length bucket. On a real TPU each
  avoided recompile is tens of seconds; the count is the honest proxy
  this CPU bench can assert.

Prints one JSON line:
  {"metric": "infer_decode_tokens_per_s", "value": ...,
   "detail": {"decode_compiles": {...}, "prefill_compiles": {...}, ...}}

``--load`` instead runs the SERVING load bench: concurrent client
threads against a directly-instantiated ``LLMDeployment`` replica (the
background stepping loop pumps the engine), three scenarios —

- ``mixed_load``: concurrent mixed-length prompts; generated tokens/s
  and client-observed TTFT p50/p95.
- ``shared_system_prompt``: every prompt opens with the same 48-token
  system prefix (prefix cache warm) — later streams prefill only their
  tails, so TTFT collapses and prefilled tokens count the tails only.
- ``shared_system_prompt_cache_off``: the identical workload with
  ``enable_prefix_cache=False`` — every stream pays the full prefill;
  the p95-TTFT gap against the cached scenario is the headline.

Writes the scenario table to BENCH_r07.json at the repo root and prints
the same object as one JSON line.

``--load`` then runs the MULTI-REPLICA phase (BENCH_r19.json): two
replica deployments behind the real prefix-routing policy
(``serve._private.prefix_router``), 8x the single-replica stream count,
each stream sharing one of two 48-token system prompts. The same
workload runs twice — blind power-of-two routing vs prefix-cache-aware
routing — reporting aggregate generated tokens/s, client TTFT p50/p95,
and the cross-replica cache-hit rate (prefix-hit tokens / prompt
tokens). The headline is the on/off TTFT-p95 win and hit-rate gap.

``--decode-sweep`` runs the PAGED-ATTENTION decode sweep: single
decode-step latency and tokens/s vs context length {128..4096} x batch
{1, 8} on a tiny Llama, for three implementations —

- ``reference`` with TRIMMED block tables (the engine's default CPU
  path after r8: tables sliced to the batch's actual page count,
  bucketed);
- ``reference_untrimmed`` (pre-r8 behavior: every decode gathers the
  full ``P_max``-wide padded table — the longest-ever sequence tax);
- ``kernel`` (the Pallas paged-attention kernel, interpret mode on
  CPU — correctness-honest but interpreter-speed; on TPU the same
  code path is the fused in-place page reader).

Also records the interpret-kernel bf16 max-abs error against the fp32
reference (acceptance: <= 2e-2). Writes BENCH_r08.json at the repo
root and prints the same object as one JSON line.

Env: RAYTPU_INFER_BENCH_REQUESTS (default 6),
RAYTPU_INFER_BENCH_NEW_TOKENS (default 24),
RAYTPU_INFER_BENCH_STAGGER (iterations between arrivals, default 3),
RAYTPU_INFER_LOAD_STREAMS (load mode, default 8).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUM_REQUESTS = int(os.environ.get("RAYTPU_INFER_BENCH_REQUESTS", 6))
NEW_TOKENS = int(os.environ.get("RAYTPU_INFER_BENCH_NEW_TOKENS", 24))
STAGGER = int(os.environ.get("RAYTPU_INFER_BENCH_STAGGER", 3))


def _force_cpu() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass


def main() -> None:
    _force_cpu()
    import dataclasses

    import jax.numpy as jnp

    from raytpu.inference import InferenceEngine, SamplingParams
    from raytpu.models.llama import Llama, LlamaConfig, init_params

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                              attn_impl="reference", remat=False)
    params = init_params(Llama(cfg), cfg, seed=0, batch=1)
    engine = InferenceEngine(cfg, params, page_size=8,
                             max_num_seqs=NUM_REQUESTS, max_model_len=128)

    # Mixed prompt lengths spanning two prefill buckets.
    prompts = [list(range(1, 4 + 5 * (i % 4))) for i in range(NUM_REQUESTS)]
    sampling = SamplingParams(max_new_tokens=NEW_TOKENS)

    # Warm the compile caches (compiles are counted, not timed — the
    # timed region below is pure steady-state decode).
    engine.generate([prompts[0]], sampling)
    warm_stats = engine.stats()

    pending = list(enumerate(prompts))
    iters = 0
    t0 = time.perf_counter()
    while pending or engine.has_unfinished():
        if pending and iters % max(1, STAGGER) == 0:
            i, prompt = pending.pop(0)
            engine.add_request(f"bench-{i}", prompt, sampling)
        engine.step()
        iters += 1
    elapsed = time.perf_counter() - t0

    stats = engine.stats()
    decode_tokens = stats["decode_tokens"] - warm_stats["decode_tokens"]
    prefill_tokens = stats["prefill_tokens"] - warm_stats["prefill_tokens"]
    hist = stats["decode_batch_hist"][len(warm_stats["decode_batch_hist"]):]
    print(json.dumps({
        "metric": "infer_decode_tokens_per_s",
        "value": round(decode_tokens / max(elapsed, 1e-9), 2),
        "unit": "decode tokens/s, staggered mixed-length requests (tiny "
                "llama, CPU reference attention)",
        "detail": {
            "requests": NUM_REQUESTS,
            "new_tokens_per_request": NEW_TOKENS,
            "stagger_iters": STAGGER,
            "decode_tokens": decode_tokens,
            "prefill_tokens": prefill_tokens,
            "elapsed_s": round(elapsed, 3),
            "iterations": iters,
            "mean_decode_batch": round(sum(hist) / max(len(hist), 1), 2),
            "max_decode_batch": max(hist or [0]),
            "decode_compiles": stats["decode_compiles"],
            "prefill_compiles": stats["prefill_compiles"],
            "num_preemptions": stats["num_preemptions"],
            "note": "each decode bucket must show exactly 1 compile "
                    "across the whole churn of batch compositions",
        },
    }))


def _quantile(xs, q):
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def _run_load_scenario(name, prompts, *, enable_prefix_cache, new_tokens):
    """Fire all prompts concurrently at one fresh replica; measure
    generated tokens/s plus client-observed TTFT quantiles.

    The identical concurrent pass runs twice: the first (untimed) pass
    compiles every program the workload touches — prefill/chunk length
    buckets AND the decode batch buckets the growing batch walks
    through — and, when caching, leaves the shared prefix pages warm.
    The second pass is the measured steady state."""
    import threading

    from raytpu import serve

    dep = serve.LLMDeployment._target(engine_options={
        "page_size": 8, "max_num_seqs": len(prompts),
        "max_model_len": 128, "enable_prefix_cache": enable_prefix_cache})
    try:
        ttfts, counts = [], []

        def consume(prompt):
            t0 = time.perf_counter()
            gen = dep.generate(prompt, max_new_tokens=new_tokens)
            next(gen)
            ttfts.append(time.perf_counter() - t0)
            counts.append(1 + sum(1 for _ in gen))

        def one_pass():
            threads = [threading.Thread(target=consume, args=(p,))
                       for p in prompts]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.perf_counter() - t0

        one_pass()  # warm pass: compiles + prefix registration
        warm_prefill = dep.stats()["prefill_tokens"]
        ttfts, counts = [], []
        elapsed = one_pass()
        stats = dep.stats()
    finally:
        dep.shutdown()
    generated = sum(counts)
    out = {
        "scenario": name,
        "streams": len(prompts),
        "prefix_cache": enable_prefix_cache,
        "generated_tokens_per_s": round(generated / max(elapsed, 1e-9), 2),
        "ttft_p50_s": round(_quantile(ttfts, 0.5), 4),
        "ttft_p95_s": round(_quantile(ttfts, 0.95), 4),
        "prefill_tokens": stats["prefill_tokens"] - warm_prefill,
        "elapsed_s": round(elapsed, 3),
    }
    if stats["prefix_cache"]:
        out["prefix_hit_tokens"] = stats["prefix_cache"]["hit_tokens"]
    return out


def _run_multi_replica_phase(prefix_routing, *, replicas, streams,
                             new_tokens):
    """One A/B arm of the multi-replica phase: ``streams`` concurrent
    clients over ``replicas`` fresh deployments, routed client-side by
    the REAL prefix-routing policy (or blind power-of-two when off).

    Each stream shares one of two 48-token system prompts, so routing
    quality shows up directly as the cross-replica cache-hit rate: the
    aware policy keeps each system prompt's pages on one replica, the
    blind policy smears both prompts across both replicas and re-pays
    their prefill."""
    import random as random_mod
    import threading

    from raytpu import serve
    from raytpu.serve._private import prefix_router

    page_size = 8
    deps = [serve.LLMDeployment._target(engine_options={
        "page_size": page_size, "max_num_seqs": streams,
        "max_model_len": 128}) for _ in range(replicas)]
    rng = random_mod.Random(19)
    try:
        systems = [list(range(1, 49)), list(range(201, 249))]
        prompts = [systems[i % 2] + [300 + 3 * i, 301 + 3 * i, 302 + 3 * i]
                   for i in range(streams)]

        # Compile warm with SAME-length, disjoint-token prompts: jit
        # caches go hot, prefix caches stay cold for the measured pass.
        for dep in deps:
            list(dep.generate(list(range(400, 400 + len(prompts[0]))),
                              max_new_tokens=new_tokens))

        def qlen(dep):
            st = dep.stats()
            return st["running"] + st["waiting"]

        def choose(prompt):
            if prefix_routing:
                summaries = []
                for i, dep in enumerate(deps):
                    s = dep.prefix_summary()
                    summaries.append((f"r{i}", dep, s["digests"]))
                pick = prefix_router.select_replica(
                    prefix_router.prompt_digests(prompt, page_size),
                    summaries, qlen, 10 ** 9, rng)
                if pick is not None:
                    return pick
            a, b = rng.sample(deps, 2)
            return a if qlen(a) <= qlen(b) else b

        # Seed pass: one completed request per system prompt registers
        # its pages on the replica the policy picked, mirroring a warm
        # production fleet.
        for p in prompts[:2]:
            list(choose(p).generate(p, max_new_tokens=new_tokens))

        hit0 = sum(d.stats()["prefix_cache"]["hit_tokens"] for d in deps)
        pre0 = sum(d.stats()["prefill_tokens"] for d in deps)
        ttfts, counts = [], []
        lock = threading.Lock()

        def consume(dep, prompt):
            t0 = time.perf_counter()
            gen = dep.generate(prompt, max_new_tokens=new_tokens)
            next(gen)
            dt = time.perf_counter() - t0
            n = 1 + sum(1 for _ in gen)
            with lock:
                ttfts.append(dt)
                counts.append(n)

        measured = prompts[2:]
        threads = []
        t0 = time.perf_counter()
        for p in measured:
            th = threading.Thread(target=consume, args=(choose(p), p))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        elapsed = time.perf_counter() - t0

        hits = sum(d.stats()["prefix_cache"]["hit_tokens"]
                   for d in deps) - hit0
        prefills = sum(d.stats()["prefill_tokens"] for d in deps) - pre0
        prompt_tokens = sum(len(p) for p in measured)
        return {
            "prefix_routing": bool(prefix_routing),
            "replicas": replicas,
            "streams": len(measured),
            "generated_tokens_per_s": round(
                sum(counts) / max(elapsed, 1e-9), 2),
            "ttft_p50_s": round(_quantile(ttfts, 0.5), 4),
            "ttft_p95_s": round(_quantile(ttfts, 0.95), 4),
            # Fraction of prompt tokens whose prefill was skipped via a
            # cross-replica cache hit. Derived from prefill_tokens, not
            # the hit_tokens counter: blocked admissions re-run the
            # prefix match every step, so hit_tokens over-counts under
            # exactly the contention this phase creates.
            "cache_hit_rate": round(
                1.0 - prefills / max(prompt_tokens, 1), 3),
            "prefix_hit_tokens": hits,
            "prefill_tokens": prefills,
            "elapsed_s": round(elapsed, 3),
        }
    finally:
        for dep in deps:
            dep.shutdown()


def main_load() -> None:
    _force_cpu()
    streams = int(os.environ.get("RAYTPU_INFER_LOAD_STREAMS", 8))
    mixed = [list(range(1, 4 + 7 * (i % 4))) for i in range(streams)]
    system = list(range(1, 49))  # 48 toks = 6 full pages at page_size 8
    shared = [system + [100 + 3 * i, 101 + 3 * i, 102 + 3 * i]
              for i in range(streams)]
    scenarios = [
        _run_load_scenario("mixed_load", mixed,
                           enable_prefix_cache=True, new_tokens=NEW_TOKENS),
        _run_load_scenario("shared_system_prompt", shared,
                           enable_prefix_cache=True, new_tokens=NEW_TOKENS),
        _run_load_scenario("shared_system_prompt_cache_off", shared,
                           enable_prefix_cache=False,
                           new_tokens=NEW_TOKENS),
    ]
    on, off = scenarios[1], scenarios[2]
    result = {
        "metric": "infer_serving_load",
        "unit": "generated tokens/s + client TTFT quantiles per scenario "
                "(tiny llama, CPU reference attention, background "
                "stepping loop)",
        "scenarios": scenarios,
        "headline": {
            "shared_prefix_ttft_p95_speedup": round(
                off["ttft_p95_s"] / max(on["ttft_p95_s"], 1e-9), 2),
            "shared_prefix_prefill_tokens_saved":
                off["prefill_tokens"] - on["prefill_tokens"],
        },
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_r07.json"), "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))

    # Multi-replica phase: prefix-routing A/B at 8x the stream count.
    multi_streams = 8 * streams
    arms = {
        "routing_off": _run_multi_replica_phase(
            False, replicas=2, streams=multi_streams,
            new_tokens=NEW_TOKENS),
        "routing_on": _run_multi_replica_phase(
            True, replicas=2, streams=multi_streams,
            new_tokens=NEW_TOKENS),
    }
    off_arm, on_arm = arms["routing_off"], arms["routing_on"]
    multi = {
        "metric": "infer_multi_replica_load",
        "unit": "aggregate generated tokens/s + client TTFT quantiles + "
                "cross-replica prefix-cache hit rate, 2 replicas, "
                "client-side prefix_router policy A/B (tiny llama, CPU "
                "reference attention)",
        "arms": arms,
        "headline": {
            "prefix_routing_ttft_p95_win": round(
                off_arm["ttft_p95_s"] / max(on_arm["ttft_p95_s"], 1e-9),
                2),
            "cache_hit_rate_on": on_arm["cache_hit_rate"],
            "cache_hit_rate_off": off_arm["cache_hit_rate"],
            "prefill_tokens_saved":
                off_arm["prefill_tokens"] - on_arm["prefill_tokens"],
        },
    }
    with open(os.path.join(root, "BENCH_r19.json"), "w") as f:
        json.dump(multi, f, indent=2)
        f.write("\n")
    print(json.dumps(multi))


def _decode_once(fn, params, ks, vs, inputs):
    logits, _, _ = fn(params, *inputs, ks, vs)
    logits.block_until_ready()


def _time_decode(fn, params, ks, vs, inputs, reps):
    _decode_once(fn, params, ks, vs, inputs)  # compile + warm
    best = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _decode_once(fn, params, ks, vs, inputs)
        best.append(time.perf_counter() - t0)
    return sorted(best)[len(best) // 2]  # median


def main_decode_sweep() -> None:
    _force_cpu()
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raytpu.inference.engine import _bucket_for, _pow2_buckets
    from raytpu.models.llama import Llama, LlamaConfig, init_params
    from raytpu.models.llama import llama_step
    from raytpu.ops.paged_attention import (paged_attention,
                                            paged_attention_reference)

    contexts = [128, 256, 512, 1024, 2048, 4096]
    batches = [1, 8]
    page_size = 32
    max_model_len = contexts[-1] + page_size  # room for the new token
    p_max = -(-max_model_len // page_size)
    page_buckets = _pow2_buckets(1, p_max)
    reps = int(os.environ.get("RAYTPU_INFER_BENCH_REPS", 3))

    base = dataclasses.replace(
        LlamaConfig.tiny(), block_size=max_model_len,
        dtype=jnp.float32, attn_impl="reference", remat=False)
    params = init_params(Llama(base), base, seed=0, batch=1)
    kv, d = base.n_kv_head, base.head_dim
    rng = np.random.default_rng(0)

    def make_state(batch, ctx, width):
        """Synthetic page pool + per-seq tables/positions for a decode
        step at context ``ctx`` (the new token is slot ctx)."""
        pages_live = -(-(ctx + 1) // page_size)
        num_pages = batch * pages_live + 1
        ks = [jnp.asarray(rng.standard_normal(
            (num_pages, page_size, kv, d)) * 0.02, base.dtype)
            for _ in range(base.n_layer)]
        vs = [jnp.asarray(rng.standard_normal(
            (num_pages, page_size, kv, d)) * 0.02, base.dtype)
            for _ in range(base.n_layer)]
        tables = np.zeros((batch, width), np.int32)
        dests = np.zeros(batch, np.int32)
        for b in range(batch):
            pages = 1 + b * pages_live + np.arange(pages_live)
            tables[b, :pages_live] = pages
            dests[b] = pages[ctx // page_size] * page_size + ctx % page_size
        # One row a sequence: ``llama_step`` at [B, 1].
        tokens = np.ones((batch, 1), np.int32)
        positions = np.full((batch, 1), ctx, np.int32)
        return ks, vs, tuple(jnp.asarray(a) for a in (
            tokens, positions, dests[:, None], tables))

    def decode_fn(paged):
        cfg = dataclasses.replace(base, paged_attn=paged)
        return jax.jit(functools.partial(llama_step, cfg))

    rows = []
    for batch in batches:
        for ctx in contexts:
            width = _bucket_for(-(-(ctx + 1) // page_size), page_buckets)
            variants = {
                "reference": (decode_fn("reference"), width),
                "reference_untrimmed": (decode_fn("reference"), p_max),
                "kernel": (decode_fn("interpret"), width),
            }
            for name, (fn, w) in variants.items():
                ks, vs, inputs = make_state(batch, ctx, w)
                # The interpret-mode kernel runs seconds per step at
                # long context on CPU (per-grid-step interpreter
                # overhead — not representative of the TPU path); one
                # rep keeps the sweep bounded.
                dt = _time_decode(fn, params, ks, vs, inputs,
                                  1 if name == "kernel" else reps)
                rows.append({
                    "impl": name, "batch": batch, "context": ctx,
                    "table_width_pages": w,
                    "decode_step_ms": round(dt * 1e3, 3),
                    "tokens_per_s": round(batch / dt, 2),
                })
                print(f"# {name:>20s} b={batch} ctx={ctx:4d} "
                      f"width={w:3d} {dt * 1e3:8.2f} ms")

    # bf16 numerics: interpret kernel vs fp32 reference (acceptance
    # bar 2e-2).
    nb, nctx = 8, 1024
    npages = nb * (-(-(nctx + 1) // page_size)) + 1
    q16 = jnp.asarray(rng.standard_normal((nb, 1, base.n_head, d)),
                      jnp.bfloat16)
    k16 = jnp.asarray(rng.standard_normal((npages, page_size, kv, d)),
                      jnp.bfloat16)
    v16 = jnp.asarray(rng.standard_normal((npages, page_size, kv, d)),
                      jnp.bfloat16)
    bt = jnp.asarray(np.arange(1, npages).reshape(nb, -1), jnp.int32)
    pos = jnp.full((nb, 1), nctx, jnp.int32)
    ref = paged_attention_reference(
        q16.astype(jnp.float32), k16.astype(jnp.float32),
        v16.astype(jnp.float32), bt, pos, sm_scale=d ** -0.5)
    ker = paged_attention(q16, k16, v16, bt, pos, force="interpret")
    bf16_err = float(jnp.max(jnp.abs(
        ref - ker.astype(jnp.float32))))

    def _at(impl, batch, ctx):
        (r,) = [r for r in rows if r["impl"] == impl
                and r["batch"] == batch and r["context"] == ctx]
        return r

    result = {
        "metric": "infer_decode_sweep",
        "unit": "single decode-step latency (ms) and tokens/s vs "
                "context x batch; tiny llama fp32 on CPU; kernel rows "
                "are the Pallas paged-attention kernel in interpret "
                "mode (correctness proxy — the TPU path is the fused "
                "in-place reader)",
        "page_size": page_size,
        "max_model_len": max_model_len,
        "rows": rows,
        "kernel_bf16_max_abs_err": bf16_err,
        "kernel_bf16_err_bound": 2e-2,
        "headline": {
            # The trim win: short-context decode no longer pays the
            # longest-ever-sequence gather.
            "trim_speedup_ctx128_b8": round(
                _at("reference_untrimmed", 8, 128)["decode_step_ms"]
                / max(_at("reference", 8, 128)["decode_step_ms"], 1e-9),
                2),
            "trim_speedup_ctx512_b8": round(
                _at("reference_untrimmed", 8, 512)["decode_step_ms"]
                / max(_at("reference", 8, 512)["decode_step_ms"], 1e-9),
                2),
        },
    }
    assert bf16_err <= 2e-2, f"bf16 kernel error {bf16_err} > 2e-2"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_r08.json"), "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))


if __name__ == "__main__":
    if "--load" in sys.argv[1:]:
        main_load()
    elif "--decode-sweep" in sys.argv[1:]:
        main_decode_sweep()
    else:
        main()
