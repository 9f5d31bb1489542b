"""Does the system still start on the chip?

Drives the main path once, through the entry points a user calls, with
GPT-2 124M at its published widths (``GPT2Config.small()``, seeded
random weights), in the one process that owns the chip(s):

1. *logits*: the same tokens through the kernel path (flash prefill,
   paged chunked prefill, paged decode) and through the repository's
   reference path, compared within ``LOGIT_TOL``.
2. *serve*: ``serve.run(LLMDeployment.bind(...))`` and a handful of
   concurrent ``generate.remote_streaming`` requests — one longer than
   ``prefill_chunk``, two sharing a multi-page prefix, enough overlap
   for a decode batch larger than one.
3. *train*: ``JaxTrainer(...).fit()`` for a few steps of
   ``make_train_step`` at batch 8 x 1024 with the default attention
   (flash forward and fused backward).
4. *window* (one chip): a small model with window layers among full ones
   (``WINDOW_MODEL``: Mellum2's block at a fifth of its hidden size)
   through ``InferenceEngine`` over two kinds of KV pool: a prompt three
   windows long in chunks, one a window and a half long whole, then
   decodes, every window sliding; kernel path against reference path.
   (Mellum2 itself against its plain reference at the published widths:
   ``chip_mellum.py``.)

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one process over the four-chip host:
                                      # tp=4 serving, fsdp=4 training
    python chip_smoke.py --replicas 4 # a head, a node with four chips, one
                                      # one-chip replica process per chip, and
                                      # this driver, which never imports JAX

It needs a TPU and says so otherwise; there is no CPU mode. Any failed
phase, stream or check raises, and the exit code is then not 0. The last
line of a passing run is one JSON object naming the device.
``tests/test_smoke.py`` calls the phases at ``tiny()`` size on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import threading
import time

import numpy as np

# Kernel path against reference path, same bf16 weights and tokens: the
# largest logit difference over the largest reference logit. The paths
# differ in that the kernels round the softmax weights to bf16 before the
# value product; twelve bf16 layers carry that to 1e-2 of the logit range
# (measured on the v5e, see PERF.md; 1e-7 in float32 on the CPU), so 5e-2
# leaves room without letting a wrong mask or a misplaced head through.
LOGIT_TOL = 5e-2

# The window phase's model has a routed layer (2 experts of 8 a token,
# weights renormalised): where the two paths' rounding makes a token
# choose another second expert, that row moves by more than rounding
# does. 0.033 and 0.047 measured on the v5e, in two runs (PERF.md
# section 6, PR 32: before the reference path was forced on the kernel
# path's tokens the two parted ways at a rounding and read 1.1). At
# Mellum2's size a window ignored reads 0.26 and more (chip_mellum.py).
WINDOW_LOGIT_TOL = 1e-1

ENGINE_OPTIONS = {"page_size": 16, "max_num_seqs": 8, "prefill_chunk": 64}
TRAIN_BATCH = 8
TRAIN_STEPS = 4
STREAM_TIMEOUT_S = 600.0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _mesh_context(mesh):
    import jax

    return jax.set_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()


def _mosaic_calls(jitted, *args) -> int:
    """Compiled Pallas kernels in the program ``jitted`` lowers to."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


# ---- phase 1: kernel path against reference path -------------------------


def logits_phase(model_config, *, page_size: int, chunk: int,
                 chips: int = 1, tol: float = LOGIT_TOL) -> dict:
    """Teacher-forced comparison at the model boundary the engine calls:
    a full prefill (flash), two sequences prefilled chunk by chunk
    through the paged cache (paged kernel, T > 1, the second chunk
    attending the first's pages), then one ragged decode step for both
    (paged kernel, T = 1), and one more over contexts that take the
    kernel through several blocks of pages: nearly all the positions
    the model has, a third of them, and one chunk, behind one table
    (``decode_long``: a long row, a short one's dead tail). With
    ``chips > 1`` both paths run sharded as
    ``InferenceEngine(tp=chips)`` shards them, and the kernel engine is
    checked for spread: quarter pool shards, no all-gather of a pool in
    its compiled decode."""
    import jax

    from raytpu.inference import InferenceEngine
    from raytpu.models.gpt2 import (GPT2, gpt2_prefill, gpt2_step,
                                    init_params)
    from raytpu.parallel.sharding import shard_params

    cfg = model_config
    reference = dataclasses.replace(cfg, attn_impl="reference",
                                    paged_attn="reference")
    params = init_params(GPT2(cfg), cfg, seed=0, batch=1)
    rng = np.random.default_rng(0)
    chunks = cfg.block_size // chunk - 1  # 960 tokens of GPT-2's 1024
    # Ragged contexts at decode: a and b, then c, d and b.
    lens = {"a": 2 * chunk, "b": chunk, "c": chunks * chunk,
            "d": max(chunks // 3, 1) * chunk}
    toks = {sid: rng.integers(0, cfg.vocab_size, size=n + 1, dtype=np.int32)
            for sid, n in lens.items()}

    facts: dict = {"mosaic_calls": {}}
    logits = {}
    for name, c in (("kernel", cfg), ("reference", reference)):
        eng = InferenceEngine(c, params, page_size=page_size,
                              max_num_seqs=2, prefill_chunk=chunk,
                              tp=chips)
        cache = eng.cache
        p = params if eng.mesh is None else shard_params(params, eng.mesh)
        prefill = jax.jit(functools.partial(gpt2_prefill, c))
        # The family's one paged entry point, over [B, T] positions: a
        # chunk at [1, T], a decode step at [B, 1].
        step = jax.jit(functools.partial(gpt2_step, c))
        out = logits[name] = {}
        with _mesh_context(eng.mesh):
            # A whole prompt of one chunk, written to pages of its own.
            cache.allocate("whole", chunk + 1)
            pargs = (p, toks["a"][None, :chunk],
                     cache.prefill_dests("whole", chunk, chunk),
                     cache.k, cache.v)
            out["prefill"], cache.k, cache.v = prefill(*pargs)
            def prefill_in_chunks(sid):
                cache.allocate(sid, lens[sid] + 1)
                for start in range(0, lens[sid], chunk):
                    args = (p, toks[sid][None, start:start + chunk],
                            np.arange(start, start + chunk,
                                      dtype=np.int32)[None],
                            cache.chunk_dests(sid, start, chunk, chunk)[None],
                            cache.table_array(
                                [sid], cache.num_seq_pages(sid)),
                            cache.k, cache.v)
                    last, cache.k, cache.v = step(*args)
                return args, last

            def decode_args(sids):
                pos = np.asarray([lens[sid] for sid in sids], np.int32)
                return (p, np.asarray([[toks[sid][lens[sid]]] for sid in sids],
                                      np.int32),
                        pos[:, None],
                        np.asarray([[cache.slot(sid, lens[sid])]
                                    for sid in sids], np.int32),
                        cache.table_array(
                            sids, max(map(cache.num_seq_pages, sids))),
                        cache.k, cache.v)

            # b then a, so the last chunk is a's second: the one that
            # attends pages an earlier chunk wrote.
            prefill_in_chunks("b")
            args, out["chunk"] = prefill_in_chunks("a")
            dargs = decode_args(["a", "b"])
            out["decode"] = step(*dargs)[0][:, 0]
            for sid in ("whole", "a"):  # room for the long ones
                cache.free(sid)
            for sid in ("c", "d"):
                prefill_in_chunks(sid)
            out["decode_long"] = step(
                *decode_args(["c", "d", "b"]))[0][:, 0]
            if name == "kernel":
                facts["mosaic_calls"] = {
                    "prefill": _mosaic_calls(prefill, *pargs),
                    "chunk": _mosaic_calls(step, *args),
                    "decode": _mosaic_calls(step, *dargs)}
                if chips > 1:
                    facts["spread"] = _spread_facts(eng, cfg, chips)
        out.update({k: np.asarray(v, np.float32) for k, v in out.items()})
        del eng, cache

    facts["rel_err"] = {}
    for step, ref in logits["reference"].items():
        got = logits["kernel"][step]
        check(got.shape == ref.shape and got.shape[-1] == cfg.vocab_size,
              f"{step} logits shape {got.shape} vs reference {ref.shape}")
        check(bool(np.isfinite(got).all()), f"{step} logits not finite")
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        facts["rel_err"][step] = err
        check(err <= tol, f"{step} logits differ from the reference path "
              f"by {err:.3g} of the logit range (tolerance {tol})")
    return facts


def window_model():
    """Mellum2's block, small: 8 query heads on 2 kv heads of 128 over a
    hidden size of 512 (not 8 x 128), one period S S S F with a window
    of 256, YaRN on the full layer, 8 experts of 256 of which a token
    takes 2."""
    from raytpu.models.llama import Rope
    from raytpu.models.mixtral import MellumConfig

    return MellumConfig(
        vocab_size=8192, block_size=2048, n_layer=4, n_head=8, n_kv_head=2,
        n_embd=512, head_dim=128, n_inter=256, n_expert=8,
        n_expert_per_tok=2, window=256,
        full_rope=Rope(theta=500000.0, yarn_factor=4.0,
                       original_max_position=512))


def window_phase(model_config, *, page_size: int, chunk: int,
                 tol: float = WINDOW_LOGIT_TOL) -> dict:
    """Window layers behind their own kind of pool, kernels against
    references: a prompt of three windows and a bit (chunked: the paged
    kernel at ``T > 1`` from the window's first page), one of a window
    and a half (whole: the windowed flash forward, its rows left of the
    window written to scratch), then eight decodes of both, each of the
    last row's and the decodes' logits compared within ``tol``."""
    from chip_mellum import DECODES, served_rows
    from raytpu.models.mixtral import Mixtral, init_params

    cfg = model_config
    window = cfg.window
    check(chunk >= window + window // 2, "the whole prompt has to be "
          "longer than the window")
    params = init_params(Mixtral(cfg), cfg, seed=0, batch=1)
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
               for n in (3 * window + 5, window + window // 2)]
    options = dict(page_size=page_size, max_num_seqs=2,
                   max_model_len=4 * window + 2 * DECODES,
                   prefill_chunk=chunk)
    reference = dataclasses.replace(cfg, attn_impl="reference",
                                    paged_attn="reference")
    got, sampled, facts = served_rows(cfg, params, prompts, options)
    facts["prompt_tokens"] = [len(p) for p in prompts]
    check(facts["window_pages_released"] > 0
          and bool(facts["programs"]["chunk_prefill_compiles"])
          and bool(facts["programs"]["prefill_compiles"]),
          f"the phase did not slide a window through both prefill "
          f"programs: {facts}")
    # The reference path is forced on the tokens the kernel path sampled
    # (a seeded model's next token turns on a rounding): each prompt and
    # its decoded tokens as one prompt, judged on its last rows.
    forced = [p + s[:DECODES] for p, s in zip(prompts, sampled)]
    want, _, _ = served_rows(reference, params, forced, options,
                             tail=DECODES + 1, new_tokens=1)
    facts["rel_err"] = {}
    for prompt, rows, ref in zip(prompts, got, want):
        check(bool(np.isfinite(rows).all()),
              f"prompt of {len(prompt)}: logits not finite")
        err = float(np.abs(rows - ref).max() / np.abs(ref).max())
        facts["rel_err"][f"prompt_{len(prompt)}"] = err
        check(err <= tol, f"prompt of {len(prompt)}: window-layer logits "
              f"differ from the reference path by {err:.3g} of the logit "
              f"range (tolerance {tol})")
    return facts


def _spread_facts(eng, cfg, chips: int) -> dict:
    """Is the tensor-parallel engine's work spread over its chips?"""
    from raytpu.inference import SamplingParams

    shards = eng.cache.k[0].addressable_shards
    head_dim = cfg.n_embd // cfg.n_head  # a pool row is heads x head_dim
    heads = sorted(s.data.shape[2] // head_dim for s in shards)
    check(len({s.device for s in shards}) == chips
          and heads == [cfg.n_head // chips] * chips,
          f"KV pool shards hold {heads} heads on "
          f"{len({s.device for s in shards})} devices, want "
          f"{cfg.n_head // chips} on each of {chips}")
    # The engine's own compiled decode: capture the arguments of a real
    # step (their shapes: the call consumes the pools) and read the
    # program it ran back from the jit cache.
    import jax

    calls = []
    decode = eng._decode_fn

    def shapes(args):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding), args)

    eng._decode_fn = lambda *a: calls.append(shapes(a)) or decode(*a)
    eng.generate([[1, 2, 3, 4, 5], [6, 7, 8]],
                 SamplingParams(max_new_tokens=2))
    eng._decode_fn = decode
    hlo = decode.lower(*calls[0]).compile().as_text()
    pool = "[%d,%d," % eng.cache.k[0].shape[:2]
    gathers = [ln.strip()[:160] for ln in hlo.splitlines()
               if "all-gather" in ln and pool in ln]
    check(not gathers, f"compiled decode all-gathers a KV pool: {gathers}")
    return {"pool_shard_heads": heads,
            "decode_all_reduces": hlo.count("all-reduce("),
            "decode_pool_all_gathers": 0}


# ---- phase 2: serving ----------------------------------------------------


def serve_phase(model_config, engine_options: dict, *, new_tokens: int = 12,
                expect_impl: str = "tpu", chips: int = 1) -> dict:
    """Deploy ``LLMDeployment`` on the running fabric and stream a mixed
    handful of concurrent requests; every stream must finish with
    ``new_tokens`` tokens."""
    from raytpu import serve

    page = engine_options["page_size"]
    chunk = engine_options["prefill_chunk"]
    rng = np.random.default_rng(1)

    def prompt(n):
        return [int(t) for t in rng.integers(1, model_config.vocab_size, n)]

    shared = prompt(3 * page)  # three full pages both "shared" requests open with
    prompts = {
        "short-a": prompt(11),
        "short-b": prompt(3),
        "long": prompt(3 * chunk + 5),  # chunked prefill, paged kernel at T > 1
        "shared-1": shared + prompt(5),
        "shared-2": shared + prompt(5),  # prefix-cache hit: prefills its tail only
    }
    t0 = time.perf_counter()
    app = serve.LLMDeployment.bind(
        model="gpt2", model_config=model_config,
        engine_options=dict(engine_options,
                            **({"tp": chips} if chips > 1 else {})),
        seed=0)
    handle = serve.run(app, name="chip-smoke", route_prefix=None,
                       wait_for_ready_timeout_s=STREAM_TIMEOUT_S)
    try:
        before = handle.stats.remote().result()
        deploy_s = time.perf_counter() - t0
        streams: dict = {}
        failures: dict = {}
        started = {name: threading.Event() for name in prompts}

        def consume(name):
            try:
                got = []
                for tok in handle.generate.remote_streaming(
                        prompts[name], max_new_tokens=new_tokens):
                    got.append(int(tok))
                    started[name].set()
                streams[name] = got
            except BaseException as e:  # re-raised on the main thread
                failures[name] = e
            finally:
                started[name].set()

        threads = {name: threading.Thread(target=consume, args=(name,),
                                          name=f"stream-{name}")
                   for name in prompts}
        t0 = time.perf_counter()
        for name, th in threads.items():
            if name != "shared-2":
                th.start()
        # shared-1's first token means its prompt pages are registered.
        check(started["shared-1"].wait(STREAM_TIMEOUT_S),
              "shared-1 produced no token in time")
        threads["shared-2"].start()
        for name, th in threads.items():
            th.join(STREAM_TIMEOUT_S)
            check(not th.is_alive(), f"stream {name} did not finish")
        streams_s = time.perf_counter() - t0
        for name, err in failures.items():
            raise RuntimeError(f"stream {name} failed") from err
        stats = handle.stats.remote().result()
    finally:
        serve.shutdown()

    for name, got in streams.items():
        check(len(got) == new_tokens
              and all(0 <= t < model_config.vocab_size for t in got),
              f"stream {name} returned {len(got)} of {new_tokens} tokens: "
              f"{got}")
    check(stats["paged_attn_impl"] == expect_impl,
          f"paged attention resolved to {stats['paged_attn_impl']!r}, "
          f"not {expect_impl!r}")
    check(max(stats["decode_batch_hist"]) > 1,
          f"no decode batch larger than one: {stats['decode_batch_hist']}")
    check(any(k.startswith(f"{chunk}x")
              for k in stats["chunk_prefill_compiles"]),
          f"no chunked prefill at T={chunk}: "
          f"{stats['chunk_prefill_compiles']}")
    hit = (stats["prefix_cache"]["hit_tokens"]
           - before["prefix_cache"]["hit_tokens"])
    check(hit >= len(shared),
          f"prefix cache hit {hit} tokens, want >= {len(shared)}")
    compiles = {k: stats[k] for k in ("prefill_compiles",
                                      "chunk_prefill_compiles",
                                      "decode_compiles")}
    check(all(n == 1 for d in compiles.values() for n in d.values()),
          f"a bucket compiled more than once: {compiles}")
    check(len(stats["devices"]) == chips,
          f"engine on {stats['devices']}, want {chips} device(s)")
    return {"deploy_s": round(deploy_s, 1),
            "streams_s": round(streams_s, 1),
            "streams": {k: len(v) for k, v in streams.items()},
            "compiles": compiles,
            "max_decode_batch": max(stats["decode_batch_hist"]),
            "prefix_hit_tokens": hit,
            "paged_attn_impl": stats["paged_attn_impl"],
            "devices": stats["devices"]}


# ---- phase 3: training ---------------------------------------------------


def _train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from raytpu import train
    from raytpu.models.gpt2 import GPT2, init_params, make_train_step
    from raytpu.parallel import build_mesh, shard_batch, shard_params
    from raytpu.parallel.sharding import tree_shardings

    cfg = config["model_config"]
    model = GPT2(cfg)
    params = init_params(model, cfg, seed=0, batch=1)
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (config["batch"], cfg.block_size), 0,
        cfg.vocab_size, jnp.int32)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    mesh, shardings = None, {}
    if config["chips"] > 1:
        mesh = build_mesh({"fsdp": config["chips"]},
                          jax.devices()[:config["chips"]])
        params = shard_params(params, mesh)
        tokens = shard_batch(tokens, mesh)
    opt_state = opt.init(params)
    if mesh is not None:
        # The whole state on the mesh before the first step (the step
        # counter is born on one device), and what comes out pinned to
        # what went in: otherwise the second step sees other layouts
        # than the first and compiles again.
        state = (tree_shardings(params, mesh),
                 tree_shardings(opt_state, mesh))
        opt_state = jax.device_put(opt_state, state[1])
        shardings = {"out_shardings": state + (None,)}
    train_step = make_train_step(model, opt)
    traces = []  # Python runs the body once per trace, i.e. per compile

    def counted_step(*args):
        traces.append(1)
        return train_step(*args)

    step = jax.jit(counted_step, donate_argnums=(0, 1), **shardings)
    with _mesh_context(mesh):
        facts = {"mosaic_calls": _mosaic_calls(step, params, opt_state,
                                               tokens)}
        held: dict = {}
        for leaf in jax.tree.leaves(params):
            for s in leaf.addressable_shards:
                held[str(s.device)] = held.get(str(s.device), 0) \
                    + s.data.nbytes
        facts["param_bytes_per_device"] = held
        for i in range(config["steps"]):
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, tokens)
            loss = float(loss)  # host fetch: the step has finished
            # One trace went to _mosaic_calls' lowering, one to the
            # first step; every later one is a recompile.
            train.report({"step": i, "loss": loss, "traces": len(traces),
                          "step_s": time.perf_counter() - t0, **facts})


def train_phase(model_config, *, batch: int = TRAIN_BATCH,
                steps: int = TRAIN_STEPS, chips: int = 1,
                expect_kernels: bool = True) -> dict:
    """``JaxTrainer.fit()`` over ``make_train_step`` on the running
    fabric; with ``chips > 1`` params and batch are sharded over an
    ``fsdp`` mesh of all of them."""
    from raytpu.train import JaxTrainer, ScalingConfig

    t0 = time.perf_counter()
    result = JaxTrainer(
        _train_loop,
        train_loop_config={
            "model_config": model_config, "batch": batch, "steps": steps,
            "chips": chips},
        scaling_config=ScalingConfig(num_workers=1),
    ).fit()
    if result.error is not None:
        raise RuntimeError("training failed") from result.error
    history = result.metrics_history
    losses = [m["loss"] for m in history]
    check(len(losses) == steps and bool(np.isfinite(losses).all()),
          f"want {steps} finite losses, got {losses}")
    last = history[-1]
    check(last["traces"] == history[0]["traces"],
          f"the train step compiled again after its first step: "
          f"{[m['traces'] for m in history]} traces")
    held = last["param_bytes_per_device"]
    check(len(held) == chips and max(held.values())
          <= 1.5 * sum(held.values()) / chips,
          f"params not spread over {chips} device(s): {held}")
    if expect_kernels:
        # The flash forward and both backward kernels, in the scanned block.
        check(last["mosaic_calls"] >= 3,
              f"train step holds {last['mosaic_calls']} compiled Pallas "
              f"kernels, want the flash forward, dq and dk/dv")
    return {"fit_s": round(time.perf_counter() - t0, 1),
            "first_step_s": round(history[0]["step_s"], 1),
            "later_step_s": [round(m["step_s"], 3) for m in history[1:]],
            "losses": [round(x, 4) for x in losses],
            "mosaic_calls": last["mosaic_calls"],
            "param_bytes_per_device": held}


# ---- one replica process per chip ----------------------------------------


def replicas_phase(replicas: int, engine_options: dict, *,
                   model_config: dict, new_tokens: int = 8,
                   expect_platform: str = "tpu") -> dict:
    """A head, one node with ``replicas`` chips, and ``replicas``
    one-chip ``LLMDeployment`` replica processes. This driver must never
    import JAX: it would take a chip from the replica that leased it."""
    import raytpu
    from raytpu import serve
    from raytpu.cluster.cluster_utils import Cluster

    cluster = Cluster(num_nodes=0)
    try:
        cluster.add_node(num_cpus=2 * replicas, num_tpus=replicas)
        cluster.wait_for_nodes()
        raytpu.init(address=cluster.address)
        app = serve.LLMDeployment.options(
            num_replicas=replicas, ray_actor_options={"num_tpus": 1},
        ).bind(model="gpt2", model_config=model_config,
               engine_options=engine_options, seed=0)
        handle = serve.run(app, name="chip-smoke", route_prefix=None,
                           wait_for_ready_timeout_s=STREAM_TIMEOUT_S)
        rng = np.random.default_rng(2)
        streams: dict = {}
        failures: dict = {}

        def consume(i):
            try:
                streams[i] = [int(t) for t in handle.generate.remote_streaming(
                    [int(t) for t in rng.integers(1, 500, 5 + i)],
                    max_new_tokens=new_tokens)]
            except BaseException as e:  # re-raised on the main thread
                failures[i] = e

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(4 * replicas)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(STREAM_TIMEOUT_S)
            check(not th.is_alive(), "a stream did not finish")
        for i, err in failures.items():
            raise RuntimeError(f"stream {i} failed") from err
        check(all(len(s) == new_tokens for s in streams.values()),
              f"short streams: {streams}")
        # The router picks replicas at random: ask until all have answered.
        seen: dict = {}
        for _ in range(50 * replicas):
            stats = handle.stats.remote().result()
            seen[stats["replica"]["pid"]] = {
                "chips": stats["replica"]["chips"],
                "devices": stats["devices"],
                "paged_attn_impl": stats["paged_attn_impl"],
                "decode_tokens": stats["decode_tokens"]}
            if len(seen) == replicas:
                break
    finally:
        try:
            serve.shutdown()
            raytpu.shutdown()
        finally:
            cluster.shutdown()
    check(len(seen) == replicas,
          f"{len(seen)} of {replicas} replica processes answered: {seen}")
    chips = sorted(r["chips"] for r in seen.values())
    check(chips == [str(i) for i in range(replicas)],
          f"replicas leased chips {chips}, want one distinct chip each")
    for pid, r in seen.items():
        check(len(r["devices"]) == 1
              and r["devices"][0].startswith(expect_platform + ":"),
              f"replica {pid} reports devices {r['devices']}, want exactly "
              f"one {expect_platform} device")
    check("jax" not in sys.modules, "the driver imported JAX")
    return {"replicas": seen}


# ---- command line --------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="chips this one process must find and use")
    ap.add_argument("--replicas", type=int, default=0,
                    help="instead: this many one-chip replica processes "
                         "behind a head and a node")
    args = ap.parse_args(argv)

    if args.replicas:
        facts = replicas_phase(args.replicas, ENGINE_OPTIONS, model_config={})
        log("replicas: " + json.dumps(facts["replicas"]))
        print(json.dumps({"ok": True, "replicas": len(facts["replicas"])}))
        return 0

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU and found none: "
                 f"jax.devices()[0].platform == {devices[0].platform!r}")
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke.py --chips {args.chips}: JAX reports "
                 f"{len(devices)} device(s)")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}

    import raytpu
    from raytpu.models.gpt2 import GPT2Config
    from raytpu.ops import resolve_flash_impl, resolve_paged_impl
    from raytpu.util import compile_cache

    cfg = GPT2Config.small()
    impls = {"flash": resolve_flash_impl(cfg.attn_impl),
             "paged": resolve_paged_impl(cfg.paged_attn)}
    log(f"device {json.dumps(device)}; model gpt2-124M {cfg.n_layer}x"
        f"{cfg.n_embd}, {cfg.n_head} heads, vocab {cfg.vocab_size}, "
        f"context {cfg.block_size}, {np.dtype(cfg.dtype).name}")
    log(f"kernels resolve to {json.dumps(impls)}; compile cache at "
        f"{compile_cache.enable()}")
    check(impls == {"flash": "tpu", "paged": "tpu"},
          f"a reference would stand in for a kernel: {impls}")

    summary = {"device": device, "model": "gpt2-124M", "impls": impls}
    raytpu.init()
    try:
        phases = (
            ("logits", functools.partial(
                logits_phase, cfg, page_size=ENGINE_OPTIONS["page_size"],
                chunk=ENGINE_OPTIONS["prefill_chunk"], chips=args.chips)),
            ("serve", functools.partial(
                serve_phase, cfg, ENGINE_OPTIONS, chips=args.chips)),
            ("train", functools.partial(
                train_phase, cfg, chips=args.chips)),
        ) + ((("window", functools.partial(
            window_phase, window_model(), page_size=128, chunk=512)),)
            if args.chips == 1 else ())
        for name, phase in phases:
            t0 = time.perf_counter()
            summary[name] = phase()
            summary[name]["phase_s"] = round(time.perf_counter() - t0, 1)
            log(f"{name}: {json.dumps(summary[name])}")
    finally:
        raytpu.shutdown()
    check(all(n > 0 for n in summary["logits"]["mosaic_calls"].values()),
          f"a serving program holds no compiled Pallas kernel: "
          f"{summary['logits']['mosaic_calls']}")
    log("summary: " + json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
