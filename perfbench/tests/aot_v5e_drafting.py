"""``aot_v5e_kinds.py`` for a cell whose model drafts for itself: compile
the five programs of its engine (the prompt's whole and chunked, the
verify step of two positions a sequence, accept/resample, the module's
draft) for the v5e without a chip and print the compiler's memory
analysis. Run by hand from the repository's root:

    JAX_PLATFORMS=cpu python3 perfbench/tests/aot_v5e_drafting.py kexaone-selfdraft-decode [width ...]

Table widths default to the engine's buckets of 4 columns and more; the
chunk program is compiled at the widest of them. Nothing runs; a program
that compiles here has not been shown to be right or fast.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import aot_v5e  # noqa: E402  (sets TPU_LOG_DIR and the path first)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from perfbench import run  # noqa: E402


def programs(cell, cfg, mix, device, widths):
    from raytpu.inference import InferenceEngine

    family = run.load_family([run.HERE], cfg)
    mcfg = family.program_config(
        cfg, dict(mix.get("model_overrides", ()), **aot_v5e.KERNELS))
    one = SingleDeviceSharding(device)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def like(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    eng = InferenceEngine(mcfg, like(jax.eval_shape(
        family.train_parts(mcfg)[0], jax.random.PRNGKey(0))),
        **mix["engine_options"])
    print(json.dumps({"kv_pool_bytes": eng.stats()["kv_pool_bytes_by_kind"],
                      "param_bytes": eng.stats()["param_bytes"],
                      "pools": sorted({a.shape for a in eng.cache.k})}),
          flush=True)
    params, state = eng._params, like(eng._draft_state)
    pools = [sds(a.shape, a.dtype) for a in eng.cache.k]
    kinds = len(eng.cache.kinds)
    vocab, hidden = mcfg.vocab_size, mcfg.n_embd

    def by_kind(shape):
        return sds(shape) if kinds == 1 else (sds(shape),) * kinds

    def rows(n):
        return (sds((n,), jnp.float32), sds((n,)), sds((n,), jnp.uint32))

    def timed(what, lowered):
        started = time.time()
        aot_v5e.report(f"{cell['name']}: {what}", lowered.compile(), started)

    widths = widths or [w for w in eng.page_buckets if w >= 4]
    for t in eng.prefill_buckets:
        drafted = (sds((1, t)), sds(()), sds(()), sds(()), *rows(1))
        timed(f"prefill {t}", eng._prefill_fn.lower(
            params, pools, pools, state, drafted, sds((1, t)),
            by_kind((t,))))
    for t in eng.chunk_buckets:
        w = max(widths)
        drafted = (sds((1, t)), sds(()), sds(()), sds(()), *rows(1))
        timed(f"chunk {t}x{w}", eng._chunk_fn.lower(
            params, pools, pools, state, drafted, sds((1, t)), sds((t,)),
            by_kind((t,)), by_kind((1, w))))
    for b in eng.decode_buckets:
        timed(f"accept {b}", eng._accept_fn.lower(
            sds((b, 2, vocab), jnp.float32), state, sds((b,)), sds((b,)),
            *rows(b)))
        for w in widths:
            timed(f"verify {b}x{w}", eng._decode_fn.lower(
                params, pools, pools, state, sds((b,)), sds((b,)),
                sds((b,)), by_kind((b, 2)), by_kind((b, w))))
            timed(f"draft {b}x{w}", eng._draft_fn.lower(
                params, pools, pools, state, sds((b,)),
                sds((b, 2, hidden), mcfg.dtype), sds((b, 2)), sds((b,)),
                sds((b,)), by_kind((b, 2)), by_kind((b, w)), *rows(b)))


def main(argv):
    with open(os.path.join(aot_v5e.ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == argv[0])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cfg = run.load_json([run.HERE], "configs", cell["config"])
    mix = run.load_json([run.HERE], "traffic", cell["traffic"])
    programs(cell, cfg, mix, topo.devices[0], [int(w) for w in argv[1:]])


if __name__ == "__main__":
    main(sys.argv[1:])
