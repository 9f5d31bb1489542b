"""``aot_v5e.py`` for a cell whose model has layers that keep a state and
no pool: compile the cell's programs for the v5e without a chip and print
the compiler's memory analysis. Run by hand from the repository's root:

    JAX_PLATFORMS=cpu python3 perfbench/tests/aot_v5e_state.py lfm2-hybrid-decode [width ...]

The engine's three programs of such a model take the state arrays and the
sequences' seats behind the pools (``(params, ks, vs, states, seats,
*inputs)``, the state arrays donated too), which ``aot_v5e.py`` does not
hand them and may not be edited to by the PR that brought this one. This
one reuses its ``report`` and ``KERNELS`` and compiles the whole-prompt,
chunk and decode programs and the sampler over a decode's logits. Table
widths default to the engine's buckets of 8 columns and more (a chunk's:
of 32 and more, the prompts longer than a chunk). Nothing runs; a program
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

    given = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            family.train_parts(mcfg)[0], jax.random.PRNGKey(0)))
    eng = InferenceEngine(mcfg, given, **mix["engine_options"])
    stats = eng.stats()
    print(json.dumps({"kv_pool_bytes": stats["kv_pool_bytes"],
                      "state_bytes": stats["state_bytes"],
                      "param_bytes": stats["param_bytes"],
                      "pools": len(eng.cache.k),
                      "state_arrays": sorted({a.shape
                                              for a in eng.cache.state})}),
          flush=True)
    params = eng._params
    pools = [sds(a.shape, a.dtype) for a in eng.cache.k]
    states = [sds(a.shape, a.dtype) for a in eng.cache.state]
    name = cell["name"]

    for t in eng.prefill_buckets:
        started = time.time()
        compiled = eng._prefill_fn.lower(
            params, pools, pools, states, sds((1,)), sds((1, t)),
            sds((t,))).compile()
        aot_v5e.report(f"{name}: prefill {t}", compiled, started)
    for w in widths or [w for w in eng.page_buckets if w >= 8]:
        for t in eng.chunk_buckets if w >= 32 or widths else ():
            started = time.time()
            compiled = eng._chunk_fn.lower(
                params, pools, pools, states, sds((1,)), sds((1, t)),
                sds((t,)), sds((t,)), sds((1, w))).compile()
            aot_v5e.report(f"{name}: chunk {t}x{w}", compiled, started)
        for b in eng.decode_buckets:
            started = time.time()
            compiled = eng._decode_fn.lower(
                params, pools, pools, states, sds((b,)), sds((b,)),
                sds((b,)), sds((b,)), sds((b, w)), sds((b,))).compile()
            aot_v5e.report(f"{name}: decode {b}x{w}", compiled, started)
    b, v = eng.decode_buckets[-1], mcfg.vocab_size
    started = time.time()
    compiled = eng._sample_fn.lower(
        sds((b, v), jnp.float32), sds((b,), jnp.float32), sds((b,)),
        sds((b,), jnp.uint32), sds((b,))).compile()
    aot_v5e.report(f"{name}: sample {b}x{v}", compiled, started)


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
