"""``aot_v5e.py`` for a cell whose model has one latent pool a layer:
compile the cell's programs for the v5e without a chip and print the
compiler's memory analysis, so that the cell's memory arithmetic is
checked before chip time is spent. Run by hand from the repository's root:

    JAX_PLATFORMS=cpu python3 perfbench/tests/aot_v5e_latent.py joyai-latent-decode [width ...]

``aot_v5e.py`` and ``aot_v5e_kinds.py`` hand every program a list of K
pools and a list of V pools; a latent-attention model's programs take one
pool a layer and an empty V list, so those files cannot lower them and
may not be edited by the PR that brought this one. This one reuses
``aot_v5e.report`` and ``KERNELS``. Table widths default to the engine's
buckets of 16 columns and more. Nothing runs; a program that compiles
here has not been shown to be right or fast.
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
    print(json.dumps({
        "kv_pool_bytes": stats["kv_pool_bytes"],
        "kv_bytes_per_token": eng.cache.token_bytes,
        "param_bytes": stats["param_bytes"],
        "pools": sorted({a.shape for a in eng.cache.k}),
        "v_pools": len(eng.cache.v)}), flush=True)
    params = eng._params
    pools = [sds(a.shape, a.dtype) for a in eng.cache.k]
    for t in eng.prefill_buckets:
        started = time.time()
        compiled = eng._prefill_fn.lower(
            params, pools, [], sds((1, t)), sds((t,))).compile()
        aot_v5e.report(f"{cell['name']}: prefill {t}", compiled, started)
    for w in widths or [w for w in eng.page_buckets if w >= 16]:
        for t in eng.chunk_buckets:
            started = time.time()
            compiled = eng._chunk_fn.lower(
                params, pools, [], sds((1, t)), sds((t,)), sds((t,)),
                sds((1, w))).compile()
            aot_v5e.report(f"{cell['name']}: chunk {t}x{w}", compiled,
                           started)
        for b in eng.decode_buckets:
            started = time.time()
            compiled = eng._decode_fn.lower(
                params, pools, [], sds((b,)), sds((b,)), sds((b,)),
                sds((b, w)), sds((b,))).compile()
            aot_v5e.report(f"{cell['name']}: decode {b}x{w}", compiled,
                           started)


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
