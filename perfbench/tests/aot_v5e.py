"""Compile each cell's largest programs for the v5e without a chip and
print the compiler's memory analysis: what does not fit is found here and
costs no chip minute. Run by hand from the repository's root:

    JAX_PLATFORMS=cpu python3 perfbench/tests/aot_v5e.py [cell ...]

Nothing runs; a program that compiles here has not been shown to be right
or fast. Kernels are forced to their compiled form (on the CPU host the
program's ``auto`` would pick the references).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from perfbench import run, train_cell  # noqa: E402

KERNELS = {"attn_impl": "tpu", "paged_attn": "kernel"}


def report(name, compiled, started):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({
        "program": name, "compile_s": round(time.time() - started, 1),
        "arguments_gb": m.argument_size_in_bytes / 1e9,
        "outputs_gb": m.output_size_in_bytes / 1e9,
        "temporaries_gb": m.temp_size_in_bytes / 1e9,
        "aliased_gb": m.alias_size_in_bytes / 1e9,
        "live_gb": total / 1e9,
        "pallas_calls": compiled.as_text().count("tpu_custom_call")}),
        flush=True)


def train_cell_programs(cell, cfg, mix, devices):
    built = train_cell.build(run.load_family([run.HERE], cfg), cfg, mix,
                             devices[:cell["chips"]],
                             dict(mix.get("model_overrides", ()), **KERNELS))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    one = SingleDeviceSharding(devices[0])
    state = jax.eval_shape(built["init"], key)
    tokens = jax.eval_shape(built["batch"], key, 0)
    if built["mesh"] is None:
        put = functools.partial(jax.tree_util.tree_map, lambda a:
                                jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=one))
        state, tokens = put(state), put(tokens)
    started = time.time()
    with train_cell.mesh_context(built["mesh"]):
        compiled = built["step"].lower(*state, tokens).compile()
    report(f"{cell['name']}: train step", compiled, started)


def serve_cell_programs(cell, cfg, mix, devices):
    """Every program the mix's pinned buckets can reach, lowered from the
    engine's own jitted functions (pool update included) over the shapes
    and types of the working copy it serves from (``serving_params``: the
    compute type, not the float32 of the tree it is given). The engine
    is built on the CPU host with abstract parameters; only its pool of
    zeros is real."""
    from raytpu.inference import InferenceEngine

    family = run.load_family([run.HERE], cfg)
    mcfg = family.program_config(
        cfg, dict(mix.get("model_overrides", ()), **KERNELS))
    one = SingleDeviceSharding(devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    given = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            family.train_parts(mcfg)[0], jax.random.PRNGKey(0)))
    eng = InferenceEngine(mcfg, given, **mix["engine_options"])
    params = eng._params
    pools = [sds(a.shape, a.dtype) for a in eng.cache.k]
    i32 = jnp.int32
    for t in eng.prefill_buckets:
        started = time.time()
        compiled = eng._prefill_fn.lower(
            params, pools, pools, sds((1, t), i32), sds((t,), i32)).compile()
        report(f"{cell['name']}: prefill {t}", compiled, started)
    for b in eng.decode_buckets:
        for w in eng.page_buckets:
            if w * eng.page_size < min(eng.prefill_buckets) // 2:
                continue  # narrower than the shortest prompt: unreachable
            started = time.time()
            compiled = eng._decode_fn.lower(
                params, pools, pools, sds((b,), i32), sds((b,), i32),
                sds((b,), i32), sds((b, w), i32), sds((b,), i32)).compile()
            report(f"{cell['name']}: decode {b}x{w}", compiled, started)


def main(argv):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for cell in benchmark["workloads"]:
        if argv and cell["name"] not in argv:
            continue
        cfg = run.load_json([run.HERE], "configs", cell["config"])
        mix = run.load_json([run.HERE], "traffic", cell["traffic"])
        fn = train_cell_programs if mix["kind"] == "train" \
            else serve_cell_programs
        fn(cell, cfg, mix, list(topo.devices))


if __name__ == "__main__":
    main(sys.argv[1:])
