"""The text of one decode program of a serving cell, compiled for the v5e
without a chip: what a PR that touches the serving walk for one model's
sake compares before and after for the others. Run by hand from the
repository's root, here and in a copy of the parent commit (the file may
be copied there: it reads the tree it lies in):

    JAX_PLATFORMS=cpu python3 perfbench/tests/aot_v5e_text.py <cell> <table width> [<file to write>]

Prints the program's live bytes as ``aot_v5e.report`` does, the number of
lines of ``compiled.as_text()`` and their SHA-256, after what names the
tree and not the program is taken out (``program_text``: the tables of
source files and lines, each instruction's ``stack_frame_id``, and the
body of a Pallas kernel, Mosaic bytecode that carries the source lines of
its callers; the kernels' own sources are compared with ``git diff
raytpu/ops``); two trees whose programs are the same print the same
digest. A latent-attention model's
programs take one pool a layer and an empty V list, the others a K and a
V list; a model with state arrays or one that drafts for itself takes
more (``aot_v5e_state.py``, ``aot_v5e_drafting.py``) and is not lowered
here. Nothing runs.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import aot_v5e  # noqa: E402  (sets TPU_LOG_DIR and the path first)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from perfbench import run  # noqa: E402


def abstract_engine(cfg, mix, device):
    """The cell's engine over abstract parameters on the described
    ``device`` -> ``(engine, K pools, V pools, sds)``, the pools as
    shapes (``V pools`` empty for a latent model) and ``sds(shape)`` an
    int32 shape there."""
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
    return (eng, [sds(a.shape, a.dtype) for a in eng.cache.k],
            [sds(a.shape, a.dtype) for a in eng.cache.v], sds)


def decode_program(name, eng, ks, vs, sds, width: int):
    """The decode program at the engine's largest batch bucket and a
    table of ``width`` columns, compiled and reported."""
    b = eng.decode_buckets[-1]
    started = time.time()
    compiled = eng._decode_fn.lower(
        eng._params, ks, vs, sds((b,)), sds((b,)), sds((b,)),
        sds((b, width)), sds((b,))).compile()
    aot_v5e.report(f"{name}: decode {b}x{width}", compiled, started)
    return compiled


def cell_files(name):
    """``(cell, configuration, mix)`` of the cell ``name``."""
    with open(os.path.join(aot_v5e.ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == name)
    return (cell, run.load_json([run.HERE], "configs", cell["config"]),
            run.load_json([run.HERE], "traffic", cell["traffic"]))


def program_text(compiled) -> str:
    """``compiled.as_text()`` without what differs between two checkouts
    of one program: the source tables before the computations, the
    instructions' frame ids, the kernels' serialized bodies."""
    text = compiled.as_text()
    if "\nFileNames\n" in text:
        tables = text.index("\nFileNames\n")
        text = text[:tables] + text[text.index(
            "\n\n", text.index("\nStackFrames\n", tables)):]
    text = re.sub(r" stack_frame_id=\d+", "", text)
    return re.sub(r'"body":"[A-Za-z0-9+/=]*"', '"body":"..."', text)


def main(argv):
    cell, cfg, mix = cell_files(argv[0])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    text = program_text(decode_program(
        cell["name"], *abstract_engine(cfg, mix, topo.devices[0]),
        int(argv[1])))
    if len(argv) > 2:
        with open(argv[2], "w") as f:
            f.write(text)
    print(json.dumps({
        "cell": cell["name"], "lines": text.count("\n"),
        "sha256": hashlib.sha256(text.encode()).hexdigest()}))


if __name__ == "__main__":
    main(sys.argv[1:])
