"""``aot_v5e.py`` for a cell whose model keeps state arrays of more than one
dtype at seats *beside a latent pool*: compile the cell's programs for the
v5e without a chip and print the compiler's memory analysis. Run by hand
from the repository's root:

    JAX_PLATFORMS=cpu python3 perfbench/tests/aot_v5e_kda.py ling-kda-decode [width ...] [--ops]

``aot_v5e_state.py`` hands every program a list of K pools and a list of V
pools before the state arrays, ``aot_v5e_latent.py`` one pool a layer, an
empty V list and no state: a model with both takes ``(params, pools, [],
states, seats, *inputs)``, which neither lowers and neither may be edited
to by the PR that brought this one. This one reuses ``aot_v5e.report``,
``KERNELS`` and ``aot_v5e_text``'s abstract engine, and compiles the
whole-prompt, chunk and decode programs and the sampler over a decode's
logits. Table widths default to the engine's buckets of 16 columns and more
(a chunk's: of 32 and more, the prompts longer than a chunk). With
``--ops`` it lists, for the last decode program, the instructions that
carry each ``kda.*`` scope and those whose operands name a KDA parameter,
which is what ``perfbench/kda.py`` can find in a device trace. Nothing
runs; a program that compiles here has not been shown to be right or fast.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import aot_v5e  # noqa: E402  (sets TPU_LOG_DIR and the path first)
import aot_v5e_text  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402

SCOPES = ("kda.in_proj", "kda.conv", "kda.gate", "kda.state", "kda.out")


def scoped_ops(compiled):
    """``{scope: sorted instruction heads}`` of the compiled text, and
    under ``__kda__`` the heads of the instructions an operand of which
    names a KDA parameter."""
    found = {scope: set() for scope in SCOPES + ("__kda__",)}
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if not m:
            continue
        head = re.sub(r"\.\d+$", "", m.group(1))
        for scope in SCOPES:
            if f"/{scope}/" in line:
                found[scope].add(head)
        if "__kda__" in line.split(", metadata=")[0]:
            found["__kda__"].add(head)
    return {scope: sorted(names) for scope, names in found.items()}


def main(argv):
    ops = "--ops" in argv
    argv = [a for a in argv if a != "--ops"]
    cell, cfg, mix = aot_v5e_text.cell_files(argv[0])
    # ``serve_options`` is the deployment's, not the engine's.
    mix = dict(mix, engine_options={
        k: v for k, v in mix["engine_options"].items()
        if k != "serve_options"})
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    eng, ks, vs, sds = aot_v5e_text.abstract_engine(cfg, mix,
                                                    topo.devices[0])
    states = [sds(a.shape, a.dtype) for a in eng.cache.state]
    stats = eng.stats()
    print(json.dumps({
        "kv_pool_bytes": stats["kv_pool_bytes"],
        "kv_bytes_per_token": eng.cache.token_bytes,
        "state_bytes": stats["state_bytes"],
        "state_bytes_per_seq": eng.cache.state_bytes,
        "param_bytes": stats["param_bytes"],
        "pools": sorted({a.shape for a in eng.cache.k}),
        "v_pools": len(eng.cache.v),
        "state_arrays": sorted({(a.shape, str(a.dtype))
                                for a in eng.cache.state})}), flush=True)
    name, params = cell["name"], eng._params
    widths = [int(w) for w in argv[1:]]
    for t in eng.prefill_buckets:
        started = time.time()
        compiled = eng._prefill_fn.lower(
            params, ks, vs, states, sds((1,)), sds((1, t)),
            sds((t,))).compile()
        aot_v5e.report(f"{name}: prefill {t}", compiled, started)
    for w in widths or [w for w in eng.page_buckets if w >= 16]:
        for t in eng.chunk_buckets if w >= 32 or widths else ():
            started = time.time()
            compiled = eng._chunk_fn.lower(
                params, ks, vs, states, sds((1,)), sds((1, t)), sds((t,)),
                sds((t,)), sds((1, w))).compile()
            aot_v5e.report(f"{name}: chunk {t}x{w}", compiled, started)
        for b in eng.decode_buckets:
            started = time.time()
            compiled = eng._decode_fn.lower(
                params, ks, vs, states, sds((b,)), sds((b,)), sds((b,)),
                sds((b,)), sds((b, w)), sds((b,))).compile()
            aot_v5e.report(f"{name}: decode {b}x{w}", compiled, started)
    if ops:
        print(json.dumps(scoped_ops(compiled)), flush=True)
    b, v = eng.decode_buckets[-1], eng._config.vocab_size
    started = time.time()
    compiled = eng._sample_fn.lower(
        sds((b, v), jnp.float32), sds((b,), jnp.float32), sds((b,)),
        sds((b,), jnp.uint32), sds((b,))).compile()
    aot_v5e.report(f"{name}: sample {b}x{v}", compiled, started)


if __name__ == "__main__":
    main(sys.argv[1:])
