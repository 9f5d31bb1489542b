"""``aot_v5e_latent.py`` for a cell whose latent layers keep two pools, the
latent rows and an indexer's keys: the whole-prompt program of each pinned
prefill bucket, the chunk program and the decode program of each table
width, compiled for the v5e without a chip, with the compiler's memory
analysis (the numbers a mix's ``engine_options_why`` cites). Run by hand
from the repository's root:

    JAX_PLATFORMS=cpu python3 perfbench/tests/aot_v5e_dsa.py glm5-sparse-decode [width ...]

``aot_v5e_latent.py`` hands every program an empty V list; here the V
list is the index-key pools (``aot_v5e_text.abstract_engine`` gives both).
With ``--ops`` it also prints, for the last decode program, the
instructions that carry each ``attn.dsa.*`` scope in their metadata: the
names the device trace will show for what is no kernel of its own (the
choice, the gather). Table widths default to the engine's buckets of 32
columns and more. Nothing runs; a program that compiles here has not been
shown to be right or fast.
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
from jax.experimental import topologies  # noqa: E402

SCOPES = ("attn.dsa.index", "attn.dsa.select", "attn.dsa.attend")


def scoped_ops(compiled):
    """``{scope: sorted instruction heads}`` of the compiled text."""
    found = {scope: set() for scope in SCOPES}
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        for scope in SCOPES:
            if m and f"/{scope}/" in line:
                found[scope].add(re.sub(r"\.\d+$", "", m.group(1)))
    return {scope: sorted(names) for scope, names in found.items()}


def main(argv):
    ops = "--ops" in argv
    argv = [a for a in argv if a != "--ops"]
    cell, cfg, mix = aot_v5e_text.cell_files(argv[0])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    eng, ks, vs, sds = aot_v5e_text.abstract_engine(cfg, mix,
                                                    topo.devices[0])
    stats = eng.stats()
    print(json.dumps({
        "kv_pool_bytes": stats["kv_pool_bytes"],
        "kv_bytes_per_token": eng.cache.token_bytes,
        "param_bytes": stats["param_bytes"],
        "pools": sorted({a.shape for a in eng.cache.k}),
        "index_pools": sorted({a.shape for a in eng.cache.v})}), flush=True)
    widths = [int(w) for w in argv[1:]] or [
        w for w in eng.page_buckets if w >= 32]
    for t in eng.prefill_buckets:
        started = time.time()
        compiled = eng._prefill_fn.lower(
            eng._params, ks, vs, sds((1, t)), sds((t,))).compile()
        aot_v5e.report(f"{cell['name']}: prefill {t}", compiled, started)
    for w in widths:
        for t in eng.chunk_buckets:
            started = time.time()
            compiled = eng._chunk_fn.lower(
                eng._params, ks, vs, sds((1, t)), sds((t,)), sds((t,)),
                sds((1, w))).compile()
            aot_v5e.report(f"{cell['name']}: chunk {t}x{w}", compiled,
                           started)
        compiled = aot_v5e_text.decode_program(cell["name"], eng, ks, vs,
                                               sds, w)
    if ops:
        print(json.dumps(scoped_ops(compiled)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
