"""``aot_v5e_latent.py`` for a cell whose prompts are served whole: the
whole-prompt program of each pinned prefill bucket and the decode program
of each table width, compiled for the v5e without a chip, with the
compiler's memory analysis (the numbers a mix's ``engine_options_why``
cites). Run by hand from the repository's root:

    JAX_PLATFORMS=cpu python3 perfbench/tests/aot_v5e_whole.py longcat-shortcut-decode [width ...]

``aot_v5e_latent.py`` compiles a chunk program for every chunk bucket and
width; an engine whose mix pins no ``prefill_chunk`` has ten chunk buckets
it never reaches (powers of two up to ``max_model_len``), forty programs
where this cell runs five. Table widths default to the engine's buckets
of 8 columns and more. Nothing runs; a program that compiles here has not
been shown to be right or fast.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import aot_v5e  # noqa: E402  (sets TPU_LOG_DIR and the path first)
import aot_v5e_text  # noqa: E402
from jax.experimental import topologies  # noqa: E402


def main(argv):
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
        "pools": len(eng.cache.k), "v_pools": len(eng.cache.v)}),
        flush=True)
    for t in eng.prefill_buckets:
        started = time.time()
        compiled = eng._prefill_fn.lower(
            eng._params, ks, vs, sds((1, t)), sds((t,))).compile()
        aot_v5e.report(f"{cell['name']}: prefill {t}", compiled, started)
    for w in [int(w) for w in argv[1:]] or [
            w for w in eng.page_buckets if w >= 8]:
        aot_v5e_text.decode_program(cell["name"], eng, ks, vs, sds, w)


if __name__ == "__main__":
    main(sys.argv[1:])
