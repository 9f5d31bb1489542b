"""GLM-5 on the chip against its plain reference, where the benchmark's
own check cannot reach: more seeds, controls, and what a step costs
against the context.

``perfbench``'s check of ``glm5-sparse-decode`` holds the whole-prompt
program (prompts of 3,300 and 4,000 tokens: over ``index_topk``, so the
absorbed form over chosen rows of the prompt's own) and eight decodes
through both pools to the float32 reference, once a run. This script
drives the same programs, at the published widths and the cell's 5 layers
and 8 held experts, engine and cache as the cell builds them:

    python chip_glm5.py check --seeds 1 2 ... 12 --controls 3
    python chip_glm5.py sweep --contexts 16384 32768 49152

``check`` reads the cell's check (``rel_err``: the largest logit
difference over the largest reference logit, over the prompt's last row
and the decoded positions) at every seed, and on the last ``--controls``
seeds the controls, programs wrong in one way each, as ``chip_longcat.py``
does it (one engine a program, reused from seed to seed; a wrong program
teacher-forced on the right program's tokens, the right program the same
way, ``forced``, to hold them against; ``chip_joyai.caught_by``).

The controls, each the right program but for one thing: ``no_selection``
(every cached row is read: dense latent attention), ``newest`` (the
newest ``index_topk`` positions and not the chosen), ``no_relu`` (the
index products summed as they are), ``no_head_weights`` (every index head
weighs the same), ``index_not_roped`` (no rope on the index queries and
keys), ``index_rope_halves`` (their rope read by halves where the config
says pairs), ``no_k_norm`` (the index key's LayerNorm left out),
``float8`` (every bf16 matrix rounded to float8_e4m3).

``sweep`` times the cell's decode program (24 sequences) and chunk program
(4,096 rows) alone on the device, pools filled with seeded noise and
tables of pages drawn anywhere in them, at each of ``--contexts``: the median of five
calls, in ms, and the chunk's share of a 29.8k prompt's turnover. It needs
a TPU; ``--cpu``, ``--config``, ``--mix`` are for the rehearsal in
``tests/test_glm_dsa.py``. The last line is one JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_joyai  # noqa: E402
import chip_longcat  # noqa: E402
from chip_mellum import DECODES, ROWS_FACTOR  # noqa: E402

PROGRAM_CONTROLS = ("no_selection", "newest", "no_relu", "no_head_weights",
                    "index_not_roped", "index_rope_halves", "no_k_norm")
PARAM_CONTROLS = ("float8",)  # last: it consumes the tree
CONTROLS = PROGRAM_CONTROLS + PARAM_CONTROLS


def log(msg: str) -> None:
    print(f"[chip_glm5] {msg}", flush=True)


def wrong_config(pcfg, control: str):
    """``pcfg`` wrong in one way: a field, or an indexer that departs
    from the equations in one place."""
    import jax.numpy as jnp

    from raytpu.models.mixtral import GlmDsaConfig
    from raytpu.models.mla import SparseLatentAttention

    fields = {"no_selection": dict(index_topk=pcfg.block_size),
              "index_rope_halves": dict(
                  index_rope_interleave=not pcfg.index_rope_interleave)}
    if control in fields:
        return dataclasses.replace(pcfg, **fields[control])

    class Wrong(SparseLatentAttention):
        def setup(self):
            super().setup()
            if control == "no_k_norm":
                self.index_k_norm = lambda x: x

        def _index_roped(self, v, cos, sin):
            if control == "index_not_roped":
                return v
            return super()._index_roped(v, cos, sin)

        def _index_queries(self, x, c_q, cos, sin):
            q, w = super()._index_queries(x, c_q, cos, sin)
            if control == "no_head_weights":
                w = jnp.full_like(w, (w.shape[-1] * q.shape[-1]) ** -0.5)
            if control == "no_relu":  # relu(a) - relu(-a) = a
                q, w = (jnp.concatenate([q, -q], 1),
                        jnp.concatenate([w, -w], 1))
            return q, w

        def _rows(self, x, positions):
            got = super()._rows(x, positions)
            if control != "newest":
                return got
            # A key that spells its position in three digits base 64 (exact
            # in bf16) and a query that reads it back: the score rises
            # with the position.
            q_nope, q_pe, rows, q_idx, keys, w_idx = got
            p = positions.astype(jnp.int32)
            digits = jnp.stack([p // 4096, p // 64 % 64, p % 64], -1)
            keys = jnp.zeros_like(keys).at[:, :3].set(
                digits.astype(keys.dtype))
            q_idx = jnp.zeros_like(q_idx).at[:, 0, :3].set(
                jnp.asarray([4096.0, 64.0, 1.0], q_idx.dtype))
            return (q_nope, q_pe, rows, q_idx, keys,
                    jnp.ones_like(w_idx))

    @dataclasses.dataclass(frozen=True)
    class WrongConfig(GlmDsaConfig):
        def attention(self, kind=None, **kw):
            return Wrong(self, **kw)

    return WrongConfig(**{f.name: getattr(pcfg, f.name)
                          for f in dataclasses.fields(pcfg)})


def sweep(pcfg, params, options, contexts, calls: int = 5) -> list:
    """Device ms of the decode and the chunk program alone at each
    context: pools of seeded noise, every sequence's table pages drawn
    anywhere in them."""
    import jax
    import jax.numpy as jnp

    from raytpu.inference import InferenceEngine

    eng = InferenceEngine(pcfg, params,
                          **dict(options, enable_prefix_cache=False))
    cache, page = eng.cache, options["page_size"]
    key = jax.random.PRNGKey(7)
    for pools in (cache.k, cache.v):
        for i, pool in enumerate(pools):
            key, sub = jax.random.split(key)
            pools[i] = jax.random.normal(sub, pool.shape, pool.dtype)
    b, t = eng.decode_buckets[-1], eng.chunk_buckets[-1]
    rng = np.random.default_rng(7)
    out = []

    def timed(fn, *inputs) -> float:
        ms = []
        for _ in range(calls + 1):  # the first call compiles
            t0 = time.perf_counter()
            logits, cache.k, cache.v, *_ = fn(
                eng._params, cache.k, cache.v, *inputs)
            jax.block_until_ready(logits)
            ms.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(ms[1:])

    for context in contexts:
        pages = -(-(context + 1) // page)
        width = next(w for w in eng.page_buckets if w >= pages)
        # (Pages drawn with replacement: 24 contexts of 48k are more
        # than the pools hold at once, and a time does not mind.)
        tables = np.zeros((b, width), np.int32)
        tables[:, :pages] = rng.integers(1, cache.num_pages, (b, pages))
        positions = np.full(b, context, np.int32)
        dests = tables[np.arange(b), positions // page] * page \
            + positions % page
        decode = timed(eng._decode_fn, jnp.zeros(b, jnp.int32), positions,
                       dests, tables, positions + 1)
        start = context - t
        rows = np.arange(start, context, dtype=np.int32)
        chunk = timed(eng._chunk_fn, jnp.zeros((1, t), jnp.int32), rows,
                      tables[0, rows // page] * page + rows % page,
                      tables[:1])
        out.append({"context": context, "table_width": width,
                    "decode_ms": decode, "chunk_ms": chunk})
        log(json.dumps(out[-1]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("phase", choices=("check", "sweep"))
    ap.add_argument("--seeds", type=int, nargs="*", default=[2147483659])
    ap.add_argument("--contexts", type=int, nargs="*",
                    default=[16384, 32768, 49152])
    ap.add_argument("--controls", type=int, default=0,
                    help="the controls on this many of the last seeds")
    ap.add_argument("--only", nargs="*", default=None, choices=CONTROLS,
                    help="of the controls, these alone")
    ap.add_argument("--config", default=None,
                    help="a configuration file (default: the cell's)")
    ap.add_argument("--mix", default=None, help="a mix file likewise")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from perfbench import run, traffic
    from raytpu.models.mixtral import Mixtral, init_params

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.cpu:
        sys.exit(f"chip_glm5.py needs a TPU and found none: "
                 f"jax.devices()[0].platform == {devices[0].platform!r}")
    with open(args.config or os.path.join(
            run.HERE, "configs", "glm-5.json")) as f:
        cfg = json.load(f)
    with open(args.mix or os.path.join(
            run.HERE, "traffic", "sparse-decode.json")) as f:
        mix = json.load(f)
    family = run.load_family([run.HERE], cfg)
    pcfg = family.program_config(cfg, mix.get("model_overrides", ()))
    options = dict(mix["engine_options"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind}
    if args.phase == "sweep":
        params = init_params(Mixtral(pcfg), pcfg,
                             seed=args.seeds[0] & 0x7FFFFFFF, batch=1)
        results = sweep(pcfg, params, options, args.contexts)
        print(json.dumps({"ok": True, "results": results,
                          "device": device}))
        return 0
    lengths = mix["check"]["prompt_tokens"]
    # The cell's programs over pools for these two prompts alone.
    options["num_pages"] = 2 * -(-(max(lengths) + 2 * DECODES + 2)
                                 // options["page_size"]) + 2
    controls = tuple(c for c in CONTROLS if c in (args.only or CONTROLS))
    engines, compiled, results = {}, {}, []
    with_controls = args.seeds[len(args.seeds) - args.controls:] \
        if args.controls else []
    for seed in args.seeds:
        # The chip does not hold two trees of 5 GB beside the engines:
        # they let go of the last seed's before the next is made.
        for served in engines.values():
            served.eng._params = None
        params = init_params(Mixtral(pcfg), pcfg, seed=seed & 0x7FFFFFFF,
                             batch=1)
        prompts = [traffic.prompt_tokens(seed, i, n, cfg["vocab_size"],
                                         stream=9)
                   for i, n in enumerate(lengths)]
        results.append(chip_longcat.compare(
            family, cfg, pcfg, params, prompts, options, engines, compiled,
            controls if seed in with_controls else (),
            f"check seed {seed}", serve=chip_joyai.Served,
            wrong=wrong_config, program_controls=PROGRAM_CONTROLS, log=log))
        del params
    tolerance = float(mix["check"]["tolerance"])
    worst = max(r["rel_err"] for r in results)
    for r in results:
        if "forced" in r:
            r["caught_by"] = {c: chip_joyai.caught_by(r, c, tolerance)
                              for c in controls}
    passed = worst <= tolerance and all(
        all(r["caught_by"].values()) for r in results if "forced" in r)
    print(json.dumps({
        "ok": bool(passed), "tolerance": tolerance, "worst_rel_err": worst,
        "rel_errs": sorted(r["rel_err"] for r in results),
        "rows_factor": ROWS_FACTOR, "results": results, "device": device}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
