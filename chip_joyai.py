"""JoyAI-LLM-Flash on the chip against its plain reference, where the
benchmark's own check cannot reach: more seeds, controls, long caches.

``perfbench``'s check of ``joyai-latent-decode`` holds the whole-prompt
(expanded) program and eight absorbed decodes of two prompts of
1,100-1,250 tokens to the float32 reference, once a run. This script
drives the same programs, at the published widths and the cell's 12
layers and 32 held experts, engine and cache as the cell builds them:

    python chip_joyai.py check --seeds 1 2 ... 30 --controls 3
    python chip_joyai.py long --tokens 16000 --seeds 1 2 --controls 1

``check`` reads the cell's check (``rel_err``: the largest logit
difference over the largest reference logit, over the prompt's last row
and the decoded positions) at every seed, and on the last ``--controls``
seeds the controls, programs wrong in one way each. ``long`` sends one
prompt of ``--tokens`` through the *chunk* program (2,048 rows a call:
since PR 58 the expanded form, each chunk's own rows and the cached
segments before them under the flash kernel, merged by log-sum-exp) and
eight absorbed decodes over that cache, against the reference computed
in blocks. One
engine a program is built and reused from seed to seed (the programs
take the parameters as an argument), so a seed costs its weights, its
requests and the reference.

The controls: ``no_bias`` (``e_score_correction_bias`` left out of the
choice), ``no_renorm`` (the chosen weights not divided by their sum),
``no_scale`` (not multiplied by ``routed_scaling_factor``),
``no_kv_norm`` (``kv_a_norm`` left out), ``sqrt_nope`` (scores over
sqrt(128), not sqrt(192)), ``no_interleave`` (the rope's pairs read as
halves), ``float8`` (every bf16 matrix rounded to float8_e4m3). A wrong
program is teacher-forced on the right program's tokens (as
``chip_mellum.py``: the prompt and the sampled tokens as one prompt,
judged on its last rows), the right program the same way (``forced``)
gives the reading to hold them against. A control is caught by ``max``
if it reads above the mix's tolerance (the benchmark's check would
catch it), else by ``min`` if the row it moved least reads
``ROWS_FACTOR`` times the right program's, else by ``median`` if the
median row does (the bias left out moves a third of the rows far and the
rest not at all). ``check`` exits 0 if the right program is under the
tolerance on every seed and every control is caught by one of the three.
On the v5e the largest row catches only ``no_renorm``, ``no_interleave``
and ``sqrt_nope`` on every seed: a token whose bf16 residual stream
chooses another eighth expert than the float32 reference's moves its row
as far as the other faults move every row, so the tolerance of the cell
``joyai-latent-decode`` stands above that and holds no precision by
itself (its mix's ``check.why``; ``PERF.md`` section 7): the least-moved
and the median row, which this script reads, do. It needs a TPU;
``--cpu``, ``--config``, ``--mix`` and small ``--tokens`` are for the
rehearsal in
``tests/test_joyai.py``. The last line is one JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chip_mellum import (DECODES, ROWS_FACTOR, rel_errs,  # noqa: E402
                         rounded_to_float8)

# Controls that are another program (a config of their own) and those
# that are the right program over other parameters.
PROGRAM_CONTROLS = ("no_renorm", "no_scale", "no_kv_norm", "sqrt_nope",
                    "no_interleave")
PARAM_CONTROLS = ("no_bias", "float8")  # float8 last: it consumes the tree
CONTROLS = PROGRAM_CONTROLS + PARAM_CONTROLS


def log(msg: str) -> None:
    print(f"[chip_joyai] {msg}", flush=True)


def wrong_config(pcfg, control: str):
    """``pcfg`` wrong in one way: a field, or an attention module that
    departs from the equations in one place."""
    from raytpu.models.mixtral import JoyAIConfig
    from raytpu.models.mla import LatentAttention

    fields = {"no_renorm": dict(norm_topk_prob=False),
              "no_scale": dict(routed_scale=1.0),
              "no_interleave": dict(rope_interleave=False)}
    if control in fields:
        return dataclasses.replace(pcfg, **fields[control])

    class Wrong(LatentAttention):
        def setup(self):
            super().setup()
            if control == "no_kv_norm":
                self.kv_a_norm = lambda x: x

        @property
        def sm_scale(self):
            return self.config.qk_nope_dim ** -0.5 \
                if control == "sqrt_nope" else super().sm_scale

    @dataclasses.dataclass(frozen=True)
    class WrongConfig(JoyAIConfig):
        def attention(self, kind=None, **kw):
            return Wrong(self, **kw)

    return WrongConfig(**{f.name: getattr(pcfg, f.name)
                          for f in dataclasses.fields(pcfg)})


def without_bias(params):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if path[-1].key == "bias" else a,
        params)


class Served:
    """One engine, built once, driven as the serve path drives it; the
    parameters are given a run."""

    def __init__(self, pcfg, params, engine_options):
        from raytpu.inference import InferenceEngine

        gc.collect()
        self.eng = eng = InferenceEngine(
            pcfg, params, **dict(engine_options, enable_prefix_cache=False))
        self.rows, self.current = {}, {"logits": None, "decoding": []}
        prefill, chunk, decode = eng._prefill_fn, eng._chunk_fn, \
            eng._decode_fn
        run_prefill, run_decode = eng._run_prefill, eng._run_decode

        def prefill_kept(*a):
            res = prefill(*a)
            self.current["logits"] = res[0]
            return res

        def chunk_kept(*a):
            res = chunk(*a)
            self.current["logits"] = res[0][0]
            return res

        def decode_kept(*a):
            res = decode(*a)
            got = np.asarray(res[0], np.float32)
            for i, rid in enumerate(self.current["decoding"]):
                self.rows[rid].append(got[i])
            return res

        def run_prefill_kept(seq, out):
            start = seq.cached_len
            n = run_prefill(seq, out)
            # The prompt's last rows, from the programs that held them.
            lo = max(len(seq.prompt) - self.tail, start)
            hi = min(len(seq.prompt), seq.cached_len)
            if lo < hi and not seq.generated[1:]:
                self.rows[seq.request_id].extend(np.asarray(
                    self.current["logits"][lo - start:hi - start],
                    np.float32))
            return n

        def run_decode_kept(seqs, out):
            self.current["decoding"] = [s.request_id for s in seqs]
            return run_decode(seqs, out)

        eng._prefill_fn, eng._chunk_fn, eng._decode_fn = (
            prefill_kept, chunk_kept, decode_kept)
        eng._run_prefill, eng._run_decode = run_prefill_kept, run_decode_kept
        self.runs = 0

    def run(self, params, prompts, tail=1, new_tokens=DECODES + 1):
        """Per prompt the float32 logits of its last ``tail`` rows and of
        ``new_tokens - 1`` decoded positions, the tokens it sampled, and
        what the engine ran."""
        from raytpu.inference.sampling import SamplingParams

        eng = self.eng
        eng._params = eng._config.serving.params(eng._config, params)
        self.tail = tail
        self.runs += 1
        ids = [f"r{self.runs}-{i}" for i in range(len(prompts))]
        self.rows = {rid: [] for rid in ids}
        for rid, prompt in zip(ids, prompts):
            eng.add_request(rid, prompt, SamplingParams(
                max_new_tokens=new_tokens))
        tokens = {rid: [] for rid in ids}
        t0, first = time.perf_counter(), len(eng.step_log()["steps"])
        while eng.has_unfinished():
            for o in eng.step():
                tokens[o.request_id].append(o.token_id)
        stats = eng.stats()
        steps = eng.step_log()["steps"][first:]
        facts = {
            "seconds": round(time.perf_counter() - t0, 1),
            "programs": {k: sorted(stats[k]) for k in (
                "prefill_compiles", "chunk_prefill_compiles",
                "decode_compiles")},
            "live_pages_max": max(s["live_pages"] for s in steps),
            "pairs_here": sum(s.get("moe_assignments", 0) for s in steps),
            "preemptions": stats["num_preemptions"]}
        return ([np.stack(self.rows[rid][:tail + new_tokens - 1])
                 for rid in ids], [tokens[rid] for rid in ids], facts)


_REFERENCES = {}


def reference_rows(family, cfg, params, prompt, sampled):
    """The reference's logits of the prompt's last row and the ``DECODES``
    decoded positions, teacher-forced on what the engine sampled; one
    compiled function a prompt length."""
    import jax
    import jax.numpy as jnp

    seq = list(prompt) + list(sampled[:DECODES])
    n = len(prompt)
    if n not in _REFERENCES:
        rows = list(range(n - 1, n + DECODES))
        _REFERENCES[n] = jax.jit(
            lambda p, t: family.logits(cfg, p, t, rows=rows))
    return np.asarray(_REFERENCES[n](params, jnp.asarray([seq],
                                                         jnp.int32)))[0]


def compare(family, cfg, pcfg, params, prompts, options, engines, controls,
            label) -> dict:
    def served(name, config):
        if name not in engines:
            engines[name] = Served(config, params, options)
        return engines[name]

    got, sampled, facts = served("right", pcfg).run(params, prompts)
    want = [reference_rows(family, cfg, params, p, s)
            for p, s in zip(prompts, sampled)]
    errs = rel_errs(got, want)
    out = {"label": label, "prompt_tokens": [len(p) for p in prompts],
           "rel_err": errs["max"], "rel_err_median": errs["median"],
           "rel_err_min": errs["min"], **facts}
    log(json.dumps(out))
    if not controls:
        return out
    forced = [list(p) + list(s[:DECODES]) for p, s in zip(prompts, sampled)]
    for control in ("forced",) + tuple(controls):
        if control in PROGRAM_CONTROLS:
            eng, tree = served(control, wrong_config(pcfg, control)), params
        else:
            eng = served("right", pcfg)
            tree = {"forced": lambda: params,
                    "no_bias": lambda: without_bias(params),
                    "float8": lambda: rounded_to_float8(params)}[control]()
        bad, _, _ = eng.run(tree, forced, tail=DECODES + 1, new_tokens=1)
        out[control] = rel_errs(bad, want)
        log(json.dumps({"label": label, "control": control,
                        **out[control]}))
    return out


def caught_by(result: dict, control: str, tolerance: float,
              factor: float = ROWS_FACTOR):
    if result[control]["max"] > tolerance:
        return "max"
    for rows in ("min", "median"):
        if result[control][rows] > factor * result["forced"][rows]:
            return rows
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("phase", choices=("check", "long"))
    ap.add_argument("--seeds", type=int, nargs="*", default=[2147483659])
    ap.add_argument("--tokens", type=int, default=16000)
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
        sys.exit(f"chip_joyai.py needs a TPU and found none: "
                 f"jax.devices()[0].platform == {devices[0].platform!r}")
    with open(args.config or os.path.join(
            run.HERE, "configs", "joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    with open(args.mix or os.path.join(
            run.HERE, "traffic", "latent-decode.json")) as f:
        mix = json.load(f)
    family = run.load_family([run.HERE], cfg)
    pcfg = family.program_config(cfg, mix.get("model_overrides", ()))
    options = dict(mix["engine_options"])
    page = options["page_size"]
    if args.phase == "check":
        lengths = mix["check"]["prompt_tokens"]
        # The cell's programs over pools for these two prompts alone.
        options["num_pages"] = 2 * -(-(max(lengths) + 2 * DECODES + 2)
                                     // page) + 2
    else:
        # A pool for one long sequence, the cell's page and chunk.
        lengths = [args.tokens]
        longest = args.tokens + 2 * DECODES + 2
        options.update(
            max_num_seqs=1, decode_buckets=[1],
            max_model_len=min(options["max_model_len"],
                              -(-longest // page) * page),
            num_pages=-(-longest // page) + 2)
        # The forced prompt is a chunk's worth past the whole-prompt
        # bucket: it goes through the chunk program too.
        options["prefill_buckets"] = [min(options["prefill_buckets"][0],
                                          options["prefill_chunk"])]
    controls = tuple(c for c in CONTROLS if c in (args.only or CONTROLS))
    engines, results = {}, []
    with_controls = args.seeds[len(args.seeds) - args.controls:] \
        if args.controls else []
    for seed in args.seeds:
        params = init_params(Mixtral(pcfg), pcfg, seed=seed & 0x7FFFFFFF,
                             batch=1)
        prompts = [traffic.prompt_tokens(seed, i, n, cfg["vocab_size"],
                                         stream=9)
                   for i, n in enumerate(lengths)]
        results.append(compare(
            family, cfg, pcfg, params, prompts, options, engines,
            controls if seed in with_controls else (),
            f"{args.phase} seed {seed}"))
        del params
    tolerance = float(mix["check"]["tolerance"])
    worst = max(r["rel_err"] for r in results)
    for r in results:
        if "forced" in r:
            r["caught_by"] = {c: caught_by(r, c, tolerance)
                              for c in controls}
    passed = worst <= tolerance and all(
        all(r["caught_by"].values()) for r in results if "forced" in r)
    print(json.dumps({
        "ok": bool(passed), "tolerance": tolerance, "worst_rel_err": worst,
        "rel_errs": sorted(r["rel_err"] for r in results),
        "rows_factor": ROWS_FACTOR, "results": results,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind}}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
