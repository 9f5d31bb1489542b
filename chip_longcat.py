"""LongCat-Flash-Chat on the chip against its plain reference, where the
benchmark's own check cannot reach: more seeds, controls, the chunk path.

``perfbench``'s check of ``longcat-shortcut-decode`` holds the whole-prompt
(expanded) program and eight absorbed decodes of two prompts of
1,100-1,250 tokens to the float32 reference, once a run. This script
drives the same programs, at the published widths and the cell's 4 layers
(8 sublayers, 8 latent pools) and 16 held experts, engine and cache as
the cell builds them:

    python chip_longcat.py check --seeds 1 2 ... 12 --controls 3
    python chip_longcat.py long --tokens 5000 --seeds 1 2 --controls 1

``check`` reads the cell's check (``rel_err``: the largest logit
difference over the largest reference logit, over the prompt's last row
and the decoded positions) at every seed, and on the last ``--controls``
seeds the controls, programs wrong in one way each. ``long`` sends one
prompt of ``--tokens`` through the *chunk* program (the cell serves its
prompts whole; here a chunk is as long as the mix's whole-prompt bucket:
over the break-even of 171 queries, so since PR 58 the expanded form,
a chunk's own rows and the cached segments before them under the flash
kernel, against both pools a layer) and eight absorbed decodes over
that cache,
against the reference computed in blocks. One engine a program is built
and reused from seed to seed (``chip_joyai.Served``).

The controls, each the right program but for one thing:
``no_identity`` (the identity experts' term left out), ``no_q_scale`` and
``no_kv_scale`` (one of the two latent scales left out, each alone),
``renorm`` (the chosen weights divided by their sum), ``no_scale`` (not
multiplied by ``routed_scaling_factor``), ``shortcut_early`` (the routed
layer's output added after the first sublayer's feed-forward, where it
belongs at the layer's end), ``bias_in_weights`` (the chosen weights are
score + bias), ``float8`` (every bf16 matrix rounded to float8_e4m3). A
wrong program is teacher-forced on the right program's tokens (the prompt
and the sampled tokens as one prompt, judged on its last rows), the right
program the same way (``forced``) gives the reading to hold them against.
A control is caught by ``max`` if it reads above the mix's tolerance,
else by ``min`` if the row it moved least reads ``ROWS_FACTOR`` times the
right program's, else by ``median`` if the median row does
(``chip_joyai.caught_by``). ``check`` exits 0 if the right program is
under the tolerance on every seed and every control is caught by one of
the three. It needs a TPU; ``--cpu``, ``--config``, ``--mix`` and small
``--tokens`` are for the rehearsal in ``tests/test_longcat_flash.py``. The
last line is one JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_joyai  # noqa: E402
from chip_mellum import (DECODES, ROWS_FACTOR, rel_errs,  # noqa: E402
                         rounded_to_float8)

# Controls that are another program (a config of their own) and the one
# that is the right program over other parameters.
PROGRAM_CONTROLS = ("no_identity", "no_q_scale", "no_kv_scale", "renorm",
                    "no_scale", "shortcut_early", "bias_in_weights")
PARAM_CONTROLS = ("float8",)  # last: it consumes the tree
CONTROLS = PROGRAM_CONTROLS + PARAM_CONTROLS


def log(msg: str) -> None:
    print(f"[chip_longcat] {msg}", flush=True)


def wrong_config(pcfg, control: str):
    """``pcfg`` wrong in one way: a field, where the shortcut ends, or a
    routed layer that departs from the equations in one place."""
    import jax.numpy as jnp

    from raytpu.models.mixtral import LongcatFlashConfig, MoEFFN

    fields = {"no_q_scale": dict(mla_scale_q_lora=False),
              "no_kv_scale": dict(mla_scale_kv_lora=False),
              "renorm": dict(norm_topk_prob=True),
              "no_scale": dict(routed_scale=1.0)}
    if control in fields:
        return dataclasses.replace(pcfg, **fields[control])

    class Wrong(MoEFFN):
        def route(self, probs, bias):
            if control != "bias_in_weights":
                return super().route(probs, bias)
            return super().route(probs + bias, jnp.zeros_like(bias))

        def identity(self, xf, topw, topi):
            if control != "no_identity":
                return super().identity(xf, topw, topi)
            return jnp.zeros(xf.shape, jnp.float32)

    @dataclasses.dataclass(frozen=True)
    class WrongConfig(LongcatFlashConfig):
        def routed(self, **kw):
            return Wrong(self, **kw)

        def shortcut_to(self, i):
            to = super().shortcut_to(i)
            return i if control == "shortcut_early" and to is not None \
                else to

    return WrongConfig(**{f.name: getattr(pcfg, f.name)
                          for f in dataclasses.fields(pcfg)})


class Served(chip_joyai.Served):
    """``chip_joyai.Served`` that also says how many of a run's routed
    pairs chose an identity expert."""

    def run(self, params, prompts, **kw):
        before = self.eng.stats()["moe_zero_pairs"]
        rows, tokens, facts = super().run(params, prompts, **kw)
        facts["zero_pairs"] = self.eng.stats()["moe_zero_pairs"] - before
        return rows, tokens, facts


def reference_rows(compiled, family, cfg, params, prompt, sampled):
    """The reference's logits of the prompt's last row and the ``DECODES``
    decoded positions, teacher-forced on what the engine sampled;
    ``compiled`` keeps one compiled function a prompt length."""
    import jax
    import jax.numpy as jnp

    seq = list(prompt) + list(sampled[:DECODES])
    n = len(prompt)
    if n not in compiled:
        rows = list(range(n - 1, n + DECODES))
        compiled[n] = jax.jit(
            lambda p, t: family.logits(cfg, p, t, rows=rows))
    return np.asarray(compiled[n](params, jnp.asarray([seq], jnp.int32)))[0]


def compare(family, cfg, pcfg, params, prompts, options, engines, compiled,
            controls, label, *, serve=None, wrong=None,
            program_controls=None, log=None) -> dict:
    """One seed's readings. ``serve``, ``wrong``, ``program_controls`` and
    ``log``: another cell's engine wrapper, wrong configs, their names and
    its log line (``chip_glm5.py``); this module's by default."""
    serve, wrong = serve or Served, wrong or wrong_config
    program_controls = program_controls or PROGRAM_CONTROLS
    log = log or globals()["log"]

    def served(name, config):
        if name not in engines:
            engines[name] = serve(config, params, options)
        return engines[name]

    got, sampled, facts = served("right", pcfg).run(params, prompts)
    want = [reference_rows(compiled, family, cfg, params, p, s)
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
        if control in program_controls:
            eng, tree = served(control, wrong(pcfg, control)), params
        else:
            eng = served("right", pcfg)
            tree = rounded_to_float8(params) if control == "float8" \
                else params
        bad, _, _ = eng.run(tree, forced, tail=DECODES + 1, new_tokens=1)
        out[control] = rel_errs(bad, want)
        log(json.dumps({"label": label, "control": control,
                        **out[control]}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("phase", choices=("check", "long"))
    ap.add_argument("--seeds", type=int, nargs="*", default=[2147483659])
    ap.add_argument("--tokens", type=int, default=5000)
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
        sys.exit(f"chip_longcat.py needs a TPU and found none: "
                 f"jax.devices()[0].platform == {devices[0].platform!r}")
    with open(args.config or os.path.join(
            run.HERE, "configs", "longcat-flash-chat.json")) as f:
        cfg = json.load(f)
    with open(args.mix or os.path.join(
            run.HERE, "traffic", "shortcut-decode.json")) as f:
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
        # A pool for one long sequence, the cell's page; a chunk as long
        # as the whole-prompt bucket, which the prompt outgrows.
        lengths = [args.tokens]
        longest = args.tokens + 2 * DECODES + 2
        chunk = options["prefill_buckets"][-1]
        options.update(
            max_num_seqs=1, decode_buckets=[1], prefill_chunk=chunk,
            chunk_buckets=[chunk], prefill_buckets=[chunk],
            max_model_len=min(cfg["max_position_embeddings"],
                              -(-longest // page) * page),
            num_pages=-(-longest // page) + 2)
    controls = tuple(c for c in CONTROLS if c in (args.only or CONTROLS))
    engines, compiled, results = {}, {}, []
    with_controls = args.seeds[len(args.seeds) - args.controls:] \
        if args.controls else []
    for seed in args.seeds:
        # The chip does not hold two trees of 10 GB: the engines let go
        # of the last seed's before the next is made.
        for served in engines.values():
            served.eng._params = None
        params = init_params(Mixtral(pcfg), pcfg, seed=seed & 0x7FFFFFFF,
                             batch=1)
        prompts = [traffic.prompt_tokens(seed, i, n, cfg["vocab_size"],
                                         stream=9)
                   for i, n in enumerate(lengths)]
        results.append(compare(
            family, cfg, pcfg, params, prompts, options, engines, compiled,
            controls if seed in with_controls else (),
            f"{args.phase} seed {seed}"))
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
        "rows_factor": ROWS_FACTOR, "results": results,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind}}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
