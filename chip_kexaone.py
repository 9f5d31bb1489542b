"""K-EXAONE-236B-A23B on the chip against its plain reference, where the
benchmark's own check cannot reach: more seeds, the prediction module's
own logits, controls.

``perfbench``'s check of ``kexaone-selfdraft-decode`` holds the
whole-prompt program and 16 decoded positions of two prompts (steps of
two positions a sequence, one or two kept) to the float32 reference, once
a run. This script drives the same programs, at the published widths and
the cell's five layers, module and 16 held experts, engine and cache as
the cell builds them, under the mix's own sampling:

    python chip_kexaone.py check --seeds 1 2 ... 12 --controls 3

``check`` reads at every seed the cell's check (``rel_err``: the largest
logit difference over the largest reference logit, over the prompt's last
row and the decoded positions, the engine's rows found by (request,
position) through ``perfbench.probe.kept_rows``; ``rows_min`` the least
over the decoded rows) and, what the cell's check does not see, the
module's logits every step left on the device against the reference's
``draft_logits`` (``draft_rel_err``, ``draft_rows_min``), with the drafts
verified and kept. One engine is built and reused from seed to seed (the
programs take the parameters as an argument), so a seed costs its
weights, its requests and the references.

On the last ``--controls`` seeds the controls, programs wrong in one way
each: ``full_roped`` (the full layers roped too), ``no_qk_norm`` (the
heads' norms of q and k left out), ``no_scale`` (``routed_scaling_factor``
left out), ``no_window`` (a window of 512, past both prompts: ignored),
``float8`` (every bf16 matrix rounded to float8_e4m3). A wrong program is
teacher-forced on the right program's tokens (as ``chip_joyai.py``: the
prompt and the sampled tokens as one prompt, judged on its last rows), the
right program the same way (``forced``) gives the reading to hold them
against, and ``caught_by`` says which statistic tells them apart (the
largest row over the mix's tolerance, else the least-moved or the median
row ``ROWS_FACTOR`` times the right program's). ``check`` exits 0 if the
right program is under the mix's limits on every seed and every control
is caught. The same controls but ``no_qk_norm`` and ``float8`` also go
through the benchmark's own comparison with ``perfbench/tests/
chip_rows.py --override`` (``rope_kinds=null``, ``routed_scale=1.0``,
``window=512``). It needs a TPU; ``--cpu``, ``--config`` and ``--mix`` are
for the rehearsal in ``tests/test_exaone_moe.py``. The last line is one
JSON object.
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

from chip_joyai import Served, caught_by  # noqa: E402
from chip_mellum import rel_errs, rounded_to_float8  # noqa: E402

# A control's least-moved (or median) row over the right program's on the
# same seed and tokens. The right program served and teacher-forced read
# within 1.15 x of each other and the weakest control, the full layers
# roped, 1.76-2.34 x (my chip runs, PR 42: under seeded weights attention
# over hundreds of random keys hardly sees a rotation); every other
# control reads 7 x and more. ``chip_mellum.py``'s 3.0 would pass it.
ROWS_FACTOR = 1.5

PROGRAM_CONTROLS = ("full_roped", "no_qk_norm", "no_scale", "no_window")
CONTROLS = PROGRAM_CONTROLS + ("float8",)  # last: it consumes the tree


def log(msg: str) -> None:
    print(f"[chip_kexaone] {msg}", flush=True)


def wrong_config(pcfg, control: str):
    """``pcfg`` wrong in one way: a field, or an attention module that
    leaves the heads' norms out (the tree keeps their scales)."""
    from raytpu.models.llama import LlamaAttention
    from raytpu.models.mixtral import ExaoneMoeConfig

    fields = {"full_roped": dict(rope_kinds=None),
              "no_scale": dict(routed_scale=1.0),
              "no_window": dict(window=512)}
    if control in fields:
        return dataclasses.replace(pcfg, **fields[control])

    class Unnormed(LlamaAttention):
        def _qkv(self, x):
            self.q_norm, self.k_norm  # the tree's scales, not applied
            return self.q_proj(x), self.k_proj(x), self.v_proj(x)

    @dataclasses.dataclass(frozen=True)
    class WrongConfig(ExaoneMoeConfig):
        def attention(self, kind="full_attention", **kw):
            return Unnormed(self, kind, **kw)

    return WrongConfig(**{f.name: getattr(pcfg, f.name)
                          for f in dataclasses.fields(pcfg)})


class Right:
    """The cell's engine under the benchmark's probe, built once; the
    parameters and the requests are given a run."""

    def __init__(self, pcfg, params, options):
        from perfbench import probe

        self.probe = probe
        self.eng = probe.ProbedEngine(pcfg, params, **options)
        self.runs = 0

    def run(self, params, prompts, sampling, new_tokens):
        """Per prompt the tokens it was given, its kept rows by position
        (the prompt's last and the decoded ones) and the module's logits
        by the position of the module's row, and what the engine ran."""
        from raytpu.inference.sampling import SamplingParams

        eng = self.eng
        eng._params = eng._config.serving.params(eng._config, params)
        self.runs += 1
        captured = eng.capture_logits()
        seqs = [eng.add_request(f"r{self.runs}-{i}", p, SamplingParams(
            max_new_tokens=new_tokens, **s))
            for i, (p, s) in enumerate(zip(prompts, sampling))]
        tokens = {s.request_id: [] for s in seqs}
        drafts = {s.request_id: {} for s in seqs}
        t0, first = time.perf_counter(), len(eng.steps)
        while eng.has_unfinished():
            for o in eng.step():
                tokens[o.request_id].append(o.token_id)
            state = np.asarray(eng._draft_state[1])
            for seq in seqs:
                slot = eng.cache._seats.get(seq.request_id)
                if slot is not None and seq.cached_len >= seq.prefill_len:
                    drafts[seq.request_id][seq.cached_len - 1] = state[slot]
        eng.stop_capture()
        rows = self.probe.kept_rows(captured, {
            s.request_id: range(len(s.prompt) - 1, s.num_tokens - 1)
            for s in seqs})
        steps = [r.program.as_dict() for r in eng.steps[first:]]
        facts = {"seconds": round(time.perf_counter() - t0, 1),
                 "drafted": sum(s.get("drafted", 0) for s in steps),
                 "accepted": sum(s.get("accepted", 0) for s in steps),
                 "decode_steps": sum(1 for s in steps if s["decodes"]),
                 "programs": sorted(eng.stats()["decode_compiles"])}
        ids = [s.request_id for s in seqs]
        return ([tokens[r] for r in ids], [rows[r] for r in ids],
                [drafts[r] for r in ids], facts)


_REFERENCES = {}


def reference(family, cfg, params, seq, rows, which):
    """The reference's ``logits`` or ``draft_logits`` of ``rows`` of
    ``seq``, one compiled function a (kind, length, rows)."""
    import jax
    import jax.numpy as jnp

    key = (which, len(seq), tuple(rows))
    if key not in _REFERENCES:
        fn = getattr(family, which)
        _REFERENCES[key] = jax.jit(
            lambda p, t: fn(cfg, p, t, rows=list(rows)))
    return np.asarray(_REFERENCES[key](
        params, jnp.asarray([seq], jnp.int32)))[0]


def compare(family, cfg, pcfg, params, prompts, sampling, positions,
            options, engines, controls, label) -> dict:
    if "right" not in engines:
        engines["right"] = Right(pcfg, params, options)
    tokens, rows, drafts, facts = engines["right"].run(
        params, prompts, sampling, positions + 1)
    got, want, dgot, dwant = [], [], [], []
    for prompt, toks, by_pos, module in zip(prompts, tokens, rows, drafts):
        seq = list(prompt) + list(toks[:positions])
        at = sorted(by_pos)
        assert at == list(range(len(prompt) - 1, len(prompt) + positions))
        got.append(np.stack([by_pos[p] for p in at]))
        want.append(reference(family, cfg, params, seq, at, "logits"))
        # The module's row at p reads token p + 1: those the stream holds.
        mat = sorted(p for p in module if p + 1 < len(seq))
        dgot.append(np.stack([module[p] for p in mat]))
        # (Every row the module can have, so that one compiled reference
        # serves every seed, and of them the rows this run left.)
        span = list(range(len(prompt) - 1, len(seq) - 1))
        dwant.append(reference(family, cfg, params, seq, span,
                               "draft_logits")[[span.index(p) for p in mat]])
    errs = rel_errs(got, want)
    decoded = rel_errs([g[1:] for g in got], [w[1:] for w in want])
    # (Over each prompt's largest reference logit, as the check has it.)
    derrs = rel_errs(dgot, dwant)
    out = {"label": label, "prompt_tokens": [len(p) for p in prompts],
           "rel_err": errs["max"], "rows_median": errs["median"],
           "rows_min": errs["min"], "decode_rows_min": decoded["min"],
           "draft_rel_err": derrs["max"], "draft_rows_min": derrs["min"],
           "draft_rows_median": derrs["median"],
           "draft_rows": int(sum(len(d) for d in dgot)), **facts}
    log(json.dumps(out))
    if not controls:
        return out
    forced = [list(p) + list(t[:positions]) for p, t in zip(prompts, tokens)]
    for control in ("forced",) + tuple(controls):
        if control in PROGRAM_CONTROLS:
            if control not in engines:
                engines[control] = Served(wrong_config(pcfg, control),
                                          params, options)
            eng, tree = engines[control], params
        else:
            if "forced" not in engines:
                engines["forced"] = Served(pcfg, params, options)
            eng = engines["forced"]
            tree = rounded_to_float8(params) if control == "float8" \
                else params
        bad, _, _ = eng.run(tree, forced, tail=positions + 1, new_tokens=1)
        out[control] = rel_errs(bad, want)
        log(json.dumps({"label": label, "control": control,
                        **out[control]}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("phase", choices=("check",))
    ap.add_argument("--seeds", type=int, nargs="*", default=[2147483659])
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
        sys.exit(f"chip_kexaone.py needs a TPU and found none: "
                 f"jax.devices()[0].platform == {devices[0].platform!r}")
    with open(args.config or os.path.join(
            run.HERE, "configs", "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    with open(args.mix or os.path.join(
            run.HERE, "traffic", "selfdraft-decode.json")) as f:
        mix = json.load(f)
    family = run.load_family([run.HERE], cfg)
    pcfg = family.program_config(cfg, mix.get("model_overrides", ()))
    options = dict(mix["engine_options"])
    lengths = mix["check"]["prompt_tokens"]
    positions = int(mix["check"]["decode_positions"])
    # The cell's programs over pools for these two prompts alone.
    options["num_pages"] = 2 * -(-(max(lengths) + positions + 3)
                                 // options["page_size"]) + 2
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
        sampling = [traffic.request_sampling(mix, seed, -100 - i)
                    for i in range(len(lengths))]
        results.append(compare(
            family, cfg, pcfg, params, prompts, sampling, positions,
            options, engines, controls if seed in with_controls else (),
            f"check seed {seed}"))
        # The chip does not hold two trees: nothing keeps this seed's.
        del params
        for served in engines.values():
            served.eng._params = None
            if hasattr(served.eng, "params_given"):
                served.eng.params_given = None
        gc.collect()
    spec = mix["check"]
    tolerance = float(spec["tolerance"])
    rows_tolerance = float(spec.get("rows_tolerance", np.inf))
    worst = max(r["rel_err"] for r in results)
    for r in results:
        if "forced" in r:
            r["caught_by"] = {c: caught_by(r, c, tolerance, ROWS_FACTOR)
                              for c in controls}
    passed = worst <= tolerance \
        and max(r["decode_rows_min"] for r in results) <= rows_tolerance \
        and all(all(r["caught_by"].values())
                for r in results if "forced" in r)
    drafted = sum(r["drafted"] for r in results)
    print(json.dumps({
        "ok": bool(passed), "tolerance": tolerance,
        "rows_tolerance": rows_tolerance, "worst_rel_err": worst,
        "rel_errs": sorted(r["rel_err"] for r in results),
        "decode_rows_mins": sorted(r["decode_rows_min"] for r in results),
        "draft_rel_errs": sorted(r["draft_rel_err"] for r in results),
        "draft_rows_mins": sorted(r["draft_rows_min"] for r in results),
        "accepted_of_drafted": [sum(r["accepted"] for r in results),
                                drafted],
        "rows_factor": ROWS_FACTOR, "results": results,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind}}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
