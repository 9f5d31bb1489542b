"""Mellum2 on the chip against its plain reference, where the benchmark's
own check cannot reach.

``perfbench``'s check of ``mellum2-long-decode`` holds the whole-prompt
program and eight decodes of two prompts of 1,100-1,250 tokens to the
float32 reference. The timed path is another one: prompts of 8,000-32,000
tokens through the *chunk* program (2,048 rows a call against both kinds
of KV pool) and decodes over contexts of tens of thousands of positions.
This script drives that path, at the published widths and the cell's 8
layers, engine and cache as the cell builds them, and compares what it
produced with the reference computed in blocks for the same rows:

    python chip_mellum.py check --seeds 1 2 3    # the cell's check, more seeds,
                                                 # the four controls on the
                                                 # last two
    python chip_mellum.py long --tokens 12000 40000   # chunks, then 8 decodes
    python chip_mellum.py long --seeds 1 2 --tokens 12000 --controls
    python chip_mellum.py long --together --tokens 39000 39500 40000 40500

Each line of output is one comparison: the largest logit difference over
the largest reference logit (``rel_err``, as the benchmark's check has
it) of the prompt's last row and the decoded positions. ``long`` gives
each prompt an engine of one slot; ``--together`` gives all of them one
engine with the cell's slots and decode bucket, where every sequence
still in its prompt takes a chunk a step: prompts of as many chunks end
together and decode in one batch.

The controls are programs that are wrong in one way each: ``no_window``
(the window layers attend their whole context), ``plain_rope`` (no YaRN
on the full layers), ``no_renorm`` (the router's weights not divided by
their sum), ``float8`` (every bf16 matrix rounded to float8_e4m3). A
control is caught if it reads above the cell's tolerance
(``perfbench/traffic/long-decode.json``: the largest error over the
rows, the benchmark's check) or, where the largest error is the routed
layer's own and hides it, if its least-moved row reads ``ROWS_FACTOR``
times the right program's on the same weights and tokens. It needs a
TPU; ``--cpu``, ``--config``, ``--mix`` and small ``--tokens`` are for
the rehearsal in ``tests/test_mellum.py``. The last line is one JSON
object.
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

DECODES = 8
CONTROLS = ("no_window", "plain_rope", "no_renorm", "float8")
# Plain rope on the full layers moves every row a little and none by
# much; a token that takes another eighth expert in bfloat16 than in
# float32 moves its row by more, and on some seeds that is most rows. So
# neither the largest error over the rows (plain rope 0.065-0.090, the
# right program 0.036-0.085) nor the median (0.049-0.061 beside
# 0.004-0.021) holds it on every seed. A fault in the mathematics moves
# every row, so it is judged on the row it moved least: the right
# program's 0.0041-0.0054, plain rope's 0.032-0.048, 6 to 12 times, the
# other three 30 times and more (v5e, four seeds, the check and 12,000
# tokens: PERF.md section 6, PR 32).
ROWS_FACTOR = 3.0


def log(msg: str) -> None:
    print(f"[chip_mellum] {msg}", flush=True)


def wrong_config(pcfg, control: str):
    from raytpu.models.llama import FULL

    return {"no_window": lambda: dataclasses.replace(
                pcfg, layer_types=(FULL,) * pcfg.n_layer),
            "plain_rope": lambda: dataclasses.replace(pcfg, full_rope=None),
            "no_renorm": lambda: dataclasses.replace(
                pcfg, norm_topk_prob=False),
            "float8": lambda: pcfg}[control]()


def rounded_to_float8(params):
    """Every bf16 matrix through float8_e4m3 and back, one leaf at a
    time and each conversion a program of its own: inside one jitted
    program the compiler may keep the excess precision and drop the
    pair (it did: PERF.md, PR 32). The chip does not hold two trees, so
    ``params`` is consumed."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        if a.dtype != jnp.bfloat16 or a.ndim < 2:
            return a
        low = a.astype(jnp.float8_e4m3fn)
        a.delete()
        return low.astype(jnp.bfloat16)

    return jax.tree_util.tree_map(rounded, params)


def served_rows(pcfg, params, prompts, engine_options, tail: int = 1,
                new_tokens: int = DECODES + 1):
    """The prompts through the engine as the serve path drives it, all at
    once: per prompt the float32 logits of its last ``tail`` rows and of
    ``new_tokens - 1`` decoded
    positions, the tokens it sampled, and what the engine ran."""
    from raytpu.inference import InferenceEngine
    from raytpu.inference.sampling import SamplingParams

    gc.collect()  # the last engine's pools: its wrappers hold it in a cycle
    eng = InferenceEngine(pcfg, params, **engine_options)
    ids = [f"r{i}" for i in range(len(prompts))]
    rows = {rid: [] for rid in ids}
    # The logits of the prefill program that ran last; who decodes now.
    current = {"logits": None, "decoding": []}
    prefill, chunk, decode = eng._prefill_fn, eng._chunk_fn, eng._decode_fn

    def prefill_kept(*a):
        res = prefill(*a)
        current["logits"] = res[0]
        return res

    def chunk_kept(*a):
        res = chunk(*a)
        current["logits"] = res[0][0]
        return res

    def decode_kept(*a):
        res = decode(*a)
        got = np.asarray(res[0], np.float32)
        for i, rid in enumerate(current["decoding"]):
            rows[rid].append(got[i])
        return res

    eng._prefill_fn, eng._chunk_fn, eng._decode_fn = (
        prefill_kept, chunk_kept, decode_kept)
    run_prefill, run_decode = eng._run_prefill, eng._run_decode

    def run_prefill_kept(seq, out):
        start = seq.cached_len
        n = run_prefill(seq, out)
        # The prompt's last rows, from the programs that held them.
        lo = max(len(seq.prompt) - tail, start)
        hi = min(len(seq.prompt), seq.cached_len)
        if lo < hi and not seq.generated[1:]:
            rows[seq.request_id].extend(np.asarray(
                current["logits"][lo - start:hi - start], np.float32))
        return n

    def run_decode_kept(seqs, out):
        current["decoding"] = [s.request_id for s in seqs]
        return run_decode(seqs, out)

    eng._run_prefill, eng._run_decode = run_prefill_kept, run_decode_kept
    for rid, prompt in zip(ids, prompts):
        eng.add_request(rid, prompt, SamplingParams(
            max_new_tokens=new_tokens))
    tokens = {rid: [] for rid in ids}
    t0 = time.perf_counter()
    while eng.has_unfinished():
        for o in eng.step():
            tokens[o.request_id].append(o.token_id)
    stats = eng.stats()
    log_steps = eng.step_log()["steps"]
    facts = {
        "seconds": round(time.perf_counter() - t0, 1),
        "programs": {k: sorted(stats[k]) for k in (
            "prefill_compiles", "chunk_prefill_compiles", "decode_compiles")},
        "window_pages_released": sum(
            s["window_pages_released"] for s in log_steps),
        "live_pages_full_max": max(s["live_pages_full"] for s in log_steps),
        "live_pages_window_max": max(
            s["live_pages_window"] for s in log_steps),
        "preemptions": stats["num_preemptions"]}
    return ([np.stack(rows[rid][:tail + new_tokens - 1]) for rid in ids],
            [tokens[rid] for rid in ids], facts)


def reference_rows(family, cfg, params, prompt, sampled):
    """The reference's logits of the prompt's last row and the ``DECODES``
    decoded positions, teacher-forced on what the engine sampled."""
    import jax
    import jax.numpy as jnp

    seq = list(prompt) + list(sampled[:DECODES])
    rows = list(range(len(prompt) - 1, len(prompt) + DECODES))
    fn = jax.jit(lambda p, t: family.logits(cfg, p, t, rows=rows))
    return np.asarray(fn(params, jnp.asarray([seq], jnp.int32)))[0]


def rel_errs(got, want) -> dict:
    """Over the prompts' rows: the largest logit difference over the
    largest reference logit of the prompt (``max``, the benchmark's
    check), and the least and the median over the rows of each row's
    own: top-8 of 64 with renormalised weights is
    discontinuous, one expert chosen otherwise moves a row by more than
    rounding does, and ``min`` does not see the rows that happens to."""
    per_row = np.concatenate([np.abs(g - w).max(-1) / np.abs(w).max()
                              for g, w in zip(got, want)])
    return {"max": float(per_row.max()), "min": float(per_row.min()),
            "median": float(np.median(per_row))}


def compare(family, cfg, pcfg, params, prompts, engine_options, controls,
            label) -> dict:
    got, sampled, facts = served_rows(pcfg, params, prompts, engine_options)
    want = [reference_rows(family, cfg, params, p, s)
            for p, s in zip(prompts, sampled)]
    errs = rel_errs(got, want)
    out = {"label": label, "prompt_tokens": [len(p) for p in prompts],
           "rel_err": errs["max"], "rel_err_median": errs["median"],
           **facts}
    log(json.dumps(out))
    if not controls:
        return out
    # A wrong program samples tokens of its own, which the reference was
    # not forced on. So it is given the prompt and the tokens the right
    # program sampled as one prompt, and judged on that prompt's last
    # rows (the same positions, through its prefill program); the right
    # program the same way gives the reading to hold them against.
    forced = [list(p) + list(s[:DECODES]) for p, s in zip(prompts, sampled)]
    for control in ("forced",) + tuple(controls):
        wrong = pcfg if control == "forced" else wrong_config(pcfg, control)
        if control == "float8":  # the last one: it consumes the tree
            params = rounded_to_float8(params)
        bad, _, _ = served_rows(wrong, params, forced, engine_options,
                                tail=DECODES + 1, new_tokens=1)
        out[control] = rel_errs(bad, want)
        log(json.dumps({"label": label, "control": control,
                        **out[control]}))
    return out


def caught_by(result: dict, control: str, tolerance: float):
    """What sees the control in ``result``: ``"max"`` (the benchmark's
    check would), ``"min"`` or ``None``."""
    if result[control]["max"] > tolerance:
        return "max"
    if result[control]["min"] > ROWS_FACTOR * result["forced"]["min"]:
        return "min"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("phase", choices=("check", "long"))
    ap.add_argument("--seeds", type=int, nargs="*", default=[2147483659])
    ap.add_argument("--tokens", type=int, nargs="*", default=[12000, 40000])
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--together", action="store_true",
                    help="long: every prompt in one engine of the "
                    "cell's slots")
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
        sys.exit(f"chip_mellum.py needs a TPU and found none: "
                 f"jax.devices()[0].platform == {devices[0].platform!r}")
    with open(args.config or os.path.join(
            run.HERE, "configs", "mellum2-12b-a2.5b.json")) as f:
        cfg = json.load(f)
    with open(args.mix or os.path.join(
            run.HERE, "traffic", "long-decode.json")) as f:
        mix = json.load(f)
    family = run.load_family([run.HERE], cfg)
    pcfg = family.program_config(cfg, mix.get("model_overrides", ()))
    options = dict(mix["engine_options"])
    controls = CONTROLS if args.controls or args.phase == "check" else ()
    results = []
    for seed in args.seeds:
        params = init_params(Mixtral(pcfg), pcfg, seed=seed & 0x7FFFFFFF,
                             batch=1)
        if args.phase == "check":
            lengths = mix["check"]["prompt_tokens"]
            prompts = [traffic.prompt_tokens(seed, i, n, cfg["vocab_size"],
                                             stream=9)
                       for i, n in enumerate(lengths)]
            # The cell's programs over pools for these two prompts alone:
            # ``no_window`` makes every pool a full one, and at the
            # cell's 7,553 pages that is 15 GB.
            options["num_pages"] = 2 * -(-(max(lengths) + DECODES + 1)
                                         // options["page_size"]) + 2
            # The controls on the last two seeds only: they cost four
            # engines and the tree each.
            results.append(compare(
                family, cfg, pcfg, params, prompts, options,
                controls if seed in args.seeds[-2:] else (),
                f"check seed {seed}"))
        elif args.together:
            # The cell's slots, decode bucket, table widths, page and
            # chunk; pools for these prompts.
            options["num_pages"] = 2 + sum(
                -(-(n + DECODES + 2) // options["page_size"])
                for n in args.tokens)
            prompts = [traffic.prompt_tokens(seed, n, n, cfg["vocab_size"],
                                             stream=9)
                       for n in args.tokens]
            results.append(compare(
                family, cfg, pcfg, params, prompts, options, controls,
                f"long together seed {seed}"))
        else:
            # A pool for one long sequence, the cell's page and chunk.
            longest = max(args.tokens) + DECODES + 2
            options.update(
                max_num_seqs=1, decode_buckets=[1],
                max_model_len=min(options["max_model_len"],
                                  -(-longest // options["page_size"])
                                  * options["page_size"]),
                num_pages=-(-longest // options["page_size"]) + 2)
            # Longest first; the controls, which consume the tree, on the
            # shortest.
            for n in sorted(args.tokens, reverse=True):
                prompt = traffic.prompt_tokens(seed, n, n,
                                               cfg["vocab_size"], stream=9)
                results.append(compare(
                    family, cfg, pcfg, params, [prompt], options,
                    controls if n == min(args.tokens) else (),
                    f"long {n} seed {seed}"))
        del params
    tolerance = float(mix["check"]["tolerance"])
    worst = max(r["rel_err"] for r in results)
    for r in results:
        if "forced" in r:
            r["caught_by"] = {c: caught_by(r, c, tolerance)
                              for c in CONTROLS}
    passed = worst <= tolerance and all(
        all(r["caught_by"].values()) for r in results if "forced" in r)
    print(json.dumps({
        "ok": bool(passed), "tolerance": tolerance, "worst_rel_err": worst,
        "rows_factor": ROWS_FACTOR, "results": results,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind}}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
