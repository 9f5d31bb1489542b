"""LFM2-24B-A2B on the chip against its plain reference, where the
benchmark's own check cannot reach: more seeds, prompts that went through
two to four chunks, controls.

``perfbench``'s check of ``lfm2-hybrid-decode`` holds the whole-prompt
program's last row and 16 decoded positions of two prompts to the float32
reference, once a run; a chunk's rows it cannot capture. This script
drives the same programs, at the published widths and the cell's ten
layers, every expert held, the engine and cache as the cell builds them
(its pools cut to the pages the few requests here need, so that the
reference fits beside them), under the mix's own sampling:

    python chip_lfm2.py check --seeds 1 2 ... 12 --controls 3

At every seed: the check's two whole prompts and three prompts that go
through two, three and four chunks of the mix's ``prefill_chunk``, each
decoded ``check.decode_positions`` positions, all in one engine at once;
of every request the logits of its prompt's last row, of its decoded rows
and, what only this script sees, of the rows at which a chunk starts (a
chunk's first two rows are the ones a short convolution of three taps
computes from the state the chunk before it left) against the reference's
rows of the same positions, teacher-forced over the tokens the stream
received: of each row its largest difference over the request's largest
reference logit. ``max``: the largest row; ``decode_min``: the least-moved
decoded row (what the mix's ``rows_tolerance`` holds); ``boundary_min``
and ``boundary_max``: over the rows at which a chunk starts. One engine is
built and reused from seed to seed (the programs take the parameters as an
argument).

On the last ``--controls`` seeds the controls, programs wrong in one way
each and driven the same way (a wrong program's stream is its own: the
reference is teacher-forced over what it sampled): ``no_carry`` (a chunk
starts from zeros and not from the state the chunk before it left),
``bucket_end`` (the state left is that after the bucket's last padded row
and not after the last live one), ``swap_bc`` (the gates ``B`` and ``C``
exchanged), ``no_bias`` (the expert bias left out of the choice),
``float8`` (every bf16 matrix rounded to float8_e4m3). ``caught_by`` says
which written limit tells a control from the right program: the largest
row over the mix's ``tolerance``, the least-moved decoded row over its
``rows_tolerance``, the median decoded row over this script's
``MEDIAN_TOLERANCE``, or the least-moved chunk-boundary row over
``rows_tolerance`` (a state not carried moves every one of them).
``check`` exits 0 if the right program is under every limit on every
seed and every control is caught. It needs a TPU; ``--cpu``, ``--config``
and ``--mix`` are for a rehearsal at a tiny size. The last line is one
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

from chip_mellum import rounded_to_float8  # noqa: E402

# The median decoded row, this script's own limit beside the mix's two: the
# right program's reads 0.076-0.174 over 14 seeds and the expert bias left
# out of the choice 0.240-0.262 over 4 (my chip runs, PR 47), whose least-moved
# row lies inside the right program's tail and whose largest under the
# mix's tolerance; the harness computes a median row and does not judge it.
MEDIAN_TOLERANCE = 0.2

PROGRAM_CONTROLS = ("no_carry", "bucket_end", "swap_bc", "no_bias")
CONTROLS = PROGRAM_CONTROLS + ("float8",)  # last: it consumes the tree


def log(msg: str) -> None:
    print(f"[chip_lfm2] {msg}", flush=True)


def wrong_config(pcfg, control: str):
    """``pcfg`` wrong in one way: a field, or a short convolution that
    mishandles its state or its gates."""
    import jax.numpy as jnp

    from raytpu.models.llama import CONV
    from raytpu.models.mixtral import Lfm2MoeConfig
    from raytpu.models.short_conv import ShortConv

    if control == "no_bias":
        return dataclasses.replace(pcfg, choice_bias=None)

    # Both are wrong in a prompt's rows alone (``T > 1``): a decode step
    # goes through the same ``step`` at one row a sequence.
    class NoCarry(ShortConv):
        def step(self, x, state, seats, live, first):
            return super().step(x, state, seats, live,
                                first if live.shape[1] == 1 else True)

    class BucketEnd(ShortConv):
        def step(self, x, state, seats, live, first):
            return super().step(
                x, state, seats,
                live if live.shape[1] == 1 else jnp.ones_like(live), first)

    class SwapBC(ShortConv):
        def _gates(self, x):
            c, b, u = jnp.split(self.in_proj(x), 3, axis=-1)
            return b * u, c

    wrong = {"no_carry": NoCarry, "bucket_end": BucketEnd,
             "swap_bc": SwapBC}[control]

    @dataclasses.dataclass(frozen=True)
    class WrongConfig(Lfm2MoeConfig):
        def attention(self, kind="full_attention", **kw):
            if kind == CONV:
                return wrong(self, **kw)
            return super().attention(kind, **kw)

    return WrongConfig(**{f.name: getattr(pcfg, f.name)
                          for f in dataclasses.fields(pcfg)})


class Served:
    """One engine, built once, that keeps of every request the logits of
    the rows asked for, by position: a whole prompt's, a chunk's and a
    decode's alike. The parameters and the requests are given a run."""

    def __init__(self, pcfg, params, options):
        from raytpu.inference import InferenceEngine

        gc.collect()
        self.eng = eng = InferenceEngine(pcfg, params, **options)
        self.rows, self.wanted, self.runs = {}, {}, 0
        self._last = None
        for name in ("_prefill_fn", "_chunk_fn", "_decode_fn"):
            setattr(eng, name, self._keeping(getattr(eng, name)))
        run_prefill, run_decode = eng._run_prefill, eng._run_decode

        def prefill_kept(seq, out):
            before = seq.cached_len
            n = run_prefill(seq, out)
            logits = self._last.reshape(-1, self._last.shape[-1])
            for pos in self.wanted[seq.request_id]:
                if before <= pos < seq.cached_len:
                    self.rows[seq.request_id][pos] = np.asarray(
                        logits[pos - before], np.float32)
            return n

        def decode_kept(seqs, out):
            before = [s.cached_len for s in seqs]
            n = run_decode(seqs, out)
            logits = np.asarray(self._last, np.float32)
            for i, (seq, at) in enumerate(zip(seqs, before)):
                if at in self.wanted[seq.request_id]:
                    self.rows[seq.request_id][at] = logits[i]
            return n

        eng._run_prefill, eng._run_decode = prefill_kept, decode_kept

    def _keeping(self, fn):
        def kept(*a):
            res = fn(*a)
            self._last = res[0]
            return res

        return kept

    def run(self, params, requests):
        """``requests``: ``(prompt, sampling, new tokens, positions whose
        rows to keep)`` each. Per request the tokens it was given and its
        kept rows by position."""
        from raytpu.inference.sampling import SamplingParams

        eng = self.eng
        eng._params = eng._config.serving.params(eng._config, params)
        self.runs += 1
        seqs = []
        for i, (prompt, sampling, new_tokens, wanted) in enumerate(requests):
            rid = f"r{self.runs}-{i}"
            self.wanted[rid], self.rows[rid] = sorted(wanted), {}
            seqs.append(eng.add_request(rid, prompt, SamplingParams(
                max_new_tokens=new_tokens, **sampling)))
        t0 = time.perf_counter()
        while eng.has_unfinished():
            eng.step()
        out = []
        for seq in seqs:
            rows = self.rows.pop(seq.request_id)
            wanted = self.wanted.pop(seq.request_id)
            assert sorted(rows) == wanted, (sorted(rows), wanted)
            out.append((list(seq.generated), rows))
        return out, round(time.perf_counter() - t0, 1)


def wanted_rows(prompt_len: int, new_tokens: int, chunk: int):
    """The positions whose rows are compared: the two rows at which each
    chunk after the first starts, the prompt's last row and the decoded
    ones."""
    starts = range(chunk, prompt_len, chunk) if prompt_len > chunk else ()
    return sorted(
        {q for at in starts for q in (at, at + 1) if q < prompt_len}
        | set(range(prompt_len - 1, prompt_len + new_tokens - 1)))


_REFERENCES = {}  # the jitted reference, by (tokens, rows asked for)


def reference_rows(family, cfg, params, tokens, positions):
    """The float32 reference's logits of ``positions``, one forward pass
    over ``tokens``."""
    import jax
    import jax.numpy as jnp

    key = (len(tokens), tuple(positions))
    if key not in _REFERENCES:
        _REFERENCES[key] = jax.jit(
            lambda p, t: family.logits(cfg, p, t, rows=list(positions)))
    return np.asarray(_REFERENCES[key](
        params, jnp.asarray([tokens], jnp.int32)))[0]


def moved(rows, want, prompt_len: int) -> dict:
    """Of each kept row its largest difference from the reference's row
    of that position over the request's largest reference logit (of the
    rows compared), by what computed it in the right program: a chunk's
    first rows, the prompt's last row, a decode."""
    positions = sorted(rows)
    got = np.stack([rows[p] for p in positions])
    per_row = np.abs(got - want).max(-1) / np.abs(want).max()
    last = prompt_len - 1
    out = {"boundary": [], "prompt": [], "decode": []}
    for pos, value in zip(positions, per_row):
        kind = ("decode" if pos > last else "prompt" if pos == last
                else "boundary")
        out[kind].append(float(value))
    return out


def summary(per_request) -> dict:
    every = {k: [x for r in per_request for x in r[k]]
             for k in ("boundary", "prompt", "decode")}
    flat = every["boundary"] + every["prompt"] + every["decode"]
    return {"max": max(flat), "decode_min": min(every["decode"]),
            "decode_median": float(np.median(every["decode"])),
            "decode_max": max(every["decode"]),
            "prompt_max": max(every["prompt"]),
            "boundary_min": min(every["boundary"], default=None),
            "boundary_max": max(every["boundary"], default=None)}


def caught_by(control: dict, tolerance: float, rows_tolerance: float):
    if control["max"] > tolerance:
        return "max"
    if control["decode_min"] > rows_tolerance:
        return "decode_min"
    if control["decode_median"] > MEDIAN_TOLERANCE:
        return "decode_median"
    if control["boundary_min"] is not None \
            and control["boundary_min"] > rows_tolerance:
        return "boundary_min"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("phase", choices=("check",))
    ap.add_argument("--seeds", type=int, nargs="*", default=[2147483659])
    ap.add_argument("--controls", type=int, default=0,
                    help="the controls on this many of the last seeds")
    ap.add_argument("--only", nargs="*", default=None, choices=CONTROLS,
                    help="of the controls, these alone")
    ap.add_argument("--chunked", type=int, nargs="*", default=None,
                    help="lengths of the prompts that go through chunks "
                         "(default: 1.2, 2.3 and 3.1 chunks)")
    ap.add_argument("--config", default=None,
                    help="a configuration file (default: the cell's)")
    ap.add_argument("--mix", default=None, help="a mix file likewise")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from perfbench import run, traffic

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.cpu:
        log(f"no TPU: {devices}")
        return 2

    def load(path, kind, name):
        if path is None:
            return run.load_json([run.HERE], kind, name)
        with open(path) as f:
            return json.load(f)

    cfg = load(args.config, "configs", "lfm2-24b-a2b")
    mix = load(args.mix, "traffic", "hybrid-decode")
    family = run.load_family([run.HERE], cfg)
    pcfg = family.program_config(cfg, mix.get("model_overrides", ()))
    options = dict(mix["engine_options"])
    chunk = options["prefill_chunk"]
    lengths = list(mix["check"]["prompt_tokens"]) + (
        args.chunked if args.chunked is not None
        else [int(chunk * f) for f in (1.2, 2.3, 3.1)])
    new_tokens = int(mix["check"].get("decode_positions", 8)) + 1
    # Pages for these requests alone: the reference needs the room.
    options["num_pages"] = 1 + sum(
        -(-(n + new_tokens) // options["page_size"]) for n in lengths)
    tolerance = float(mix["check"]["tolerance"])
    rows_tolerance = float(mix["check"]["rows_tolerance"])
    init = jax.jit(family.train_parts(pcfg)[0])
    vocab = int(cfg["vocab_size"])
    results, right = [], None
    ok = True
    for n, seed in enumerate(args.seeds):
        if right is not None:  # the chip does not hold two trees
            right.eng._params = None
            gc.collect()
        params = init(jax.random.PRNGKey(seed & 0x7FFFFFFF))
        if right is None:
            right = Served(pcfg, params, options)
        prompts = [traffic.prompt_tokens(seed, i, length, vocab, stream=9)
                   for i, length in enumerate(lengths)]
        sampling = [traffic.request_sampling(mix, seed, -100 - i)
                    for i in range(len(lengths))]

        def reading(served, tree, which):
            """The requests ``which`` through ``served`` as streams of
            their own, against the reference over what each received."""
            out, seconds = served.run(tree, [
                (prompts[i], sampling[i], new_tokens,
                 wanted_rows(len(prompts[i]), new_tokens, chunk))
                for i in which])
            refs, per_request = {}, []
            for i, (tokens, rows) in zip(which, out):
                full = prompts[i] + tokens[:-1]
                want = reference_rows(family, cfg, tree, full, sorted(rows))
                refs[i] = (full, sorted(rows), want)
                per_request.append(moved(rows, want, len(prompts[i])))
            return dict(summary(per_request), seconds=seconds), refs

        everything = list(range(len(prompts)))
        got, refs = reading(right, params, everything)
        result = {"seed": seed, "prompt_tokens": lengths, "right": got}
        ok &= caught_by(got, tolerance, rows_tolerance) is None
        log(json.dumps(result))
        if n >= len(args.seeds) - args.controls:
            # A whole prompt and the prompts of two and four chunks.
            which = [i for i in (1, 2, len(lengths) - 1) if i < len(lengths)]
            for control in args.only or CONTROLS:
                t0 = time.perf_counter()
                if control == "float8":
                    # The tree is consumed, so the reference is the right
                    # program's, over the tokens its streams received,
                    # and the rounded weights are teacher-forced on them:
                    # every row a prompt's (a whole prompt's or a chunk's).
                    params = rounded_to_float8(params)
                    out, seconds = right.run(params, [
                        (refs[i][0], {}, 1, refs[i][1]) for i in which])
                    got = dict(summary([
                        moved(rows, refs[i][2], len(prompts[i]))
                        for i, (_, rows) in zip(which, out)]),
                        seconds=seconds)
                else:
                    wrong = Served(wrong_config(pcfg, control), params,
                                   options)
                    got, _ = reading(wrong, params, which)
                    del wrong
                    gc.collect()
                got["caught_by"] = caught_by(got, tolerance, rows_tolerance)
                got["wall_s"] = round(time.perf_counter() - t0, 1)
                ok &= got["caught_by"] is not None
                result[control] = got
                log(json.dumps({"seed": seed, control: got}))
        results.append(result)
        del params
        gc.collect()
    print(json.dumps({"ok": bool(ok), "device": devices[0].device_kind,
                      "tolerance": tolerance,
                      "rows_tolerance": rows_tolerance,
                      "results": results}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
