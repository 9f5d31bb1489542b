"""Ling-3.0-flash-VL's language model on the chip against its plain
reference, where the benchmark's own check cannot reach: more seeds,
prompts that went through two chunks, controls.

``perfbench``'s check of ``ling-kda-decode`` holds the whole-prompt
program's last row and 16 decoded positions of two prompts to the float32
reference, once a run; a chunk's rows it cannot capture. This script drives
the same programs, at the published widths and the cell's seven layers, the
engine and cache as the cell builds them (its pool cut to the pages the few
requests here need and its seats to 16, so that the reference fits beside
them), under the mix's own sampling:

    python chip_ling.py check --seeds 1 2 ... 12 --controls 3

At every seed: the check's two whole prompts and two prompts that go
through two chunks of the mix's ``prefill_chunk``, each decoded
``check.decode_positions`` positions, all in one engine at once; of every
request the logits of its prompt's last row, of its decoded rows and, what
only this script sees, of the two rows at which the second chunk starts
(the first rows computed from the state the chunk before it left at the
seat) against the reference's rows of the same positions, teacher-forced
over the tokens the stream received: of each row its largest difference
over the request's largest reference logit (``chip_lfm2.py``'s machinery,
which this script imports). ``max``: the largest row; ``decode_min``: the
least-moved decoded row (what the mix's ``rows_tolerance`` holds);
``boundary_min`` and ``boundary_max``: over the rows at which a chunk
starts. One engine is built and reused from seed to seed.

And two readings that are of no logits, of the matrices each KDA layer
holds ``[H, d, d]`` at a request's seat when the request ends; of each
layer the norm of a difference over the norm of what it is compared with.
``state_min`` / ``state_max``: against the reference's state after the
same tokens (``families/ling_hybrid.py:kda_states``, the literal
recurrence): the least and the largest over layers and requests; how far
the bf16 activations under the float32 recurrence carry the state from
the float32 model's, reported and held to nothing. ``replay_min``: the
same tokens are served once more, all of them as a prompt (one new
token), and the state the prompt's program leaves is compared with the
state the decoded rows left: the least over layers and requests (the first
KDA layer's as a rule, whose inputs are the embedding's rows in both
runs). The right program computes both in float32 from the same bf16
activations and they differ by float32's rounding; a state array of a
narrower type differs by that type's rounding at every decoded position.
``replay_min`` is held to ``REPLAY_TOLERANCE`` below, this script's own
limit and not the cell's: ``serve_cell.check_logits`` compares rows of
logits, and no row of logits tells a state held in bfloat16 from the
float32 one (a state that decays forgets a rounding about as fast as it
makes the next; the readings are in ``kda-decode.json``'s ``check.why``).

On the last ``--controls`` seeds the controls, programs wrong in one way
each and driven the same way (a wrong program's stream is its own: the
reference is teacher-forced over what it sampled): ``bf16_state`` (the
matrix state held in bfloat16 and not float32: the nearest precision below
the one the configuration states), ``decay_after`` (the decay applied
after the update and not before), ``no_beta`` (``beta`` left out: 1),
``plain_topk`` (the 8 best experts of all 512 in the place of the best
inside the best 4 of 8 groups), ``bucket_end`` (the state left is that
after the bucket's last padded row and not after the last live one),
``no_carry`` (a chunk starts from zeros and not from the state the chunk
before it left), ``float8`` (every bf16 matrix rounded to float8_e4m3).
``caught_by`` says which limit tells a control from the right program:
the largest row over the mix's ``tolerance`` (``max``), the least-moved
decoded row over its ``rows_tolerance`` (``decode_min``), which are the
cell's two, or the decoded state against the prompt program's over
``REPLAY_TOLERANCE`` (``replay_min``), which is this script's. ``check``
exits 0 if the right program is under all three on every seed and every
control is caught;
``caught_by_the_cell`` in the last line lists the controls one of the
cell's own two limits caught on every seed they ran on.
It needs a TPU; ``--cpu``, ``--config`` and ``--mix`` are for a rehearsal
at a tiny size. The last line is one JSON object.
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

from chip_lfm2 import (Served, moved, reference_rows,  # noqa: E402
                       summary, wanted_rows)
from chip_mellum import rounded_to_float8  # noqa: E402

# The most a right program's decoded state may differ from the state its
# own prompt program leaves over the same tokens (``replay_min``).
# READINGS (my chip runs, PR 59): the right program 1.6e-5 to 2.0e-5
# (seeds 11, 12 at 16 seats, 13 at 128; the kernel's sums against the
# chunked form's, both float32), the matrix state held in bfloat16 6.0e-3
# and 6.1e-3 (seeds 11, 12; 16 decoded positions): 50 x over the one, 6 x
# under the other. Every other control reads as the right program does
# here or is caught before (``decay_after`` reads 0: its recurrence is
# one scan for prompt and decode alike).
REPLAY_TOLERANCE = 1e-3

PROGRAM_CONTROLS = ("bf16_state", "decay_after", "no_beta", "plain_topk",
                    "bucket_end", "no_carry")
CONTROLS = PROGRAM_CONTROLS + ("float8",)  # last: it consumes the tree


def log(msg: str) -> None:
    print(f"[chip_ling] {msg}", flush=True)


def wrong_config(pcfg, control: str):
    """``pcfg`` wrong in one way: a field, or a KDA operator that
    mishandles its state, its gates or its recurrence."""
    import jax
    import jax.numpy as jnp

    from raytpu.models.gpt2 import State
    from raytpu.models.kda import KimiDeltaAttention
    from raytpu.models.llama import KDA
    from raytpu.models.mixtral import LingHybridConfig
    from raytpu.ops.kda import kda_decode_reference

    if control == "plain_topk":
        return dataclasses.replace(pcfg, n_group=1, topk_group=1)

    # The next two are wrong in a prompt's rows alone (``T > 1``): a decode
    # step goes through the same ``step`` at one row a sequence.
    class NoCarry(KimiDeltaAttention):
        def step(self, x, state, tails, seats, live, first):
            return super().step(x, state, tails, seats, live,
                                first if live.shape[1] == 1 else True)

    class BucketEnd(KimiDeltaAttention):
        def step(self, x, state, tails, seats, live, first):
            return super().step(
                x, state, tails, seats,
                live if live.shape[1] == 1 else jnp.ones_like(live), first)

    class NoBeta(KimiDeltaAttention):
        def _gates(self, x, live):
            g, beta = super()._gates(x, live)
            return g, jnp.where(live[..., None], 1.0, 0.0)

    class DecayAfter(KimiDeltaAttention):
        """The recurrence one position after another, its two steps in the
        wrong order: ``S = Diag(exp g) (S + beta k (v - S^T k)^T)``."""

        def _recurrence(self, q, k, v, g, beta, state, seats, first):
            def one(s, x):
                q, k, v, g, beta = x
                u = beta[..., None] * (v - jnp.sum(s * k[..., None], -2))
                s = (s + k[..., None] * u[..., None, :]) \
                    * jnp.exp(g)[..., None]
                return s, jnp.sum(s * q[..., None], -2)

            s, o = jax.lax.scan(
                one, jnp.where(first[:, None, None, None], 0.0,
                               state[seats]),
                tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
            return jnp.moveaxis(o, 0, 1), state.at[seats].set(s)

    class Bf16State(KimiDeltaAttention):
        """The matrices at the seats are bfloat16 (``layer_state``
        below): a decode row's pass over them is
        ``kda_decode_reference``'s, float32 inside and rounded at the
        write (``kda_decode`` and its kernel refuse such a state); a
        prompt's rows go the right program's way, which casts at the read
        and at the write."""

        def _recurrence(self, q, k, v, g, beta, state, seats, first):
            if q.shape[1] > 1:
                return super()._recurrence(q, k, v, g, beta, state, seats,
                                           first)
            o, state = kda_decode_reference(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                seats, first)
            return o[:, None], state

    wrong = {"no_carry": NoCarry, "bucket_end": BucketEnd,
             "no_beta": NoBeta, "decay_after": DecayAfter,
             "bf16_state": Bf16State}[control]

    @dataclasses.dataclass(frozen=True)
    class WrongConfig(LingHybridConfig):
        def attention(self, kind="full_attention", **kw):
            if kind == KDA:
                return wrong(self, **kw)
            return super().attention(kind, **kw)

        def layer_state(self, kind):
            specs = super().layer_state(kind)
            if control != "bf16_state" or specs is None:
                return specs
            return (State(specs[0].shape, jnp.bfloat16), *specs[1:])

    return WrongConfig(**{f.name: getattr(pcfg, f.name)
                          for f in dataclasses.fields(pcfg)})


class Seated(Served):
    """``Served`` that also keeps, of every request, the matrices each
    KDA layer held at its seat when the request ended (a freed seat's
    rows stay as they are until another sequence starts there from
    zeros)."""

    def __init__(self, pcfg, params, options):
        super().__init__(pcfg, params, options)
        self.states = {}
        cache = self.eng.cache
        free = cache.free

        def freed(seq_id):
            try:
                seat = cache.seat(seq_id)
            except KeyError:  # freed before: ``free`` is idempotent
                return free(seq_id)
            self.states[seq_id] = np.stack([
                np.asarray(a[seat]) for a in cache.state if a.ndim == 4
            ]).astype(np.float32)
            return free(seq_id)

        cache.free = freed

    def run(self, params, requests):
        """``Served.run``'s result, and each request's states ``[KDA
        layers, H, d, d]``."""
        first = self.runs + 1
        out, seconds = super().run(params, requests)
        return out, seconds, [self.states.pop(f"r{first}-{i}")
                              for i in range(len(requests))]


_STATES = {}  # the jitted reference's states, by the tokens' length


def reference_states(family, cfg, params, tokens):
    """The float32 reference's KDA states after ``tokens`` ``[KDA layers,
    H, d, d]``."""
    import jax
    import jax.numpy as jnp

    if len(tokens) not in _STATES:
        _STATES[len(tokens)] = jax.jit(
            lambda p, t: family.kda_states(cfg, p, t)[:, 0])
    return np.asarray(_STATES[len(tokens)](
        params, jnp.asarray([tokens], jnp.int32)))


def states_moved(got, want) -> list:
    """Of each layer, the norm of the difference over the norm of the
    reference's."""
    axes = tuple(range(1, want.ndim))
    return [float(x) for x in np.sqrt(
        ((got - want) ** 2).sum(axes) / (want ** 2).sum(axes))]


def caught_by(control: dict, tolerance: float, rows_tolerance: float):
    if control["max"] > tolerance:
        return "max"
    if control["decode_min"] > rows_tolerance:
        return "decode_min"
    if control.get("replay_min", 0.0) > REPLAY_TOLERANCE:
        return "replay_min"
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
                         "(default: 1.2 and 1.9 chunks)")
    ap.add_argument("--seats", type=int, default=16,
                    help="seats of the engine, at most the mix's (16: the "
                         "reference and a control's second engine fit "
                         "beside them)")
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

    cfg = load(args.config, "configs", "ling-3.0-flash-vl")
    mix = load(args.mix, "traffic", "kda-decode")
    family = run.load_family([run.HERE], cfg)
    pcfg = family.program_config(cfg, mix.get("model_overrides", ()))
    options = dict(mix["engine_options"])
    options.pop("serve_options", None)  # the deployment's, not the engine's
    chunk = options["prefill_chunk"]
    lengths = list(mix["check"]["prompt_tokens"]) + (
        args.chunked if args.chunked is not None
        else [int(chunk * f) for f in (1.2, 1.9)])
    new_tokens = int(mix["check"].get("decode_positions", 8)) + 1
    # Pages and seats for these requests alone: the reference and a
    # control's second engine need the room.
    options["num_pages"] = 1 + sum(
        -(-(n + new_tokens) // options["page_size"]) for n in lengths)
    options["max_num_seqs"] = min(options["max_num_seqs"], args.seats)
    options["decode_buckets"] = [options["max_num_seqs"]]
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
            right = Seated(pcfg, params, options)
        prompts = [traffic.prompt_tokens(seed, i, length, vocab, stream=9)
                   for i, length in enumerate(lengths)]
        sampling = [traffic.request_sampling(mix, seed, -100 - i)
                    for i in range(len(lengths))]

        def reading(served, tree, which):
            """The requests ``which`` through ``served`` as streams of
            their own, against the reference over what each received."""
            out, seconds, states = served.run(tree, [
                (prompts[i], sampling[i], new_tokens,
                 wanted_rows(len(prompts[i]), new_tokens, chunk))
                for i in which])
            refs, per_request, by_layer = {}, [], []
            for i, (tokens, rows), held in zip(which, out, states):
                full = prompts[i] + tokens[:-1]
                want = reference_rows(family, cfg, tree, full, sorted(rows))
                want_held = reference_states(family, cfg, tree, full)
                refs[i] = (full, sorted(rows), want, want_held)
                per_request.append(moved(rows, want, len(prompts[i])))
                by_layer.append(states_moved(held, want_held))
            # The same tokens once more, all of them a prompt's: the
            # state the prompt's program leaves, against the state the
            # decoded rows left.
            _, _, again = served.run(tree, [
                (refs[i][0], {}, 1, [len(refs[i][0]) - 1]) for i in which])
            replayed = [states_moved(held, whole)
                        for held, whole in zip(states, again)]
            return dict(
                summary(per_request),
                state_min=min(min(r) for r in by_layer),
                state_max=max(max(r) for r in by_layer),
                state_by_layer=[round(max(r[j] for r in by_layer), 5)
                                for j in range(len(by_layer[0]))],
                replay_min=min(min(r) for r in replayed),
                replay_by_layer=[round(max(r[j] for r in replayed), 6)
                                 for j in range(len(replayed[0]))],
                seconds=seconds), refs

        everything = list(range(len(prompts)))
        got, refs = reading(right, params, everything)
        result = {"seed": seed, "prompt_tokens": lengths, "right": got}
        ok &= caught_by(got, tolerance, rows_tolerance) is None
        log(json.dumps(result))
        if n >= len(args.seeds) - args.controls:
            # A whole prompt and the prompts of two chunks.
            which = [i for i in (1, 2, len(lengths) - 1) if i < len(lengths)]
            which = sorted(set(which))
            for control in args.only or CONTROLS:
                t0 = time.perf_counter()
                if control == "float8":
                    # The tree is consumed, so the reference is the right
                    # program's, over the tokens its streams received, and
                    # the rounded weights are teacher-forced on them: every
                    # row a prompt's (a whole prompt's or a chunk's).
                    params = rounded_to_float8(params)
                    out, seconds, states = right.run(params, [
                        (refs[i][0], {}, 1, refs[i][1]) for i in which])
                    by_layer = [states_moved(held, refs[i][3])
                                for i, held in zip(which, states)]
                    got = dict(summary([
                        moved(rows, refs[i][2], len(prompts[i]))
                        for i, (_, rows) in zip(which, out)]),
                        state_min=min(min(r) for r in by_layer),
                        state_max=max(max(r) for r in by_layer),
                        seconds=seconds)
                else:
                    wrong = Seated(wrong_config(pcfg, control), params,
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
    ran = {c for r in results for c in CONTROLS if c in r}
    print(json.dumps({
        "ok": bool(ok), "device": devices[0].device_kind,
        "tolerance": tolerance, "rows_tolerance": rows_tolerance,
        "replay_tolerance": REPLAY_TOLERANCE,
        "caught_by_the_cell": sorted(
            c for c in ran if all(r[c]["caught_by"] in ("max", "decode_min")
                                  for r in results if c in r)),
        "results": results}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
