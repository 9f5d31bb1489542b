"""A serving cell's check at many seeds in one call: what a limit on
``check_rel_err`` or on ``check_decode_rows_min`` is set from, and the
controls that have to come out not correct through the same comparison.

    python3 perfbench/tests/chip_rows.py mellum2-long-decode --seeds 1 2 3
    python3 perfbench/tests/chip_rows.py mellum2-long-decode --seeds 1 2 3 \
        --override norm_topk_prob=false

``--override FIELD=JSON`` is a control: the program is built with that
field of its configuration changed (the mix's ``model_overrides``, which
the reference never sees), at the cell's own size, and ``ok`` has to read
false.

For each seed the cell is deployed as a run deploys it (``serve_cell``'s
own ``deploy``, the mix's engine options and pools, the seed's weights),
the first entry of the mix's ``warmup`` is served (the check's programs)
and ``serve_cell.check_logits`` is read: one line a seed with
``rel_err``, ``rows_min``, ``rows_median``, the least a program
(``prefill_rows_min``, ``decode_rows_min``), every row's own reading
(``per_row``, so that another statistic needs no second call) and ``ok``,
then one line with their ranges. No window, no traffic: a seed costs its process, its
weights, two programs from the compile cache and the reference, about
half of a run. Every seed runs in a process of its own, one after
another, and this one never touches JAX (a chip belongs to one process):
a second deployment in one process never became healthy on the chip
(``serve.run`` gave up after its 1,200 s, after the first seed's
reading; my chip run, PR 41; on the CPU the same loop runs through). It needs a TPU; ``--cpu`` and ``--dirs``
(directories searched before the benchmark's own, for a tiny
configuration and mix) are for the rehearsal in ``test_check_rows.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEED_TIMEOUT_S = 900.0  # a seed that outlasts this has hung: fail, not wait


def read_seed(args, seed: int) -> dict:
    """One seed's reading, in this process."""
    from perfbench import run, serve_cell

    dirs = list(args.dirs) + [run.HERE]
    if args.config:
        config, traffic = args.config.split(":")
    else:
        with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
            cell = next(w for w in json.load(f)["workloads"]
                        if w["name"] == args.cell)
        config, traffic = cell["config"], cell["traffic"]
    cfg = run.load_json(dirs, "configs", config)
    mix = run.load_json(dirs, "traffic", traffic)
    if args.override:
        mix = dict(mix, model_overrides={
            **dict(mix.get("model_overrides", ())),
            **{k: json.loads(v) for k, v in
               (o.split("=", 1) for o in args.override)}})
    family = run.load_family(dirs, cfg)

    import jax
    import raytpu
    from raytpu.util import compile_cache

    if jax.devices()[0].platform != "tpu" and not args.cpu:
        sys.exit("chip_rows.py needs a TPU and found none")
    compile_cache.enable()
    raytpu.init()
    handle, engine = serve_cell.deploy(
        family, cfg, mix, seed, float(mix.get("deploy_timeout_s", 1200)))
    try:
        serve_cell.warm(handle, dict(mix, warmup=mix["warmup"][:1]), seed,
                        int(cfg["vocab_size"]), family.vocab_rows_held(cfg))
        check = serve_cell.check_logits(handle, engine, family, cfg, mix,
                                        seed)
    finally:
        serve_cell.shutdown()
    return {"seed": seed, **check, "device": jax.devices()[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dirs", nargs="*", default=[])
    ap.add_argument("--config", default=None,
                    help="the cell's configuration and mix by name, for a "
                    "cell BENCHMARK.json does not hold: CONFIG:MIX")
    ap.add_argument("--override", nargs="*", default=[],
                    metavar="FIELD=JSON", help="a control: fields of the "
                    "program's configuration, over the mix's model_overrides")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    argv = list(sys.argv[1:] if argv is None else argv)

    if len(args.seeds) == 1:
        print(json.dumps(read_seed(args, args.seeds[0])), flush=True)
        return 0
    first = argv.index("--seeds")
    rest = argv[:first] + argv[first + 1 + len(args.seeds):]
    read, lost = [], []
    for seed in args.seeds:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *rest, "--seeds",
             str(seed)], stdout=subprocess.PIPE, text=True,
            timeout=SEED_TIMEOUT_S)
        lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        if proc.returncode or not lines:  # a lost seed costs no other
            print(f"seed {seed}: exit {proc.returncode}", file=sys.stderr,
                  flush=True)
            lost.append(seed)
            continue
        print(lines[-1], flush=True)
        read.append(json.loads(lines[-1]))
    if not read:
        sys.exit(f"no seed of {args.seeds} gave a reading")
    print(json.dumps({
        "cell": args.cell, "seeds": len(read), "lost": lost,
        "override": args.override,
        "all_ok": all(c["ok"] for c in read),
        "none_ok": not any(c["ok"] for c in read),
        **{k: [min(c[k] for c in read), max(c[k] for c in read)]
           for k in ("rel_err", "rows_min", "rows_median",
                     "prefill_rows_min", "decode_rows_min")},
        "device": read[0]["device"]}), flush=True)
    return 1 if lost else 0


if __name__ == "__main__":
    sys.exit(main())
