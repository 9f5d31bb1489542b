"""GPT-2 throughput sweep: attn impl x remat x batch x seq.

Produces the evidence the headline bench rests on: a recorded pallas-vs-XLA
attention A/B on hardware plus batch/remat scaling, so the chosen bench
config is a measured optimum rather than a guess. Writes one JSON line per
config to stdout and a summary file.

Usage:  python benchmarks/sweep_gpt2.py [--out SWEEP.json]
Env:    RAYTPU_SWEEP_SMOKE=1  (tiny model on CPU, 2 configs, for tests)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_config(jax, jnp, np, optax, *, batch: int, seq: int, remat: bool,
               attn: str, steps: int, min_wall: float) -> dict:
    import dataclasses

    from raytpu.models.gpt2 import GPT2, GPT2Config, init_params, \
        make_train_step

    smoke = os.environ.get("RAYTPU_SWEEP_SMOKE") == "1"
    if smoke:
        cfg = GPT2Config(vocab_size=512, block_size=seq, n_layer=2,
                         n_head=4, n_embd=128, dtype=jnp.float32,
                         remat=remat, attn_impl=attn)
    else:
        cfg = GPT2Config(vocab_size=50304, block_size=seq, n_layer=12,
                         n_head=12, n_embd=768, dtype=jnp.bfloat16,
                         remat=remat, attn_impl=attn)
    model = GPT2(cfg)
    params = init_params(model, cfg, batch=batch)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))

    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0,
                                cfg.vocab_size, jnp.int32)

    t_c = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, tokens)
    np.asarray(loss)
    compile_s = time.perf_counter() - t_c
    params, opt_state, loss = step(params, opt_state, tokens)
    np.asarray(loss)

    while True:
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, tokens)
        loss_host = float(np.asarray(loss))
        dt = time.perf_counter() - t0
        if dt >= min_wall:
            break
        steps *= 2

    toks = batch * seq * steps / dt
    n_params = cfg.n_params_approx
    fpt = 6 * n_params + 12 * cfg.n_layer * cfg.n_embd * seq
    dev = jax.devices()[0]
    mfu = None  # the CPU smoke run has no peak to be measured against
    if dev.platform != "cpu":
        from raytpu.core.chip_specs import chip_spec

        mfu = round(toks * fpt / chip_spec(dev.device_kind).bf16_flops, 4)
    return {
        "batch": batch, "seq": seq, "remat": remat, "attn": attn,
        "tokens_per_sec": round(toks, 1), "mfu": mfu,
        "steps": steps, "wall_s": round(dt, 3),
        "compile_s": round(compile_s, 1), "loss": loss_host,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/SWEEP_gpt2.json")
    ap.add_argument("--configs", default=None,
                    help="comma list batch:seq:remat:attn, e.g. 16:1024:0:tpu")
    args = ap.parse_args()

    smoke = os.environ.get("RAYTPU_SWEEP_SMOKE") == "1"
    if smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    dev = jax.devices()[0]
    on_accel = dev.platform != "cpu"
    print(f"# device: {dev}", file=sys.stderr)

    if args.configs:
        grid = []
        for c in args.configs.split(","):
            b, s, r, a = c.split(":")
            grid.append((int(b), int(s), bool(int(r)), a))
    elif smoke:
        grid = [(2, 128, True, "reference"), (2, 128, False, "reference")]
    else:
        grid = []
        # A/B: attention impl at the round-2 bench config.
        for attn in ("tpu", "reference"):
            grid.append((8, 1024, True, attn))
        # remat off + batch scaling (both attn impls at the best batch).
        for batch in (8, 16, 32):
            for attn in ("tpu", "reference"):
                grid.append((batch, 1024, False, attn))
        # longer sequence, where flash should win harder.
        for attn in ("tpu", "reference"):
            grid.append((8, 2048, False, attn))

    steps = 3 if smoke else 10
    min_wall = 0.3 if smoke else 2.0
    results = []
    for batch, seq, remat, attn in grid:
        if attn == "tpu" and not on_accel:
            continue
        try:
            r = run_config(jax, jnp, np, optax, batch=batch, seq=seq,
                           remat=remat, attn=attn, steps=steps,
                           min_wall=min_wall)
        except Exception as e:  # noqa: BLE001
            r = {"batch": batch, "seq": seq, "remat": remat, "attn": attn,
                 "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(r), flush=True)
        results.append(r)

    best = max((r for r in results if "error" not in r),
               key=lambda r: r["tokens_per_sec"], default=None)
    summary = {"device": str(dev), "results": results, "best": best}
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"# wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
