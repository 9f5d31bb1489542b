"""GPT-2 124M training throughput on one TPU chip, tokens/s/chip.

One fixed configuration, the one with a chip history (the r02 row quoted
in ROADMAP.md): seq 1024, batch 8, bf16, full rematerialization, AdamW,
the default attention (the Pallas flash kernel on a TPU). It runs in the
one process that owns the chip, stops the clock on a host fetch of the
last loss, and prints ONE JSON line naming the device. Off a TPU, or on
any error, it exits non-zero and prints no result.

    python bench.py
"""

from __future__ import annotations

import json
import sys
import time

BATCH = 8
WARMUP_STEPS = 2
TIMED_STEPS = 20


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from raytpu.core.chip_specs import chip_spec
    from raytpu.models.gpt2 import (GPT2, GPT2Config, init_params,
                                    make_train_step)
    from raytpu.util import compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures a TPU and found none: "
                 f"jax.devices()[0].platform == {dev.platform!r}")
    peak = chip_spec(dev.device_kind).bf16_flops  # unknown kind: raises
    compile_cache.enable()

    cfg = GPT2Config.small()  # full remat, attn_impl=None
    model = GPT2(cfg)
    params = init_params(model, cfg, batch=BATCH)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (BATCH, cfg.block_size), 0,
        cfg.vocab_size, jnp.int32)

    # The clock stops on a host fetch of the loss, which depends on every
    # step before it through the donated params chain.
    t0 = time.perf_counter()
    for _ in range(WARMUP_STEPS):
        params, opt_state, loss = step(params, opt_state, tokens)
    np.asarray(loss)
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        params, opt_state, loss = step(params, opt_state, tokens)
    loss_host = float(np.asarray(loss))
    wall_s = time.perf_counter() - t0
    if not np.isfinite(loss_host):
        sys.exit(f"bench.py: loss is {loss_host}")

    tokens_per_s = BATCH * cfg.block_size * TIMED_STEPS / wall_s
    # Model FLOPs per token, forward and backward (6N plus attention at
    # the full T x T, as the r02 row counted it); recomputation under
    # remat is not counted.
    flops_per_token = (6 * cfg.n_params_approx
                       + 12 * cfg.n_layer * cfg.n_embd * cfg.block_size)
    print(json.dumps({
        "metric": "gpt2_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s/chip",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "detail": {
            "model": "gpt2-124M", "batch": BATCH, "seq": cfg.block_size,
            "remat": cfg.remat, "attn": "pallas flash (attn_impl=None)",
            "steps": TIMED_STEPS, "wall_s": round(wall_s, 3),
            "setup_s": round(setup_s, 1), "loss": loss_host,
            "model_flops_utilization": round(
                tokens_per_s * flops_per_token / peak, 4),
            "peak_bf16_flops": peak,
        },
    }))


if __name__ == "__main__":
    main()
