"""Distributed GPT-2 pretraining with JaxTrainer (reference analogue:
Ray Train's TorchTrainer DDP quickstart).

  python examples/train_gpt2.py
uses whatever JAX finds (on a TPU the attention is the Pallas flash
kernel). On a machine without an accelerator, or to leave one alone:
  JAX_PLATFORMS=cpu python examples/train_gpt2.py
"""

import os
import sys

# Run in-repo without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import dataclasses

import jax
import jax.numpy as jnp
import optax

import raytpu
from raytpu.models.gpt2 import GPT2, GPT2Config, init_params, make_train_step
from raytpu.train import JaxTrainer, ScalingConfig


def train_loop(config):
    from raytpu import train

    cfg = dataclasses.replace(GPT2Config.tiny(), remat="dots")
    model = GPT2(cfg)
    params = init_params(model, cfg, batch=config["batch"])
    opt = optax.adamw(config["lr"])
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
    tokens = jax.random.randint(
        jax.random.PRNGKey(train.get_context().get_world_rank()),
        (config["batch"], cfg.block_size), 0, cfg.vocab_size, jnp.int32)
    for i in range(config["steps"]):
        params, opt_state, loss = step(params, opt_state, tokens)
        train.report({"step": i, "loss": float(loss)})


def main():
    raytpu.init()
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"batch": 2, "steps": 5, "lr": 1e-3},
        scaling_config=ScalingConfig(num_workers=2),
    )
    result = trainer.fit()
    raytpu.shutdown()
    if result.error is not None:  # fit() reports a failed gang here
        raise result.error
    print("final metrics:", result.metrics)


if __name__ == "__main__":
    main()
