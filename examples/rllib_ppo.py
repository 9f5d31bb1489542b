"""Train PPO on CartPole with remote env runners (reference analogue:
RLlib's PPO quickstart).

  python examples/rllib_ppo.py
On a machine without an accelerator (or to leave one alone):
  JAX_PLATFORMS=cpu python examples/rllib_ppo.py
"""

import os
import sys

# Run in-repo without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import raytpu
from raytpu.rllib import PPOConfig


def main():
    raytpu.init()
    # num_env_runners=0 samples in-process (fastest on one core); bump it
    # to fan sampling out over remote actor processes on a real machine.
    config = (PPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=0, num_envs_per_env_runner=4,
                           rollout_fragment_length=64)
              .training(lr=3e-4, num_epochs=6, minibatch_size=128,
                        entropy_coeff=0.01)
              .debugging(seed=0))
    algo = config.build()
    for i in range(10):
        result = algo.train()
        print(f"iter {i + 1:2d}  return_mean="
              f"{result['episode_return_mean']:7.1f}  "
              f"env_steps/s={result['env_steps_per_s']:8.0f}")
    print("greedy eval:", algo.evaluate())
    algo.stop()
    raytpu.shutdown()


if __name__ == "__main__":
    main()
