"""Serve a jitted model with SSE token streaming (reference analogue:
Ray Serve streaming responses).

  python examples/serve_token_streaming.py
(on a machine without an accelerator, or to leave one alone:
  JAX_PLATFORMS=cpu python examples/serve_token_streaming.py)
then:
  curl -N -H 'Accept: text/event-stream' localhost:8000/generate?prompt=2
"""

import os
import sys

# Run in-repo without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import time

import jax
import jax.numpy as jnp

import raytpu
from raytpu import serve


@serve.deployment(num_replicas=1)
class TokenStreamer:
    def __init__(self):
        # "Model": a jitted next-value fn standing in for an LM decode step.
        self._step = jax.jit(lambda x: x * 2 + 1)

    def __call__(self, request):
        n = int(request.query.get("prompt", 5))
        x = jnp.asarray(n)
        for _ in range(8):
            x = self._step(x)
            yield f"token={int(x)}"
            time.sleep(0.05)


def main():
    raytpu.init()
    serve.run(TokenStreamer.bind(), route_prefix="/generate")
    print("serving on :8000/generate — ctrl-c to stop")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        pass
    finally:
        serve.shutdown()
        raytpu.shutdown()


if __name__ == "__main__":
    main()
