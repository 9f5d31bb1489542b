"""Stream LLM tokens from the paged-KV inference engine behind serve
(reference analogue: vLLM's continuous batching behind Ray Serve).

Deploys ``LLMDeployment`` (a tiny Llama), fires two staggered
requests with different prompt/output lengths, and prints tokens as
they stream back — both sequences share decode iterations inside the
single engine while each client sees only its own stream. A second
phase sends three requests that open with the same 16-token system
prompt: the first prefills and registers the shared pages, the rest
graft them from the prefix cache and prefill only their 3-token tails
(watch ``prefill_tokens`` vs ``prefix_cache.hit_tokens``).

  python examples/serve_llm_streaming.py
On a machine without an accelerator (or to leave one alone):
  JAX_PLATFORMS=cpu python examples/serve_llm_streaming.py
"""

import os
import sys

# Run in-repo without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import threading
import time

import raytpu
from raytpu import serve


def consume(tag, handle, prompt, n_new):
    t0 = time.perf_counter()
    for tok in handle.generate.remote_streaming(prompt, max_new_tokens=n_new):
        print(f"[{tag} +{time.perf_counter() - t0:6.2f}s] token={tok}")


def main():
    raytpu.init()
    app = serve.LLMDeployment.bind(
        model="llama",
        engine_options={"page_size": 8, "max_num_seqs": 4,
                        "max_model_len": 64},
        seed=0,
    )
    handle = serve.run(app, name="llm", route_prefix=None)
    try:
        ta = threading.Thread(
            target=consume, args=("a", handle, list(range(1, 12)), 8))
        ta.start()
        time.sleep(0.5)  # stagger: b joins a's in-flight decode
        tb = threading.Thread(
            target=consume, args=("b", handle, [7, 3, 9], 5))
        tb.start()
        ta.join()
        tb.join()
        stats = handle.stats.remote().result()
        print(f"decode batch sizes seen: {stats['decode_batch_hist']}")
        print(f"decode compiles per bucket: {stats['decode_compiles']}")

        # -- shared system prompt: prefix-cache hits ------------------
        # Token ids disjoint from phase 1's prompts, so the pages it
        # registered can't partially match here.
        system = list(range(101, 117))  # 2 full pages at page_size 8
        prefill_before = stats["prefill_tokens"]
        for i, tail in enumerate(([31, 32, 33], [41, 42, 43],
                                  [51, 52, 53])):
            # Sequential on purpose: request 0 must finish (and register
            # the system-prompt pages) before 1 and 2 can hit them.
            consume(f"sys{i}", handle, system + tail, 4)
        stats = handle.stats.remote().result()
        print(f"prefill tokens for 3 shared-prefix requests: "
              f"{stats['prefill_tokens'] - prefill_before} "
              f"(19 + 3 + 3 — tails only after the first)")
        print(f"prefix cache: {stats['prefix_cache']}")
    finally:
        serve.shutdown()
        raytpu.shutdown()


if __name__ == "__main__":
    main()
