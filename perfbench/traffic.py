"""The one general traffic generator: a mix file's parameters plus a seed
give a schedule. Three kinds: ``closed`` (clients that each wait for a
reply), ``open`` (arrivals on a clock, whatever the system does) and
``train`` (a fixed global batch).

Every seed sees the same work. Sizes are not sampled: a block of ``n``
requests takes the ``n`` mid-quantiles of its distribution, and the seed
only permutes them inside the block. Arrival gaps are the mid-quantiles
of the exponential law, permuted the same way, so every seed offers the
same number of requests over the same time. What the seed changes is the
order, the token ids and the weights.

A mix may say how its clients sample (``sampling``: ``temperature`` and
``top_k``): every request then carries them and a seed of its own, made
from the run's seed and the request's index (``request_sampling``).
Without the field every request is greedy.

A mix may fix the order too (``order_seed``): which requests share the
batch decides the work of a step (a decode reads a page table as wide as
the batch's longest context), so two orders of the same requests are not
the same work. The permutations are then drawn from the mix's number and
every run has one schedule; its seed draws the token ids and the weights.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Mapping

import numpy as np

KINDS = ("closed", "open", "train")


def quantile_sizes(dist: Mapping, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a size distribution, as whole numbers in
    ascending order. ``dist`` is ``{"dist": "const", "value": v}``,
    ``{"dist": "loguniform", "lo": a, "hi": b}`` or ``{"dist":
    "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}`` (clipped)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "const":
        x = np.full(n, float(dist["value"]))
    elif kind == "loguniform":
        x = np.exp(np.log(dist["lo"]) + u * (np.log(dist["hi"])
                                             - np.log(dist["lo"])))
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        x = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
        x = np.clip(x, dist["lo"], dist["hi"])
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    return np.rint(x).astype(np.int64)


def _blocks(values: np.ndarray, count: int, rng: np.random.Generator
            ) -> np.ndarray:
    """``count`` values: the block ``values`` repeated, each repetition
    permuted by ``rng``."""
    reps = -(-count // len(values))
    return np.concatenate([rng.permutation(values)
                           for _ in range(reps)])[:count]


def _order_rng(mix: Mapping, seed: int, stream: int) -> np.random.Generator:
    """What permutes the blocks: the run's seed, or the mix's own
    ``order_seed`` where it fixes one order for every run."""
    return np.random.default_rng([int(mix.get("order_seed", seed)), stream])


def prompt_tokens(seed: int, index: int, length: int, vocab: int,
                  stream: int = 7) -> List[int]:
    """Token ids of request ``index`` of a ``stream`` (7 the window's
    traffic, 8 warm-up, 9 the check), drawn from the seed. Id 0 is left
    out so a stream of zeros is never sent."""
    ids = np.random.default_rng(
        [int(seed), int(stream), int(index)]).integers(1, vocab, size=length)
    return [int(t) for t in ids]


def request_sampling(mix: Mapping, seed: int, index: int) -> Dict:
    """What request ``index`` (negative: a warm-up's or the check's) asks
    of the sampler beyond its length: nothing where the mix has no
    ``sampling``; else its ``temperature`` and ``top_k`` and a ``seed``
    drawn from the run's seed and the index, so that a request's draws
    are its own whatever shares its batch."""
    spec = mix.get("sampling")
    if not spec:
        return {}
    unknown = set(spec) - {"temperature", "top_k"}
    if unknown:
        raise ValueError(f"sampling has no field {sorted(unknown)}")
    drawn = np.random.default_rng(
        [int(seed), 11, int(index) % 2**32]).integers(0, 2**31)
    return {"temperature": float(spec.get("temperature", 0.0)),
            "top_k": int(spec.get("top_k", 0)), "seed": int(drawn)}


def closed_schedule(mix: Mapping, seed: int) -> Dict:
    """Per client, the requests it sends one after another.

    ``first_wave_new_tokens`` (optional) overrides the output length of
    each client's first request: client ``i`` asks for entry ``i``. With
    equal lengths afterwards the slots then turn over one at a time at a
    fixed spacing, whatever the seed.
    """
    clients = int(mix["clients"])
    per_client = int(mix["requests_per_client"])
    total = clients * per_client
    rng = _order_rng(mix, seed, 1)
    # One block is one round: every client sends one request of it.
    prompts = _blocks(quantile_sizes(mix["prompt_tokens"], clients),
                      total, rng)
    outputs = _blocks(quantile_sizes(mix["new_tokens"], clients),
                      total, rng)
    first = mix.get("first_wave_new_tokens")
    if first is not None and len(first) != clients:
        raise ValueError("first_wave_new_tokens needs one entry per client")
    plan = []
    for c in range(clients):
        reqs = []
        for r in range(per_client):
            i = r * clients + c
            reqs.append({
                "index": i, "prompt_len": int(prompts[i]),
                "new_tokens": int(first[c]) if first is not None and r == 0
                else int(outputs[i])})
        plan.append(reqs)
    return {"kind": "closed", "clients": plan}


def open_schedule(mix: Mapping, seed: int, duration_s: float) -> Dict:
    """Arrivals at ``rate_per_s`` over at least ``duration_s`` seconds:
    for each its due time and sizes. Bursts (a rate schedule), sessions
    and shared prefixes are fields this kind does not have yet
    (``PERF.md``, open questions)."""
    rate = float(mix["rate_per_s"])
    block = int(mix.get("block", 32))
    # Whole blocks, so that every seed offers the same requests; the
    # caller sends those that are due before its window ends.
    count = int(math.ceil(rate * duration_s / block)) * block
    rng = _order_rng(mix, seed, 2)
    u = (np.arange(block) + 0.5) / block
    gaps = _blocks(-np.log1p(-u), count, rng)  # Exp(1) mid-quantiles
    due = np.cumsum(gaps) / rate
    prompts = _blocks(quantile_sizes(mix["prompt_tokens"], block),
                      count, rng)
    outputs = _blocks(quantile_sizes(mix["new_tokens"], block), count, rng)
    return {"kind": "open", "arrivals": [
        {"index": i, "due_s": float(due[i]), "prompt_len": int(prompts[i]),
         "new_tokens": int(outputs[i])} for i in range(count)]}


def train_schedule(mix: Mapping) -> Dict:
    """A training mix has nothing to draw but its tokens: the global batch
    and the sequence length are the mix's own numbers."""
    return {"kind": "train", "global_batch": int(mix["global_batch"]),
            "seq_len": int(mix["seq_len"])}


def simulate_closed_turnovers(plan: Dict, steps: int) -> List[int]:
    """Decode-step indices at which a slot turns over, for a closed
    schedule served by an engine that decodes every running request one
    token a step and admits a waiting request in the step after its
    slot freed (prefill yields the first token). A model of the schedule,
    used by the tests and to label a run's window positions."""
    queues = [list(c) for c in plan["clients"]]
    left = [q.pop(0)["new_tokens"] - 1 for q in queues]  # prefill gave one
    turnovers = []
    for step in range(1, steps + 1):
        for c in range(len(queues)):
            if left[c] == 0:  # admitted this step: prefill, first token
                left[c] = queues[c].pop(0)["new_tokens"] - 1 \
                    if queues[c] else -1
                continue
            if left[c] < 0:
                continue
            left[c] -= 1
            if left[c] == 0:
                turnovers.append(step)
    return turnovers
