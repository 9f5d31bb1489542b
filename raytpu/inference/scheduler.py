"""Continuous-batching scheduler (reference analogue: Orca, OSDI '22).

Scheduling happens at *iteration* granularity: every engine step calls
:meth:`Scheduler.schedule`, which (1) guarantees each running sequence
a KV slot for the token it is about to decode — preempting the
YOUNGEST sequence (latest arrival) to recompute later when pages run
out, so the oldest requests always make progress and the total
recomputation bill is minimized — and (2) admits waiting requests
FIFO while both a sequence slot and enough KV pages for their prompt
are available, and where the cache keeps seats (a model whose layers
keep a state, :mod:`raytpu.inference.kv_cache`) a seat: ``cache.allocate``
gives pages and seat together or neither, so admission stops at the last
seat whatever pages are left. Fresh prefills therefore merge with in-flight decodes
in the same iteration instead of waiting for the batch to drain
(the continuous-batching throughput lever).

The engine keeps one decode step in flight: ``Sequence.cached_len``
counts the positions whose write is dispatched and ``Sequence.in_flight``
the tokens sampled on the device and not yet fetched, so a slot is
secured from ``cached_len`` as ever. A sequence whose tokens in flight
hold its last by length stays among the running until they are fetched
and is given no slot and no row meanwhile. The scheduler knows nothing
else of the step in flight: the engine asks :meth:`Scheduler.pages_short`
and fetches what is in flight before an iteration that would preempt.

Preemption is preempt-to-RECOMPUTE (vLLM's default for small
sequences): the victim's pages are freed (and its seat: the state it
held is recomputed with its keys and values), its ``cached_len`` drops to
0, and it re-enters the FRONT of the waiting queue; when re-admitted,
its prompt *plus everything it already generated* is re-prefetched in
one bucketed prefill. Already-sampled tokens are never re-sampled, so
preemption is invisible in the output stream.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, List, Optional

from raytpu.inference.kv_cache import PagedKVCache
from raytpu.inference.sampling import SamplingParams
from raytpu.util import serve_slo, task_events

WAITING = "waiting"
RUNNING = "running"
FINISHED = "finished"


@dataclasses.dataclass
class Sequence:
    """One request's decode state."""

    request_id: str
    prompt: List[int]
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    arrival: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    # Positions whose K/V a dispatched program has written, or will have
    # by the time anything dispatched later runs: it advances when the
    # write is dispatched, not when the token sampled behind it is
    # fetched. After a prefill this is len(tokens) - 1 (the newest
    # sampled token's KV is written by its decode step), and with a
    # decode in flight len(tokens) - 1 + in_flight; 0 means
    # preempted/never prefilled.
    cached_len: int = 0
    # Tokens of its own a dispatched decode has sampled on the device
    # and the host has not fetched yet (the engine's step in flight).
    in_flight: int = 0
    state: str = WAITING
    finish_reason: Optional[str] = None
    # Serving-plane attribution (stamped by the replica from its request
    # context): request-timeline events and the goodput ledger book
    # under these tags. Empty outside the serve path.
    deployment: str = ""
    tenant: str = ""

    def __post_init__(self):
        self.prompt = [int(t) for t in self.prompt]

    @property
    def tokens(self) -> List[int]:
        return self.prompt + self.generated

    @property
    def num_tokens(self) -> int:
        return len(self.prompt) + len(self.generated)

    @property
    def prefill_len(self) -> int:
        """Tokens a (re-)prefill must process: everything known except
        the newest generated token, whose KV the next decode writes.
        A fresh prompt prefills fully (its last logit samples token 0)."""
        return self.num_tokens - (1 if self.generated else 0)


@dataclasses.dataclass
class ScheduleOutput:
    """One iteration's work: prefills run first, then every decode is
    batched into a single padded step. ``preempted`` is informational
    (those sequences are already back in the waiting queue)."""

    prefills: List[Sequence]
    decodes: List[Sequence]
    preempted: List[Sequence]


class Scheduler:
    def __init__(self, cache: PagedKVCache, max_num_seqs: int = 8,
                 max_model_len: int = 2048, prefix_cache=None,
                 step_positions: int = 1):
        self.cache = cache
        self.max_num_seqs = max_num_seqs
        self.max_model_len = max_model_len
        # Positions a decode step writes for a sequence: 1, or 2 where a
        # step verifies a draft beside the token it stands at. Each has
        # its slot secured before the step, kept or not. (A running
        # sequence has fewer than ``max_model_len`` tokens, so the
        # draft's position lies inside it.)
        self.step_positions = step_positions
        # Optional raytpu.inference.prefix_cache.PrefixCache: admission
        # then grafts cached prompt pages instead of allocating them.
        self.prefix_cache = prefix_cache
        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        self.num_preemptions = 0
        self._arrivals = 0

    # ---- request lifecycle -----------------------------------------

    def add(self, seq: Sequence) -> None:
        seq.arrival = self._arrivals
        self._arrivals += 1
        seq.state = WAITING
        self.waiting.append(seq)

    def abort(self, request_id: str) -> bool:
        """Drop a request wherever it is; frees its pages. Idempotent."""
        for seq in list(self.waiting):
            if seq.request_id == request_id:
                self.waiting.remove(seq)
                seq.state = FINISHED
                seq.finish_reason = "aborted"
                if task_events.request_events_enabled():
                    task_events.emit_request(
                        seq.request_id,
                        task_events.RequestTransition.ABORTED,
                        deployment=seq.deployment, tenant=seq.tenant)
                return True
        for seq in self.running:
            if seq.request_id == request_id:
                self.finish(seq, "aborted")
                return True
        return False

    def finish(self, seq: Sequence, reason: str) -> None:
        seq.state = FINISHED
        seq.finish_reason = reason
        self.cache.free(seq.request_id)
        if seq in self.running:
            self.running.remove(seq)
        if task_events.request_events_enabled():
            if reason == "aborted":
                task_events.emit_request(
                    seq.request_id,
                    task_events.RequestTransition.ABORTED,
                    deployment=seq.deployment, tenant=seq.tenant)
            else:
                task_events.emit_request(
                    seq.request_id,
                    task_events.RequestTransition.FINISHED,
                    deployment=seq.deployment, tenant=seq.tenant,
                    data={"tokens_out": len(seq.generated),
                          "reason": reason})

    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running)

    def _last_in_flight(self, seq: Sequence) -> bool:
        """Whether the tokens ``seq`` has in flight hold its last by
        length: it stays among the running until they are fetched, and
        takes no slot and no row of a decode meanwhile."""
        return seq.in_flight > 0 and (
            len(seq.generated) + seq.in_flight >= seq.sampling.max_new_tokens
            or seq.num_tokens + seq.in_flight >= self.max_model_len)

    def _decoding(self, seq: Sequence) -> bool:
        """Whether ``seq`` takes a slot and a row of the next decode: it
        runs, its prompt is cached, and its last token is not out."""
        return seq.state == RUNNING and seq.cached_len >= seq.prefill_len \
            and not self._last_in_flight(seq)

    def pages_short(self) -> bool:
        """Whether the next :meth:`schedule` would preempt: the decoding
        sequences' next slots take more pages than are free. The engine
        asks before it schedules with a decode in flight and fetches
        first if so: the fetch may end sequences and give pages back, and
        leaves ``tokens`` holding every token whose KV was written, as a
        preempted sequence's re-prefill needs them."""
        cache, ahead = self.cache, self.step_positions
        free = cache.free_pages()
        if free >= len(self.running) * cache.pages_for(
                ahead + cache.page_size - 1):
            return False  # a page each and more: the usual step
        need = sum(
            max(0, cache.pages_for(seq.cached_len + ahead)
                - cache.num_seq_pages(seq.request_id))
            for seq in self.running if self._decoding(seq))
        return need > free

    # ---- the per-iteration decision --------------------------------

    def schedule(self) -> ScheduleOutput:
        preempted: List[Sequence] = []

        # 1) Secure a KV slot for every DECODING sequence's next token
        #    (and for its draft's, ``step_positions``), oldest first. Under page pressure evict the youngest
        #    running sequence; if a sequence must evict itself, it just
        #    waits (it's already the lowest-priority survivor).
        #    Sequences still mid-prefill (chunked) skip this: their
        #    admission already reserved pages for the whole prompt.
        for seq in sorted(self.running, key=lambda s: s.arrival):
            if not self._decoding(seq):
                # Preempted by an earlier turn of this loop; or
                # mid-prefill: allocation covers prefill_len.
                continue
            while not self.cache.extend(
                    seq.request_id, seq.cached_len + self.step_positions):
                victim = max(self.running, key=lambda s: s.arrival)
                self._preempt(victim)
                preempted.append(victim)
                if victim is seq:
                    break

        decodes = [s for s in self.running if self._decoding(s)]
        # Running sequences whose prompt isn't fully cached yet keep
        # prefilling (one chunk per engine step) alongside the decodes.
        prefills: List[Sequence] = [
            s for s in self.running if s.state == RUNNING
            and s.cached_len < s.prefill_len]

        # 2) Admit waiting requests FIFO — but never in an iteration
        #    that preempted (we'd thrash: admitting took the very pages
        #    the preemption just freed for older sequences).
        if not preempted:
            while self.waiting and len(self.running) < self.max_num_seqs:
                seq = self.waiting[0]
                if not self._admit(seq):
                    break  # FIFO head-of-line: don't skip ahead
                self.waiting.popleft()
                seq.state = RUNNING
                self.running.append(seq)
                prefills.append(seq)
                if task_events.request_events_enabled():
                    # A sequence re-entering with generated tokens is a
                    # preemption victim coming back, not a fresh admit.
                    task_events.emit_request(
                        seq.request_id,
                        (task_events.RequestTransition.RESUMED
                         if seq.generated else
                         task_events.RequestTransition.ADMITTED),
                        deployment=seq.deployment, tenant=seq.tenant)

        return ScheduleOutput(prefills=prefills, decodes=decodes,
                              preempted=preempted)

    def _admit(self, seq: Sequence) -> bool:
        """Allocate KV for a waiting sequence. With a prefix cache,
        fully-matched prompt pages are grafted (pointer copy + ref
        bump) and ``cached_len`` jumps past them so the engine only
        prefills the tail. The match is capped one token short of
        ``prefill_len`` — at least one token must run through the model
        so there are logits to sample the next token from."""
        if self.prefix_cache is None:
            return self.cache.allocate(seq.request_id, seq.prefill_len)
        ps = self.cache.page_size
        cap = (seq.prefill_len - 1) // ps
        matched = (self.prefix_cache.match(seq.tokens, max_pages=cap)
                   if cap > 0 else [])
        if not self.cache.allocate_shared(seq.request_id,
                                          seq.prefill_len, matched):
            return False
        seq.cached_len = len(matched) * ps
        return True

    def _preempt(self, seq: Sequence) -> None:
        self.cache.free(seq.request_id)
        seq.cached_len = 0
        seq.state = WAITING
        self.running.remove(seq)
        self.waiting.appendleft(seq)
        self.num_preemptions += 1
        # Generated tokens whose KV we just discarded will be re-
        # prefilled on re-admission: pure recompute waste in the
        # goodput ledger (preemption is rare; off the per-token path).
        serve_slo.wasted("preempt_recompute", len(seq.generated),
                         seq.deployment, seq.tenant)
        if task_events.request_events_enabled():
            task_events.emit_request(
                seq.request_id, task_events.RequestTransition.PREEMPTED,
                deployment=seq.deployment, tenant=seq.tenant,
                data={"tokens_discarded": len(seq.generated)})
