"""LLMDeployment: the inference engine behind a serve replica.

Wire path (all existing machinery): client calls
``handle.generate.remote_streaming(prompt, ...)`` → router
``assign_request_streaming`` → replica ``handle_request_streaming``
admits the request on its executor, once, and awaits the stream that
:meth:`LLMDeployment.generate` returns on its event loop → each token
id becomes an object of the worker's stream, stored on that loop →
``ObjectRefGenerator`` → ``DeploymentResponseGenerator`` on the
client, which sees tokens *while the sequence still decodes*. A step's
tokens reach the loop through one call, and no pool thread runs for a
token.

The engine is pumped by a REPLICA-OWNED background stepping loop: one
daemon thread per replica steps the engine whenever any request is
unfinished and parks on a condition variable otherwise. Consumers
only drain their own streams — a slow (or stalled) consumer never
stalls other streams, and tokens keep decoding while nobody is
pulling. This replaces the PR-4 caller-driven design where whichever
request thread was waiting ran the step. Cancellation rides the
stream's close: the client's ``close()`` (or GC of an abandoned
stream) reaches :meth:`TokenStream.close`, which aborts the request —
freeing its KV pages.

The loop also maintains a lock-free ``engine_pressure()`` snapshot
(waiting depth, KV-page occupancy, TTFT p95) that the replica exports
through ``get_metrics`` for engine-pressure autoscaling.

Disaggregated serving (r19): a deployment may be built with
``role="prefill"`` (serves ``kv_export_*`` — prefills prompts on
demand, pins the finished pages, streams them out chunk by chunk) or
``role="decode"`` with ``prefill=<handle or sibling deployment>`` (on
each request, pulls the prompt's KV prefix from the prefill peer into
the local prefix cache before admission, so the engine grafts the
pages and starts at ``cached_len`` without re-prefilling). Routers can
also probe :meth:`LLMDeployment.prefix_summary` for prefix-cache-aware
replica selection. See :mod:`raytpu.inference.disagg`.

Families (``model=``): "llama", "gpt2", "mixtral", "olmoe", "mellum"
(window layers among full ones: two kinds of KV pool, no prefix cache,
no role) and "joyai" (latent attention: one latent pool a layer read
through the absorbed kernel, a leading dense layer, sigmoid-routed
experts of which ``experts_held`` may be a share, a shared expert; the
prefix cache works over its pages, a role is refused) and "exaone_moe"
(window layers among rope-less full ones, a sigmoid-routed expert layer
and a prediction module through which the model drafts for itself: a
decode step yields a sequence one token or two; as "mellum", no prefix
cache and no role) and "lfm2_moe" (gated short convolutions in three
layers of four, which keep a state a sequence at its seat and no keys or
values, beside full-attention layers' pools and sigmoid-routed experts;
no prefix cache and no role: a prefix's pages say nothing of the state
at its end) and "longcat_flash" (two latent-attention sublayers a layer,
so two latent pools, and one routed layer beside them on a shortcut whose
router also scores identity experts; as "joyai", the prefix cache works
over its pages and a role is refused) and "glm_moe_dsa" (latent
attention that reads only the cached positions a learned indexer
chooses: two pools a layer of unequal row width under one block table,
the latent rows and the index keys; three leading dense layers and
"joyai"'s routed layer; the prefix cache shares a page of both pools at
once, a preempted sequence recomputes both, a role is refused) and
"ling_hybrid" (five delta-rule linear-attention layers to every latent-
attention layer: a float32 matrix a head and the convolutions' tails at a
sequence's seat beside one latent pool, experts chosen inside the best
groups; as "lfm2_moe", no prefix cache and no role). Each
reaches the engine through its config's ``serving`` and nothing else.
"""

from __future__ import annotations

import asyncio
import logging
import os
import queue
import threading
import uuid
from collections import deque
from typing import Dict, Optional

from raytpu.cluster import constants as tuning
from raytpu.inference import disagg
from raytpu.inference.engine import InferenceEngine
from raytpu.inference.sampling import SamplingParams
from raytpu.serve.config import DeploymentConfig
from raytpu.serve.deployment import deployment
from raytpu.util import serve_slo, task_events, tracing

logger = logging.getLogger(__name__)

# Newest step records the loop publishes for ``step_log``.
_STEP_TAIL = 64


class _END:
    """Closes a request's stream: nothing follows it. A class, because
    it pickles by reference: ``LLMDeployment`` reaches its replica
    pickled by value, globals and all, and an ``object()`` among them
    would arrive as another than the one :class:`TokenStream` knows."""


class TokenStream:
    """One request's token ids, as :meth:`LLMDeployment.generate` returns
    them: iterable (``for token in stream``, ``close()``) and
    asynchronously iterable (``async for``, ``aclose()``).

    How it is consumed is what it observes: the first of ``__next__`` and
    ``__anext__`` called on it. A thread that iterates waits on the
    stream's queue. A coroutine that iterates waits on a future of its
    event loop, never on a thread: from its first ``__anext__`` on, the
    stepping loop hands the tokens of a step to all such streams through
    one ``call_soon_threadsafe`` (:meth:`LLMDeployment._send`), and what
    the queue held until then goes first.

    The deployment holds the stream from admission until it sends the
    stream's end or the consumer closes it, whichever is first. A
    consumer that leaves early must close it (the serve replica does):
    that aborts the request and frees its pages at once.
    """

    __slots__ = ("request_id", "_dep", "_queue", "_handover", "_loop",
                 "_items", "_waiter", "_done")

    def __init__(self, dep: "LLMDeployment", request_id: str):
        self.request_id = request_id
        self._dep = dep
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        # Held for an offer and for the hand-over to an event loop, so
        # that no item lands in the queue behind the loop's back.
        self._handover = threading.Lock()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # On the loop's side: what was delivered and not yet taken, and
        # the future the consumer waits on while that is nothing.
        self._items: deque = deque()
        self._waiter: Optional[asyncio.Future] = None
        self._done = False  # the end was taken, or the stream closed

    def _offer(self, items: tuple, by_loop: dict) -> bool:
        """``items`` into the queue (True), or onto the batch of the loop
        that awaits the stream. From the stepping loop's side."""
        with self._handover:
            loop = self._loop
            if loop is None:
                for item in items:
                    self._queue.put(item)
                return True
        by_loop.setdefault(loop, []).append((self, items))
        return False

    # ---- consumed by a thread ---------------------------------------

    def __iter__(self) -> "TokenStream":
        return self

    def __next__(self) -> int:
        """Waits on the stream's own queue and not on the engine lock,
        which the loop holds for the whole of a step: sixteen streams
        taking it in turn after every step kept the loop, and the chip,
        waiting."""
        if self._loop is not None:
            raise RuntimeError("an event loop awaits this stream")
        dep = self._dep
        while not self._done:
            try:
                item = self._queue.get(timeout=1.0)
            except queue.Empty:
                # Guards against an end that closed no stream. (What a
                # step published while this waited for the lock is in
                # the queue, ahead of the end put here.)
                with dep._cv:
                    dep._raise_if_dead()
                    if dep._closed or not dep._engine_knows(
                            self.request_id):
                        self._queue.put(_END)
                continue
            if item is _END:
                self._done = True
                dep._raise_if_dead()
                break
            return item
        raise StopIteration

    def close(self) -> None:
        """Abort the request if it still runs, and let go of the stream.
        Takes the engine lock, which a step holds to its end, unless the
        stream's end was already sent."""
        if self._done:
            return
        self._done = True
        self._queue.put(_END)  # a thread in ``__next__`` ends with it
        dep = self._dep
        if dep._streams.get(self.request_id) is not self:
            return  # ended by the engine, unread: nothing runs for it
        with dep._cv:
            dep._engine.abort(self.request_id)  # no-op if finished
            dep._streams.pop(self.request_id, None)
            dep._cv.notify_all()

    # ---- consumed by a coroutine ------------------------------------

    def __aiter__(self) -> "TokenStream":
        return self

    async def __anext__(self) -> int:
        if self._loop is None:
            with self._handover:
                self._loop = asyncio.get_running_loop()
                while not self._queue.empty():
                    self._items.append(self._queue.get_nowait())
        while not self._done:
            if not self._items:
                self._waiter = self._loop.create_future()
                try:
                    await self._waiter
                finally:
                    self._waiter = None
                continue
            item = self._items.popleft()
            if item is _END:
                self._done = True
                self._dep._raise_if_dead()
                break
            return item
        raise StopAsyncIteration

    def take_ready(self) -> tuple:
        """On the loop that awaits the stream: the tokens delivered and
        not yet taken, now, without waiting (none before the first
        ``__anext__``; the end stays for ``__anext__``). A serve replica
        sends them with the token ``__anext__`` just gave, in one object:
        where the loop lags the engine a stream's tokens travel together
        and the hand-over catches up."""
        items = self._items
        ready = []
        while items and items[0] is not _END:
            ready.append(items.popleft())
        return tuple(ready)

    async def aclose(self) -> None:
        """:meth:`close` from a coroutine: the wait for the engine lock
        goes to a pool thread, once, and not onto the loop, where it
        would stop every stream's delivery for a step."""
        if not self._done:
            await asyncio.get_running_loop().run_in_executor(None, self.close)


def _deliver(batch: list) -> None:
    """On the event loop: a step's tokens into the streams it awaits, and
    every consumer that waited is woken."""
    for stream, items in batch:
        stream._items.extend(items)
        waiter = stream._waiter
        if waiter is not None and not waiter.done():
            waiter.set_result(None)


class _FifoLock:
    """A mutex that threads take in the order they asked for it.

    The stepping loop drops the engine lock between iterations and asks
    for it again at once. With a plain lock it wins that race every
    time — it is running, the waiter has yet to be woken — so new
    requests were not admitted and aborts not heard until the engine ran
    dry. Here ``release`` hands the lock to the longest waiter, and the
    loop queues behind it. (Tokens do not wait for it: each request has
    a :class:`TokenStream` of its own.)
    """

    def __init__(self):
        self._mutex = threading.Lock()
        self._held = False
        self._waiters: deque = deque()  # one locked gate per waiter

    def acquire(self, blocking: bool = True) -> bool:
        with self._mutex:
            if not self._held:
                self._held = True
                return True
            if not blocking:
                return False
            gate = threading.Lock()
            gate.acquire()
            self._waiters.append(gate)
        gate.acquire()  # release() opens it: the lock is now ours
        return True

    def release(self) -> None:
        with self._mutex:
            if self._waiters:
                self._waiters.popleft().release()  # stays held: handed over
            else:
                self._held = False

    def waiting(self) -> int:
        """Threads queued for the lock right now (unlocked read)."""
        return len(self._waiters)

    __enter__ = acquire

    def __exit__(self, *exc) -> None:
        self.release()


class _HandlePeer:
    """``kv_export_*`` over a serve DeploymentHandle, sticky to ONE
    prefill replica — every chunk of a handoff must hit the replica
    that pinned the pages, so the power-of-two router is consulted
    once per peer, not once per chunk. Any failure drops the sticky
    pick (the next request re-chooses a live replica)."""

    def __init__(self, handle):
        self._handle = handle
        self._replica = None

    def _actor(self):
        if self._replica is None:
            router = self._handle._get_router()
            self._replica = router._replica_set.choose()
        return self._replica

    def _call(self, method: str, args: tuple):
        import raytpu

        try:
            return raytpu.get(self._actor().handle_request.remote(
                method, args, {}, {}))
        except Exception:
            self._replica = None
            raise

    def kv_export_begin(self, prompt, max_pages=None):
        return self._call("kv_export_begin", (prompt, max_pages))

    def kv_export_read(self, handoff_id, offset, length):
        return self._call("kv_export_read", (handoff_id, offset, length))

    def kv_export_end(self, handoff_id):
        if self._replica is None:
            return False
        return self._call("kv_export_end", (handoff_id,))


@deployment
class LLMDeployment:
    """Serve a decoder LM with continuous batching + streaming tokens.

    Args:
        model: "llama", "gpt2", "mixtral", "olmoe", "mellum", "joyai",
            "exaone_moe", "lfm2_moe", "longcat_flash", "glm_moe_dsa" or
            "ling_hybrid".
        model_config: the family's config (``LlamaConfig``,
            ``GPT2Config``, ``MixtralConfig``, ``OlmoeConfig``,
            ``MellumConfig``, ``JoyAIConfig``, ``ExaoneMoeConfig``,
            ``Lfm2MoeConfig``, ``LongcatFlashConfig``, ``GlmDsaConfig``,
            ``LingHybridConfig``) or a
            kwargs dict for one. Defaults to the family's ``tiny()``
            config in fp32/reference-attention mode (CPU-runnable).
        engine_options: kwargs forwarded to :class:`InferenceEngine`
            (page_size, num_pages, max_num_seqs, prefill_chunk,
            enable_prefix_cache, ...). One key is not the engine's:
            ``serve_options``, a dict of this deployment's own serve
            options (``health_check_timeout_s``, ...) for a caller that
            reaches the deployment through ``bind`` alone; see
            :meth:`deployment_options`.
        seed: parameter-init seed — two replicas (or a test building a
            reference model) with the same seed hold identical weights.
        role: None (serve everything, the default), "prefill" (KV
            factory: prefills + exports pages, normally not routed user
            traffic), or "decode" (pulls prompt KV from ``prefill``
            before admission and decodes).
        prefill: the prefill peer for ``role="decode"`` — a
            DeploymentHandle (serve composition) or any object with the
            ``kv_export_*`` trio (direct-instantiation tests). A model
            with window layers takes no role: what is handed off are
            prefix-cache pages, and it is served without that cache.
            Nor does a latent-attention model ("joyai",
            "longcat_flash": one pool an attention; "glm_moe_dsa": a
            latent pool and an index-key pool): the hand-off's wire
            segments are pages of K and of V, ``kv_heads * head_dim``
            wide. Nor does a model with layers
            that keep a state ("lfm2_moe", "ling_hybrid"): the state at a
            prefix's end is in none of its pages, and the wire has no
            segment for it.
    """

    @staticmethod
    def deployment_options(*args, **kwargs) -> dict:
        """What the constructor's arguments imply for the deployment
        (:meth:`raytpu.serve.deployment.Deployment.bind` asks): a replica
        takes at least as many requests as its engine has seats
        (``max_num_seqs``; the engine admits by seats and pages and keeps
        the rest waiting, so under the serve layer's 100 an engine built
        for 128 sequences decoded 100 and the router timed the others
        out), and ``engine_options["serve_options"]`` as given."""
        options = dict(kwargs.get("engine_options")
                       or (args[2] if len(args) > 2 else None) or {})
        implied = dict(options.get("serve_options") or {})
        seats = int(options.get("max_num_seqs") or 0)
        if seats > DeploymentConfig().max_ongoing_requests:
            implied.setdefault("max_ongoing_requests", seats)
        return implied

    def __init__(self, model: str = "llama", model_config=None,
                 engine_options: Optional[dict] = None, seed: int = 0,
                 role: Optional[str] = None, prefill=None):
        import dataclasses

        import jax.numpy as jnp

        if model == "llama":
            from raytpu.models.llama import Llama, LlamaConfig, init_params

            cfg_cls, model_cls, init = LlamaConfig, Llama, init_params
        elif model == "gpt2":
            from raytpu.models.gpt2 import GPT2, GPT2Config, init_params

            cfg_cls, model_cls, init = GPT2Config, GPT2, init_params
        elif model in ("mixtral", "olmoe", "mellum", "joyai", "exaone_moe",
                       "lfm2_moe", "longcat_flash", "glm_moe_dsa",
                       "ling_hybrid"):
            from raytpu.models import mixtral

            cfg_cls = {"mixtral": mixtral.MixtralConfig,
                       "olmoe": mixtral.OlmoeConfig,
                       "mellum": mixtral.MellumConfig,
                       "joyai": mixtral.JoyAIConfig,
                       "exaone_moe": mixtral.ExaoneMoeConfig,
                       "lfm2_moe": mixtral.Lfm2MoeConfig,
                       "longcat_flash": mixtral.LongcatFlashConfig,
                       "glm_moe_dsa": mixtral.GlmDsaConfig,
                       "ling_hybrid": mixtral.LingHybridConfig}[model]
            model_cls, init = mixtral.Mixtral, mixtral.init_params
        else:
            raise ValueError(f"unknown model family: {model!r}; known: "
                             f"'llama', 'gpt2', 'mixtral', 'olmoe', "
                             f"'mellum', 'joyai', 'exaone_moe', "
                             f"'lfm2_moe', 'longcat_flash', "
                             f"'glm_moe_dsa', 'ling_hybrid'")
        if model_config is None:
            model_config = dataclasses.replace(
                cfg_cls.tiny(), dtype=jnp.float32, attn_impl="reference",
                remat=False)
        elif isinstance(model_config, dict):
            model_config = cfg_cls(**model_config)
        params = init(model_cls(model_config), model_config, seed=seed,
                      batch=1)
        if role not in (None, "prefill", "decode"):
            raise ValueError(f"unknown replica role: {role!r}")
        self._role = role
        self._prefill = prefill
        self._peer = None
        engine_options = dict(engine_options or {})
        engine_options.pop("serve_options", None)  # deployment_options'
        self._engine = InferenceEngine(model_config, params,
                                       **engine_options)
        if role is not None and self._engine.cache.window is not None:
            raise ValueError(
                f"role={role!r}: a model with window layers is not "
                f"served disaggregated: the hand-off ships prefix-cache "
                f"pages, and its window pools share none")
        if role is not None and self._engine.cache.latent_row:
            raise ValueError(
                f"role={role!r}: a latent-attention model is not served "
                f"disaggregated: the hand-off's wire segments are pages of "
                f"K and of V, kv_heads * head_dim wide, and its layers "
                f"hold a latent pool (and an indexer's keys, where it "
                f"has one)")
        if role is not None and self._engine.cache.state:
            raise ValueError(
                f"role={role!r}: a model with layers that keep a state is "
                f"not served disaggregated: the state at a prefix's end is "
                f"in none of the pages the hand-off ships, and its wire "
                f"has no segment for a state")
        self._handoff_source = disagg.KVHandoffSource(self._engine)
        # One condition serializes engine mutation (add/abort/step):
        # producers signal "new work" to the loop through it.
        self._lock = _FifoLock()
        self._cv = threading.Condition(self._lock)
        # A request's stream, from its admission until its end is sent
        # or its consumer closes it: the consumer waits on the stream and
        # never for the lock, which a step holds to its end. Written
        # under the lock.
        self._streams: Dict[str, TokenStream] = {}
        # The last step's tokens, until the loop publishes them.
        self._held: list = []
        self._engine.on_launch = self._publish
        # Running totals of ``_publish``: tokens, calls onto an event
        # loop, and tokens that went through a stream's queue instead.
        self._published = {"published_tokens": 0, "publish_loop_calls": 0,
                           "published_queued": 0}
        self._closed = False
        # What killed the stepping loop, if anything did: every stream,
        # live or new, then ends by raising it.
        self._error: Optional[BaseException] = None
        # Lock-free pressure snapshot: the loop REPLACES the dict, so
        # readers never see a half-written one (GIL-atomic store).
        self._pressure = self._engine.pressure()
        # Likewise the prefix-cache digests the router and the
        # controller's health check read: a probe must never wait for
        # the engine lock, which a step holds for as long as a compile.
        self._prefix_digests: list = []
        self._prefix_version = -1
        # And the newest step records, for ``step_log``.
        self._step_tail: list = []
        self._step_thread = threading.Thread(
            target=self._step_loop, name="llm-step-loop", daemon=True)
        self._step_thread.start()

    # ---- the replica-owned stepping loop ----------------------------

    def _step_loop(self) -> None:
        """Pump the engine while any request is unfinished; park on the
        condition when idle. Runs on a daemon thread for the replica's
        whole life — consumers never step the engine themselves. Its
        own two phases, the wait for the lock before a step and the
        publishing of the step before (:meth:`_publish`), go into that
        step's record."""
        recorder = self._engine.recorder
        while True:
            # Asked for the lock -> holding it: the FIFO hand-over
            # behind the requests that came or went during the step.
            with recorder.phase("serve.llm.lock_wait",
                                {"waiters": self._lock.waiting()}):
                self._cv.acquire()
            try:
                while not self._closed and not self._engine.has_unfinished():
                    self._engine.note_idle()
                    self._pressure = self._engine.pressure()
                    self._publish_prefix_digests()  # KV handoffs adopt pages
                    self._cv.wait(timeout=0.5)
                if self._closed:
                    return
                try:
                    outs = self._engine.step()
                except Exception as e:
                    # The step's record and span carry the error; a dead
                    # loop must not leave its streams waiting for ever.
                    # There is no step to retry: a program that failed
                    # while it ran has consumed the KV pools it was given.
                    logger.exception("engine step failed; ending %d "
                                     "stream(s)", len(self._streams))
                    self._error = e
                    self._publish()
                    self._end_streams()
                    self._cv.notify_all()
                    return
                self._publish()  # of a step that waited for no decode
                self._held = outs
                if not self._engine.has_unfinished():
                    self._publish()  # no launch is coming to wait for
                self._pressure = self._engine.pressure()
                self._publish_prefix_digests()
            finally:
                self._step_tail = recorder.tail(_STEP_TAIL)
                # Dropped between iterations so request threads can
                # drain buffers / add / abort while the engine is busy.
                self._cv.release()

    def _publish_prefix_digests(self) -> None:
        """Refresh the digest snapshot if the prefix cache's index
        changed. Called by the stepping loop, under the lock."""
        cache = self._engine.prefix_cache
        if cache is not None and cache.version != self._prefix_version:
            self._prefix_version = cache.version
            self._prefix_digests = cache.summary(tuning.PREFIX_SUMMARY_MAX)

    def _publish(self) -> None:
        """Hand the tokens of the last step to their streams. The loop
        does, under the lock, and where it can as the engine's
        ``on_launch``: while the next step runs on the device. (The
        engine keeps one decode in flight: a step's tokens are those of
        the decode the step before dispatched, and ``on_launch`` comes
        before every block on a step's ids, also in a step that only
        fetches the batch's last ones and dispatches nothing. After
        that step the engine has nothing unfinished, and the loop
        publishes its tokens at once.) The
        streams that an event loop awaits (every stream of a serve
        replica) get theirs through one call onto that loop
        (:meth:`_send`), where each is stored as its client's next object
        while this thread waits for the chip; no pool thread runs for a
        token. With a consumer thread a stream, woken one by one and each
        token crossing the replica's executor twice, thirty-two streams
        held the interpreter for 8 ms after the ids had arrived
        (``infer.decode.wait`` 20.0 ms for 11.6; PERF.md, PR 44). The
        phase goes into the record of the step it falls in (inside its
        ``infer.decode.wait``), or of the one it follows, and its counts
        into that record's ``publishes``."""
        outs, self._held = self._held, []
        if not outs:
            return
        with self._engine.recorder.phase(
                "serve.llm.publish", {"tokens": len(outs)}, after=True) as ph:
            sends = []
            for out in outs:
                stream = self._streams.get(out.request_id)
                if stream is None:
                    continue
                if out.finished:
                    del self._streams[out.request_id]
                    sends.append((stream, (out.token_id, _END)))
                else:
                    sends.append((stream, (out.token_id,)))
            loop_calls, queued = self._send(sends)
            ph.attrs.update(loop_calls=loop_calls, queued=queued)
        if ph.record is not None:
            ph.record.fields.setdefault("publishes", []).append(ph.attrs)
        totals = self._published
        totals["published_tokens"] += len(outs)
        totals["publish_loop_calls"] += loop_calls
        totals["published_queued"] += queued

    @staticmethod
    def _send(sends: list) -> tuple:
        """Each ``(stream, items)`` to its stream, in order: all that an
        event loop awaits through ONE ``call_soon_threadsafe`` onto that
        loop, the others through their queues. Returns the calls made
        and the tokens queued."""
        by_loop: dict = {}
        queued = 0
        for stream, items in sends:
            if stream._offer(items, by_loop):
                queued += len(items) - (items[-1] is _END)
        for loop, batch in by_loop.items():
            try:
                loop.call_soon_threadsafe(_deliver, batch)
            except RuntimeError:
                pass  # the loop is closed: its consumers went with it
        return len(by_loop), queued

    def _end_streams(self) -> None:
        """Send every stream its end. Under the lock."""
        streams = list(self._streams.values())
        self._streams.clear()
        self._send([(stream, (_END,)) for stream in streams])

    def shutdown(self) -> None:
        """Stop the stepping loop (used by direct-instantiation tests;
        replica teardown kills the daemon thread with the process)."""
        with self._cv:
            self._handoff_source.abort_all()
            self._closed = True
            self._end_streams()
            self._cv.notify_all()
        self._step_thread.join(timeout=5.0)

    # ---- request-facing API -----------------------------------------

    def generate(self, prompt, max_new_tokens: int = 16,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 stop_token_ids=()) -> TokenStream:
        """Admit one request and return the stream of its token ids, for
        ``for`` or ``async for``; safe to call from many requests
        concurrently — they share decode steps. Waits for the engine
        lock, which a step holds to its end: call it from a thread (the
        serve replica does, from its executor), not from an event loop
        that streams wait on."""
        sampling = SamplingParams(
            max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, seed=seed, stop_token_ids=tuple(stop_token_ids))
        prompt = [int(t) for t in prompt]
        # Router-stamped identity rides the replica's request context:
        # the engine sequence keeps the CLIENT's request id, so one id
        # stitches the whole cross-process waterfall. Direct callers
        # (no router) fall back to a fresh id.
        from raytpu.serve._private.replica import get_request_context

        ctx = get_request_context()
        request_id = str(ctx.get("request_id") or uuid.uuid4().hex)
        deployment_name = str(ctx.get("deployment") or "")
        tenant = str(ctx.get("tenant") or "")
        if self._role == "decode" and self._prefill is not None:
            # Disaggregated prefill: graft the prompt's KV prefix from
            # the prefill peer before admission. Best-effort by design
            # — on any failure the request simply prefills here (the
            # colocated-retry path), never errors out.
            self._maybe_pull_prefix(prompt, request_id=request_id,
                                    deployment=deployment_name,
                                    tenant=tenant)
        stream = TokenStream(self, request_id)
        with self._cv:
            self._raise_if_dead()
            if self._closed:
                stream._offer((_END,), {})
                return stream
            seq = self._engine.add_request(request_id, prompt, sampling)
            seq.deployment = deployment_name
            seq.tenant = tenant
            self._streams[request_id] = stream
            self._cv.notify_all()  # wake the stepping loop
        return stream

    def _raise_if_dead(self) -> None:
        if self._error is not None:
            raise RuntimeError(
                f"the engine's step loop died: {self._error!r}"
            ) from self._error

    def _engine_knows(self, request_id: str) -> bool:
        # O(1): the streams are held by request id from admission until
        # the engine's last word on the request is sent.
        return request_id in self._streams

    # ---- disaggregated prefill/decode (see inference/disagg.py) -----

    def _peer_obj(self):
        if self._peer is None:
            from raytpu.serve.handle import DeploymentHandle

            peer = self._prefill
            # hasattr is useless on a DeploymentHandle (its __getattr__
            # manufactures a method wrapper for ANY name), so the wire
            # case is matched by type; everything else duck-types.
            self._peer = (_HandlePeer(peer)
                          if isinstance(peer, DeploymentHandle) else peer)
        return self._peer

    def _maybe_pull_prefix(self, prompt, request_id: str = "",
                           deployment: str = "", tenant: str = "") -> int:
        """Pull the prompt's full-page KV prefix from the prefill peer
        unless the local prefix cache already covers it. Returns tokens
        grafted (0 = nothing pulled; local prefill covers the rest)."""
        eng = self._engine
        if eng.prefix_cache is None:
            return 0
        cap = (len(prompt) - 1) // eng.page_size
        if cap <= 0:
            return 0
        with self._cv:
            local = len(eng.prefix_cache.match(prompt, max_pages=cap))
        if local >= cap:
            return 0
        if task_events.request_events_enabled() and request_id:
            task_events.emit_request(
                request_id, task_events.RequestTransition.HANDOFF_START,
                deployment=deployment, tenant=tenant,
                data={"pages_wanted": cap - local})
        pulled = disagg.pull_kv_prefix(eng, self._cv, self._peer_obj(),
                                       prompt)
        if pulled == 0:
            # Failed pull: the whole prompt goes back through local
            # prefill — book the recompute in the goodput ledger.
            serve_slo.wasted("handoff_fallback", len(prompt), deployment,
                             tenant)
        if task_events.request_events_enabled() and request_id:
            task_events.emit_request(
                request_id, task_events.RequestTransition.HANDOFF_END,
                deployment=deployment, tenant=tenant,
                data={"tokens_grafted": pulled,
                      "fallback": pulled == 0})
        return pulled

    def kv_export_begin(self, prompt, max_pages=None):
        """Open a KV export of ``prompt``'s full-page prefix, running a
        (chunked) prefill first when it isn't cached yet — the prefill
        replica's whole job. Returns the handoff meta dict, or None
        when there is nothing to export."""
        if self._role == "decode":
            raise RuntimeError("decode replicas do not export KV")
        eng = self._engine
        if eng.prefix_cache is None:
            return None
        prompt = [int(t) for t in prompt]
        cap = (len(prompt) - 1) // eng.page_size
        if max_pages is not None:
            cap = min(cap, int(max_pages))
        if cap <= 0:
            return None
        with self._cv:
            have = len(eng.prefix_cache.match(prompt, max_pages=cap))
        if have < cap:
            # Prefill through the normal request path (chunked per the
            # engine's prefill_chunk), which registers the prompt's
            # full pages as a side effect; one sampled-and-discarded
            # token is the price of reusing the engine seam unmodified.
            for _ in self.generate(prompt, max_new_tokens=1):
                pass
        with self._cv:
            return self._handoff_source.begin(prompt, max_pages=cap)

    def kv_export_read(self, handoff_id, offset, length):
        """Serve one chunk of an open export (lock-free: reads only
        pinned pages, so a slow puller never blocks the step loop)."""
        return self._handoff_source.read(handoff_id, offset, length)

    def kv_export_end(self, handoff_id) -> bool:
        with self._cv:
            return self._handoff_source.end(handoff_id)

    def prefix_summary(self) -> dict:
        """Compact routing summary for the prefix-aware router:
        registered page-chain digests plus the load signals (the same
        KV-occupancy/TTFT numbers that ride the TSDB gauges). Reads the
        stepping loop's snapshots and takes no lock: the replica's event
        loop calls this from its health check."""
        pressure = self.engine_pressure()
        return {
            "digests": list(self._prefix_digests),
            "page_size": self._engine.page_size,
            "role": self._role,
            "kv_utilization": pressure.get("kv_utilization", 0.0),
            "ttft_p95_s": pressure.get("ttft_p95_s", 0.0),
        }

    # ---- introspection ----------------------------------------------

    def engine_pressure(self) -> dict:
        """Latest engine-load snapshot, readable without the engine
        lock — the controller polls this through ``get_metrics`` even
        while a step is in flight."""
        return dict(self._pressure)

    def step_log(self, last: int = _STEP_TAIL, pauses: bool = False):
        """The newest ``last`` engine steps (at most 64), oldest first,
        as :meth:`InferenceEngine.step_log` gives them, each with the
        loop's ``serve.llm.lock_wait`` and ``serve.llm.publish`` among
        its phases: the answer to "why was that token late". With
        ``pauses`` the engine's form, ``{"steps": those, "pauses": what
        stopped this process's interpreter since the oldest of them
        began}`` (:func:`raytpu.util.tracing.host_pauses`, the steps'
        clock). Reads the stepping loop's snapshot and takes no lock."""
        tail = self._step_tail
        steps = [r.as_dict() for r in tail[-last:]] if last > 0 else []
        if not pauses:
            return steps
        return {"steps": steps, "pauses": tracing.host_pauses(
            steps[0]["start"]) if steps else []}

    def stats(self) -> dict:
        """Engine statistics, plus which process this replica is and
        the chips it leased (empty in local mode), and ``host_pauses``:
        the collections of this process's cycle collector so far, by
        generation (``{"gc": {"2": {count, seconds, longest_s}, ...}}``)."""
        with self._cv:
            stats = self._engine.stats()
            stats.update(self._published)
        stats["host_pauses"] = tracing.host_pause_totals()
        stats["replica"] = {
            "pid": os.getpid(),
            "chips": os.environ.get("RAYTPU_VISIBLE_CHIPS", "")}
        return stats

    def abort(self, request_id: str) -> bool:
        with self._cv:
            ok = self._engine.abort(request_id)
            stream = self._streams.pop(request_id, None) if ok else None
            if stream is not None:
                # Out-of-band abort: its consumer ends with the stream.
                self._send([(stream, (_END,))])
            self._cv.notify_all()
            return ok
