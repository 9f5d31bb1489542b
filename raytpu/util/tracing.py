"""Distributed tracing & profiling.

Reference analogue (SURVEY.md §5 tracing): (a) span wrapping of task/actor
calls (``python/ray/util/tracing/tracing_helper.py:34``, OpenTelemetry);
(b) chrome-trace timeline from buffered profile events (``ray timeline``,
``python/ray/_private/state.py:917``); (c) on-demand worker profiling.

Cross-process model (Dapper): a :class:`TraceContext` — trace id, span id,
parent span id, sampled flag — rides every RPC frame as a ``"tc"`` field
next to the deadline's ``"d"`` (see :mod:`raytpu.cluster.protocol`) and is
re-anchored server-side into a contextvar, so a driver's submit span is
the ancestor of the head's scheduling span and the worker's execution
span. Each process records closed spans into a bounded ring buffer;
``trace_dump`` RPCs fan the buffers back (head → nodes → workers) and
:func:`assemble_timeline` merges them into one chrome-trace/Perfetto JSON
with per-process tracks and flow arrows on cross-process parent edges.

Cost model mirrors :mod:`raytpu.util.failpoints`: with tracing disabled a
span site is one module-flag check plus returning a shared no-op context
manager — nothing allocates, no contextvar is read (pinned by the
micro-bench in tests/test_tracing.py). Arming is inherited by child
processes via ``RAYTPU_TRACING`` / ``RAYTPU_TRACE_SAMPLE`` env vars.

TPU-first: device-side profiling is ``jax.profiler`` (XLA traces viewable
in TensorBoard/Perfetto include per-op HBM/MXU utilization), host-side is
the task-event timeline the backend already buffers. Both are exposed
here: ``profile()`` wraps a region with a jax profiler trace; ``timeline``
dumps chrome-trace JSON of task events.

Step records (:class:`StepRecorder`): the inference engine keeps one
record per step in a bounded ring of its own, never shipped. A phase of
a step is stamped with ``time.perf_counter()`` into the record, entered
as a ``jax.profiler.TraceAnnotation`` (so a profiler session shows it on
the device trace's clock) and opened as a :func:`span` of the same name.
Ring spans carry that same monotonic clock as ``t0`` beside the wall
clock ``start``, so the three can be laid on one axis.

Host pauses: what stops the interpreter under a step is kept on the same
clock in one bounded ring a process (:func:`host_pauses`), installed by
the first :class:`StepRecorder` built, so a process without an engine
pays nothing. Today the one kind is ``host.gc``, a collection of the
cycle collector, from ``gc.callbacks``.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import gc
import itertools
import json
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

ENV_VAR = "RAYTPU_TRACING"
SAMPLE_ENV_VAR = "RAYTPU_TRACE_SAMPLE"
BUFFER_ENV_VAR = "RAYTPU_TRACE_BUFFER"


def _env_truthy(name: str) -> bool:
    return os.environ.get(name, "") not in ("", "0", "false", "False")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


_BUFFER = max(16, int(_env_float(BUFFER_ENV_VAR, 4096)))
_spans: "deque[dict]" = deque(maxlen=_BUFFER)
_spans_lock = threading.Lock()
_enabled = _env_truthy(ENV_VAR)
_sample_rate = _env_float(SAMPLE_ENV_VAR, 1.0)
# [kind, ident] — e.g. ["head", ""], ["worker", "ab12cd34"]. Mutated in
# place so dump() sees updates without rebinding.
_identity: List[str] = ["proc", ""]


class TraceContext:
    """Immutable Dapper-style context: which trace, which span, whose
    child, and whether anything records. On the wire only
    ``[trace_id, span_id, sampled]`` travels — the receiver's parent IS
    the sender's span id, so ``parent_span_id`` never needs to ride."""

    __slots__ = ("trace_id", "span_id", "parent_span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str,
                 parent_span_id: Optional[str] = None,
                 sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id
        self.sampled = sampled

    @classmethod
    def root(cls, sampled: bool = True) -> "TraceContext":
        return cls(os.urandom(16).hex(), os.urandom(8).hex(), None, sampled)

    def child(self) -> "TraceContext":
        return TraceContext(self.trace_id, os.urandom(8).hex(),
                            self.span_id, self.sampled)

    def to_wire(self) -> list:
        # Primitives only: must encode on strict (allow_pickle=False)
        # surfaces like the driver proxy.
        return [self.trace_id, self.span_id, 1 if self.sampled else 0]

    @classmethod
    def from_wire(cls, w: Any) -> Optional["TraceContext"]:
        try:
            trace_id, span_id, sampled = w[0], w[1], bool(w[2])
        except (TypeError, IndexError, KeyError):
            return None
        if not isinstance(trace_id, str) or not isinstance(span_id, str):
            return None
        return cls(trace_id, span_id, None, sampled)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"TraceContext({self.trace_id[:8]}…/{self.span_id}"
                f" parent={self.parent_span_id} sampled={self.sampled})")


_current: "contextvars.ContextVar[Optional[TraceContext]]" = \
    contextvars.ContextVar("raytpu_trace", default=None)


def current_trace() -> Optional[TraceContext]:
    """The ambient trace context (None outside any span/handler)."""
    return _current.get()


def set_current_trace(ctx: Optional[TraceContext]):
    """Anchor ``ctx`` as the ambient context; returns a reset token."""
    return _current.set(ctx)


def reset_current_trace(token) -> None:
    _current.reset(token)


def enabled() -> bool:
    return _enabled


def enable_tracing(sample_rate: Optional[float] = None,
                   env: bool = False) -> None:
    """Turn on span capture (reference: tracing startup hook enables the
    OpenTelemetry proxy). ``sample_rate`` bounds ROOT creation: 0.0 means
    new roots are created unsampled (contexts still propagate, nothing
    records). ``env=True`` exports the arming so child processes — cluster
    daemons, pool workers — inherit it (failpoints' ``cfg(env=True)``
    pattern)."""
    global _enabled, _sample_rate
    if sample_rate is not None:
        _sample_rate = float(sample_rate)
    _enabled = True
    if env:
        os.environ[ENV_VAR] = "1"
        os.environ[SAMPLE_ENV_VAR] = repr(_sample_rate)


def disable_tracing(env: bool = False) -> None:
    global _enabled
    _enabled = False
    if env:
        os.environ.pop(ENV_VAR, None)
        os.environ.pop(SAMPLE_ENV_VAR, None)


def set_process_identity(kind: str, ident: str = "") -> None:
    """Name this process for cluster timelines (head / node:<id> /
    worker:<id> / driver)."""
    _identity[0] = str(kind)
    _identity[1] = str(ident)


def get_spans() -> List[dict]:
    with _spans_lock:
        return list(_spans)


def clear_spans() -> None:
    with _spans_lock:
        _spans.clear()


def dump() -> dict:
    """This process's span buffer plus identity — the payload of the
    ``trace_dump`` RPC every daemon registers."""
    return {"identity": list(_identity), "pid": os.getpid(),
            "spans": get_spans()}


_NOOP_ATTRS: Dict[str, Any] = {}


class _NoopSpan:
    """Shared disabled-path context manager: zero allocation per site."""

    __slots__ = ()

    def __enter__(self) -> Dict[str, Any]:
        # Sites may write attributes into the yielded dict; a shared one
        # is fine because nothing ever reads it. Bounded by the set of
        # distinct attribute keys, not by call count.
        return _NOOP_ATTRS

    def __exit__(self, et, ev, tb) -> bool:
        return False


_NOOP_SPAN = _NoopSpan()


class _Span:
    """Recording context manager. Entering derives a child context from
    the ambient one (or starts a new root, subject to the sample rate)
    and anchors it; exiting restores the parent and — only when sampled —
    appends one record to the ring buffer."""

    __slots__ = ("name", "attrs", "_ctx", "_token", "_start", "_t0")

    def __init__(self, name: str, attributes: Optional[Dict] = None):
        self.name = name
        self.attrs: Dict[str, Any] = dict(attributes) if attributes else {}

    def __enter__(self) -> Dict[str, Any]:
        parent = _current.get()
        if parent is not None:
            self._ctx = parent.child()
        else:
            sampled = _sample_rate >= 1.0 or random.random() < _sample_rate
            self._ctx = TraceContext.root(sampled=sampled)
        self._token = _current.set(self._ctx)
        self._start = time.time()
        self._t0 = time.perf_counter()
        return self.attrs

    def __exit__(self, et, ev, tb) -> bool:
        dur = time.perf_counter() - self._t0
        _current.reset(self._token)
        ctx = self._ctx
        if ctx.sampled:
            with _spans_lock:
                _spans.append({
                    "name": self.name,
                    "trace_id": ctx.trace_id,
                    "span_id": ctx.span_id,
                    "parent_span_id": ctx.parent_span_id,
                    "start": self._start,
                    "t0": self._t0,  # perf_counter: the step records' clock
                    "duration_s": dur,
                    "pid": os.getpid(),
                    "tid": threading.get_native_id(),
                    "attributes": self.attrs,
                    "error": repr(ev) if ev is not None else None,
                })
        return False


def span(name: str, attributes: Optional[Dict[str, Any]] = None):
    """One traced region. Disabled cost is this flag check plus a shared
    no-op context manager; enabled, it parents into the ambient
    :class:`TraceContext` and records into the ring buffer. Yields the
    (mutable) attributes dict so sites can attach results post-hoc::

        with tracing.span("sched.decide") as attrs:
            node = pick()
            attrs["node"] = node
    """
    if not _enabled:
        return _NOOP_SPAN
    return _Span(name, attributes)


# -- host pauses ---------------------------------------------------------------

# A collection is kept as an entry where it was a full one or took longer
# than this; every collection is counted.
GC_KEPT_OVER_S = 1e-3
PAUSE_RING = 1024
# [kind, t0, t1, attrs] on ``time.perf_counter()``, oldest first.
_pauses: "deque[list]" = deque(maxlen=PAUSE_RING)
# Collections so far by generation: how many, their seconds, and the
# longest of those kept.
_gc_counts = [0, 0, 0]
_gc_seconds = [0.0, 0.0, 0.0]
_gc_longest = [0.0, 0.0, 0.0]
# What :func:`gc_unpublished` has handed out of the first two.
_gc_published = [0, 0, 0, 0.0]
_gc_t0 = 0.0  # the newest collection's start
_gc_annotation = None
_stepping_thread = 0  # ident of the thread that last opened a step
_pauses_installed = False
_now = time.perf_counter


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks``: ``start`` stamps, ``stop`` counts the collection
    and keeps an entry of a full or a long one. Collections do not nest
    and run under the interpreter's lock, so the module's state is
    enough; nothing is allocated here but a kept entry. A young
    collection costs two clock reads and three additions."""
    global _gc_t0
    if phase == "start":
        if info["generation"] == 2:
            _gc_full_begins()
        _gc_t0 = _now()
        return
    t1 = _now()
    took = t1 - _gc_t0
    generation = info["generation"]
    _gc_counts[generation] += 1
    _gc_seconds[generation] += took
    if took > GC_KEPT_OVER_S or generation == 2:
        _gc_keep(generation, _gc_t0, t1, info)


def _gc_full_begins() -> None:
    """A full collection lies beside the device's operations in a
    profiler session, as a phase does."""
    global _gc_annotation
    annotation = _trace_annotation or _annotation_type()
    if annotation is not None:
        _gc_annotation = annotation("host.gc")
        _gc_annotation.__enter__()


def _gc_keep(generation: int, t0: float, t1: float,
             info: Dict[str, int]) -> None:
    global _gc_annotation
    if _gc_annotation is not None:
        _gc_annotation.__exit__(None, None, None)
        _gc_annotation = None
    if t1 - t0 > _gc_longest[generation]:
        _gc_longest[generation] = t1 - t0
    _pauses.append(["host.gc", t0, t1, {
        "generation": generation, "collected": info["collected"],
        "stepping": threading.get_ident() == _stepping_thread}])


def _install_host_pauses() -> None:
    """Once a process, by the first :class:`StepRecorder` it builds."""
    global _pauses_installed, _gc_t0
    if not _pauses_installed:
        _pauses_installed = True
        # A collection that is running now is counted from here.
        _gc_t0 = _now()
        gc.callbacks.append(_on_gc)


def host_pauses(since: float = 0.0) -> List[list]:
    """The pauses of this process's interpreter that ended after ``since``
    (``perf_counter`` seconds, the step log's clock), oldest first:
    ``[kind, t0, t1, attrs]``. ``host.gc`` is a collection of the cycle
    collector, with ``generation``, ``collected`` and ``stepping`` (whether
    the thread that collected is the one that last opened a step); kept
    are the full collections and any that took over ``GC_KEPT_OVER_S``,
    the newest ``PAUSE_RING`` of them. Empty in a process that has built
    no :class:`StepRecorder`."""
    for _ in range(4):
        try:
            kept = list(_pauses)
            break
        except RuntimeError:  # a collection ended while it was copied
            continue
    else:
        return []
    return [[kind, t0, t1, dict(attrs)] for kind, t0, t1, attrs in kept
            if t1 > since]


def host_pause_totals() -> Dict[str, Dict[str, Dict[str, float]]]:
    """Every collection since the callback was installed, by generation
    (string keys: the dict crosses the serve wire): ``count``,
    ``seconds``, and ``longest_s``, the longest of those kept as an
    entry (a full one, or one over ``GC_KEPT_OVER_S``; 0.0: none was)."""
    return {"gc": {str(g): {"count": _gc_counts[g],
                            "seconds": _gc_seconds[g],
                            "longest_s": _gc_longest[g]}
                   for g in range(3)}}


def gc_unpublished() -> Optional[Tuple[List[int], float]]:
    """Collections by generation and their seconds since the last call
    that returned any: what a step's end adds to the metrics pipeline.
    None, at the cost of a sum of three, where there was none."""
    counts = _gc_counts
    done = _gc_published
    if counts[0] + counts[1] + counts[2] == done[0] + done[1] + done[2]:
        return None
    seconds = sum(_gc_seconds)
    out = ([counts[g] - done[g] for g in range(3)], seconds - done[3])
    done[:] = [*counts, seconds]
    return out


# -- step records -------------------------------------------------------------

_trace_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation_type():
    """``jax.profiler.TraceAnnotation`` if this process has imported jax,
    else None. Never imports it: a driver must stay off JAX (a process
    that touches it takes the chip)."""
    global _trace_annotation
    if _trace_annotation is None:
        jax = sys.modules.get("jax")
        _trace_annotation = getattr(getattr(jax, "profiler", None),
                                    "TraceAnnotation", None)
    return _trace_annotation


class StepRecord:
    """One step: ``start``/``end`` on ``time.perf_counter()``, its phases
    as ``[name, t0, t1]`` in order of their start (a phase before the
    phases inside it), and what the step's sites wrote into ``fields``."""

    __slots__ = ("start", "end", "phases", "fields")

    def __init__(self, fields: Dict[str, Any]):
        self.start = self.end = 0.0
        self.phases: List[list] = []
        self.fields = fields

    def seconds(self, *names: str) -> float:
        """Total duration of the phases called one of ``names``."""
        return sum(t1 - t0 for name, t0, t1 in self.phases if name in names)

    def as_dict(self) -> Dict[str, Any]:
        return dict(self.fields, start=self.start, end=self.end,
                    phases=[list(p) for p in self.phases])


class _Phase:
    """An open phase (or, with ``opens``, the step itself). Entering
    stamps the clock last and leaving stamps it first, so the stamps are
    the innermost of the three records of the phase. A phase that counts
    its thread's CPU time (the step always, a phase opened with ``cpu``)
    reads ``time.thread_time()`` inside its stamps, so the CPU seconds of
    a phase never pass its wall seconds."""

    __slots__ = ("name", "attrs", "t0", "t1", "record", "_recorder",
                 "_opens", "_after", "_entry", "_annotation", "_span",
                 "_cpu", "_cpu0")

    def __init__(self, recorder: "StepRecorder", name: str,
                 attrs: Optional[Dict[str, Any]], opens: bool, after: bool,
                 cpu: Optional[str] = None):
        self.name = name
        self.attrs: Dict[str, Any] = {} if attrs is None else attrs
        self.t0 = self.t1 = 0.0
        self._recorder = recorder
        self._opens = opens
        self._after = after
        # The record's field that takes this thread's CPU seconds over
        # the phase (the step's own: ``cpu_s``), read inside the stamps.
        self._cpu = "cpu_s" if opens else cpu

    def __enter__(self) -> "_Phase":
        self._span = None
        if _enabled:
            self._span = _Span(self.name)
            self._span.attrs = self.attrs  # one dict: sites write it once
            self._span.__enter__()
        annotation = _trace_annotation or _annotation_type()
        self._annotation = None
        if annotation is not None:
            self._annotation = annotation(self.name)
            self._annotation.__enter__()
        recorder = self._recorder
        if self._opens:
            global _stepping_thread
            _stepping_thread = threading.get_ident()
            self.record = record = recorder.open = StepRecord(self.attrs)
            record.phases.extend(recorder._early)
            recorder._early.clear()
            self._entry = record
            self.t0 = record.start = time.perf_counter()
        else:
            self._entry = entry = [self.name, 0.0, 0.0]
            record = recorder.open
            if record is None and self._after and recorder._ring:
                record = recorder._ring[-1]
            self.record = record  # None: the next step adopts the phase
            (recorder._early if record is None
             else record.phases).append(entry)
            self.t0 = entry[1] = entry[2] = time.perf_counter()
        if self._cpu is not None:
            self._cpu0 = time.thread_time()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        if self._cpu is not None and self.record is not None:
            fields = self.record.fields
            fields[self._cpu] = fields.get(self._cpu, 0.0) \
                + time.thread_time() - self._cpu0
        self.t1 = time.perf_counter()
        if self._opens:
            self._entry.end = self.t1
            if ev is not None:
                self.attrs["error"] = repr(ev)
            self._recorder.open = None
            self._recorder._close(self._entry)
        else:
            self._entry[2] = self.t1
        if self._annotation is not None:
            self._annotation.__exit__(et, ev, tb)
        if self._span is not None:
            self._span.__exit__(et, ev, tb)
        return False


class StepRecorder:
    """A bounded ring of :class:`StepRecord`, local to its owner (the
    inference engine) and read in-process: nothing drains it over RPC.
    One thread at a time steps, so one step at most is open, and a
    closed record is written only by the phases that follow its step
    (``after=True``). ``maxlen`` 4096 holds a 40 s window at a 10 ms
    step. A record's ``cpu_s`` is its thread's CPU time over the step
    (``time.thread_time()``): what the step's wall time holds beyond it,
    the thread spent off the CPU, blocked or without the interpreter."""

    def __init__(self, maxlen: int = 4096, keep: Tuple[str, ...] = ()):
        self._ring: "deque[StepRecord]" = deque(maxlen=maxlen)
        self.open: Optional[StepRecord] = None
        # Phases closed while no step was open, for the next to adopt
        # (the wait for the lock that precedes it).
        self._early: "deque[list]" = deque(maxlen=16)
        # Of the fields in ``keep``, the truthy values of the records the
        # ring holds, each beside its record's count: what ``values``
        # would walk the ring for, kept as the ring is appended to.
        self._closed = 0
        self._kept: Dict[str, "deque[Tuple[int, Any]]"] = {
            field: deque() for field in keep}
        _install_host_pauses()

    def _close(self, record: StepRecord) -> None:
        """A step has ended: into the ring, and out of ``_kept`` what
        the ring dropped for it."""
        self._ring.append(record)
        self._closed += 1
        dropped = self._closed - self._ring.maxlen
        for field, kept in self._kept.items():
            value = record.fields.get(field)
            if value:
                kept.append((self._closed, value))
            while kept and kept[0][0] <= dropped:
                kept.popleft()

    def step(self, name: str,
             fields: Optional[Dict[str, Any]] = None) -> _Phase:
        """Open the step: ``with recorder.step("infer.step") as st``.
        ``st.attrs`` is the record's ``fields`` and the span's
        attributes, one dict."""
        return _Phase(self, name, fields, True, False)

    def phase(self, name: str, attrs: Optional[Dict[str, Any]] = None,
              after: bool = False, cpu: Optional[str] = None) -> _Phase:
        """Open a phase of the step in flight. With no step open it
        belongs to the next one, or with ``after`` to the last. ``cpu``
        names a field of the record that the phase adds its thread's CPU
        seconds to (``time.thread_time()``: two clock calls)."""
        return _Phase(self, name, attrs, False, after, cpu)

    def __len__(self) -> int:
        return len(self._ring)

    def values(self, field: str) -> list:
        """The truthy values of ``field`` over the ring, oldest first:
        a copy of what was kept for a field in ``keep``, else a walk of
        the ring."""
        kept = self._kept.get(field)
        if kept is not None:
            return [v for _, v in kept]
        return [v for r in self._ring if (v := r.fields.get(field))]

    def tail(self, n: int) -> List[StepRecord]:
        """The newest ``n`` closed records, oldest first."""
        out = list(itertools.islice(reversed(self._ring), n))
        out.reverse()
        return out

    def log(self, since: float = 0.0) -> Dict[str, Any]:
        """The records that ended after ``since`` (``perf_counter``
        seconds), oldest first, as plain dicts; and the start of the
        oldest record the ring still holds (None when empty), so that a
        reader can tell truncation from silence; and under ``pauses``
        what stopped this process's interpreter and ended after
        ``since`` (:func:`host_pauses`), on the same clock."""
        steps = []
        for record in reversed(self._ring):
            if record.end <= since:
                break
            steps.append(record.as_dict())
        steps.reverse()
        return {"oldest_start": self._ring[0].start if self._ring else None,
                "steps": steps, "pauses": host_pauses(since)}


def traced(name: Optional[str] = None) -> Callable:
    """Decorator version of :func:`span`."""

    def wrap(fn: Callable) -> Callable:
        label = name or getattr(fn, "__qualname__", "fn")

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(label):
                return fn(*args, **kwargs)

        return inner

    return wrap


def run_with_trace(tc: Optional[TraceContext], name: str,
                   fn: Callable, *args, **kwargs):
    """Re-anchor ``tc`` around ``fn`` on THIS thread and run it inside a
    span. The bridge for every hop that loses contextvars: executor
    offloads (``run_in_executor`` does not copy context) and
    queue-decoupled execution (a task enqueued by one RPC and executed
    later by a dispatcher thread)."""
    token = _current.set(tc) if tc is not None else None
    try:
        with span(name):
            return fn(*args, **kwargs)
    finally:
        if token is not None:
            _current.reset(token)


def _span_event(s: dict, pid: Optional[int] = None) -> dict:
    args = dict(s.get("attributes") or {})
    for k in ("trace_id", "span_id", "parent_span_id"):
        if s.get(k):
            args[k] = s[k]
    if s.get("error"):
        args["error"] = s["error"]
    return {
        "name": s["name"],
        "cat": "span",
        "ph": "X",
        "ts": s["start"] * 1e6,
        "dur": s["duration_s"] * 1e6,
        "pid": s.get("pid", 0) if pid is None else pid,
        "tid": s.get("tid", 0),
        "args": args,
    }


@contextlib.contextmanager
def profile(logdir: str, *, host_tracer_level: int = 2):
    """XLA device profiling for the enclosed region: the operator's entry
    to ``jax.profiler``. Produces a trace viewable in TensorBoard's
    profiler / Perfetto (per-op timing, HBM pressure, MXU utilization —
    the TPU analogue of the reference's nsight runtime-env plugin). The
    host's lines hold what entered a ``TraceAnnotation`` in the region:
    every phase of a :class:`StepRecorder` (``infer.*``, ``serve.llm.*``)
    lies beside the device's operations, on their clock, and so does a
    full collection of the cycle collector (``host.gc``) in a process
    that has built a recorder.
    ``host_tracer_level`` is the profiler's (1: annotations only, 2: the
    runtime's own events too); Python's call tracer stays off, it would
    bury the phases under every function call."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = int(host_tracer_level)
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, create_perfetto_trace=False,
                             profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def timeline(filename: Optional[str] = None) -> List[dict]:
    """Chrome-trace events from the backend's task-event buffer plus any
    locally recorded spans (reference: ``ray timeline``). Spans carry
    their real pid/tid so a multi-threaded local timeline lays out on
    distinct tracks. For the whole cluster, see
    :func:`cluster_timeline`."""
    import raytpu

    events = raytpu.timeline()
    trace = list(events) if isinstance(events, list) else []
    for s in get_spans():
        trace.append(_span_event(s))
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


def assemble_timeline(dumps: List[dict],
                      filename: Optional[str] = None) -> List[dict]:
    """Merge per-process trace dumps (:func:`dump` payloads) into one
    chrome-trace JSON. Each dump becomes one ``pid`` track named by its
    identity via a ``process_name`` metadata event; spans whose parent
    lives in a DIFFERENT process get a flow-event pair (``ph:"s"`` at the
    parent, ``ph:"f", bp:"e"`` at the child) so Perfetto draws the
    cross-process arrow."""
    events: List[dict] = []
    # span_id -> (track pid, record)
    index: Dict[str, Tuple[int, dict]] = {}
    for i, d in enumerate(dumps or []):
        if not isinstance(d, dict):
            continue
        ident = list(d.get("identity") or ("proc", ""))
        label = str(ident[0]) if ident else "proc"
        if len(ident) > 1 and ident[1]:
            label += f":{ident[1]}"
        label += f" (pid {d.get('pid', '?')})"
        track = i + 1
        events.append({"name": "process_name", "ph": "M", "pid": track,
                       "tid": 0, "args": {"name": label}})
        for s in d.get("spans") or []:
            events.append(_span_event(s, pid=track))
            sid = s.get("span_id")
            if sid:
                index[sid] = (track, s)
    for sid, (track, s) in index.items():
        parent = s.get("parent_span_id")
        if not parent or parent not in index:
            continue
        ptrack, ps = index[parent]
        if ptrack == track:
            continue  # local nesting draws itself; arrows are for hops
        events.append({
            "name": "trace", "cat": "flow", "ph": "s", "id": sid,
            "pid": ptrack, "tid": ps.get("tid", 0),
            "ts": ps["start"] * 1e6,
        })
        events.append({
            "name": "trace", "cat": "flow", "ph": "f", "bp": "e",
            "id": sid, "pid": track, "tid": s.get("tid", 0),
            "ts": s["start"] * 1e6,
        })
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events


def cluster_timeline(filename: Optional[str] = None) -> List[dict]:
    """Pull every process's span buffer through the connected backend's
    ``trace_dump`` fan-out (driver → head → nodes → workers) and
    assemble one cluster-wide chrome trace. Falls back to just the local
    process when not connected to a cluster."""
    dumps: List[dict] = []
    try:
        from raytpu.runtime import api as _api

        backend = _api._backend_or_none()
    except Exception:  # pragma: no cover - api import never fails in-tree
        backend = None
    if backend is not None and hasattr(backend, "trace_dump"):
        try:
            dumps = list(backend.trace_dump() or [])
        except Exception:
            dumps = []
    # The head's fan-out can reach this very process (a connected driver
    # runs a serve-only node daemon): drop that copy in favor of the
    # local buffer, which is strictly fresher, or the driver would get
    # two identical tracks.
    me = os.getpid()
    dumps = [d for d in dumps
             if not (isinstance(d, dict) and d.get("pid") == me)]
    dumps.append(dump())  # this (driver) process
    return assemble_timeline(dumps, filename)
