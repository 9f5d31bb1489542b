"""The benchmark's own instrumentation: spans around the calls into each
layer, recorded from outside the program.

``Tracer`` wraps JAX's profiler for the traced part of a window and puts
``pb.*`` spans on the profiler's clock. ``ProbedEngine`` is the program's
``InferenceEngine`` with a clock around ``step``; it is put in place of
the class the serve replica builds, so requests still take the normal
path from ``serve.run`` down, and nothing in the program is changed.
The running totals it keeps per step (compiles, preemptions, pool use,
prefill tokens) are read from the program's record of the step
(``step_log``) and the public ``cache.utilization()`` and
``scheduler.num_preemptions``. Two
things have no public source yet and come from overriding the engine's
private ``_run_prefill`` and ``_run_decode`` (and, for the check only,
wrapping ``_prefill_fn``/``_decode_fn`` and noting each sequence's
``cached_len`` around the call: ``kept_rows`` states that contract): the
spans around the prefill and the decode, and the sequences and live
pages of each decode. They are listed for the ``tracing`` PR in
``PERF.md``'s open questions.

The program keeps its own step records in a ring (``step_log``). The
probe takes each one as its step ends, on the thread that steps, and
holds it beside its own, so that no reader depends on how many steps the
ring holds, and copies nothing while the run goes.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import time
from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

from raytpu.inference.engine import InferenceEngine


class Tracer:
    """The profiler for the first ``seconds`` of a window."""

    def __init__(self, directory: str, seconds: float):
        self.directory = directory
        self.seconds = seconds
        self.running = False
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # our spans only; Python's
        options.host_tracer_level = 1    # own calls would swamp them
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started_at = time.perf_counter()
        self.running = True

    def stop(self) -> None:
        import jax

        self.running = False
        self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name) if self.running \
            else contextlib.nullcontext()

    def xplane(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


class StepRecord:
    __slots__ = ("start", "end", "traced", "prefills", "decodes",
                 "live_pages", "kv_utilization", "preemptions", "compiles",
                 "prefill_tokens", "program")

    def __init__(self, start: float):
        self.start = start
        self.end = start
        self.traced = False   # its span was opened in the profiler's trace
        self.prefills = 0
        self.decodes = 0
        self.live_pages = 0
        # After the step, from ``stats()``; the last three are the
        # engine's running totals, so a window takes differences.
        self.kv_utilization = 0.0
        self.preemptions = 0
        self.compiles = 0     # programs traced so far, all three kinds
        self.prefill_tokens = 0
        # The program's own record of the step (``tracing.StepRecord``),
        # the object itself: the stepping loop may still add a phase to
        # it (its publishing), and the ring may drop it.
        self.program = None


class ProbedEngine(InferenceEngine):
    """``InferenceEngine`` observed from the benchmark's side."""

    instances: List["ProbedEngine"] = []  # the serve replica's, in-process

    def __init__(self, *args, **kwargs):
        self.init_started = time.perf_counter()
        super().__init__(*args, **kwargs)
        # InferenceEngine(model_config, params, **options): the weights
        # the deployment made, for the reference check.
        self.params_given = args[1] if len(args) > 1 else kwargs["params"]
        self.steps: List[StepRecord] = []
        self.tracer: Optional[Tracer] = None
        self.captured: Optional[list] = None  # logits, for the check
        self._current: Optional[StepRecord] = None
        ProbedEngine.instances.append(self)

    def _span(self, name: str):
        """A span inside the step's, where the step's own was taken."""
        return self.tracer.span(name) if self._current.traced \
            else contextlib.nullcontext()

    def step(self):
        rec = self._current = StepRecord(time.perf_counter())
        rec.traced = bool(self.tracer and self.tracer.running)
        with self._span("pb.engine.step"):
            out = super().step()
        rec.end = time.perf_counter()
        # What the step ran, from the program's own record of it and two
        # public counters: a constant cost a step. (``stats()`` walks the
        # recorder's whole ring for its histogram: 0.13 ms a step at the
        # window's opening and 0.72 at its close, my chip runs, PR 29.)
        rec.program = done = self.recorder.tail(1)[0]
        last = self.steps[-1] if self.steps else rec  # a new one: zeros
        rec.kv_utilization = self.cache.utilization()
        rec.preemptions = self.scheduler.num_preemptions
        rec.prefill_tokens = last.prefill_tokens + sum(
            p["tokens"] for p in done.fields.get("prefills", ()))
        rec.compiles = last.compiles + done.fields["compiled"]
        self.steps.append(rec)
        return out

    def step_log(self, since: float = 0.0) -> dict:
        """As the program's ``step_log``, of every step since the engine
        was built, however many its ring holds. With no step in flight
        (the readers call it when the traffic has ended)."""
        return {"oldest_start": self.steps[0].program.start
                if self.steps else None,
                "steps": [r.program.as_dict() for r in self.steps
                          if r.program.end > since]}

    def _run_prefill(self, seq, out):
        captured = self.captured  # the check's calls: see ``kept_rows``
        if captured is not None:
            captured.append(("prefill_id", seq.request_id))
            before = seq.cached_len
        self._current.prefills += 1
        with self._span("pb.engine.prefill"):
            n = super()._run_prefill(seq, out)
        if captured is not None:
            captured.append(("advanced",
                             [(seq.request_id, before, seq.cached_len)]))
        return n

    def _run_decode(self, seqs, out):
        rec = self._current
        rec.decodes = len(seqs)
        # Pages the paged kernel must read this step: each sequence's
        # context (the token being written included) in whole pages.
        rec.live_pages = sum(self.cache.pages_for(s.cached_len + 1)
                             for s in seqs)
        captured = self.captured
        if captured is not None:
            captured.append(("decode_ids", [s.request_id for s in seqs]))
            before = [s.cached_len for s in seqs]
        with self._span("pb.engine.decode"):
            n = super()._run_decode(seqs, out)
        if captured is not None:
            captured.append(("advanced", [
                (s.request_id, b, s.cached_len)
                for s, b in zip(seqs, before)]))
        return n

    def capture_logits(self) -> list:
        """From now on keep every prefill's and decode's logits (the check
        reads them; ``stop_capture`` ends it)."""
        self.captured = []
        prefill, decode = self._prefill_fn, self._decode_fn

        def prefill_kept(*a):
            res = prefill(*a)
            self.captured.append(("prefill", res[0]))
            return res

        def decode_kept(*a):
            res = decode(*a)
            self.captured.append(("decode", res[0]))
            return res

        self._prefill_fn, self._decode_fn = prefill_kept, decode_kept
        self._plain_fns = (prefill, decode)
        return self.captured

    def stop_capture(self) -> None:
        self._prefill_fn, self._decode_fn = self._plain_fns
        self.captured = None


def kept_rows(captured: Sequence, wanted: Mapping[str, Sequence[int]]
              ) -> Dict[str, Dict[int, np.ndarray]]:
    """The logits the engine computed for each wanted (request, position),
    float32, from what ``capture_logits`` kept.

    The contract a program's step has to meet. ``_run_decode`` (and
    ``_run_prefill``) is noted with each sequence's ``cached_len`` before
    the call and after it (``"advanced"``: ``(request, before, after)`` a
    sequence, in the order of ``seqs``). Inside the call the program
    calls ``_decode_fn`` once, and its first result is ``[bucket, V]``
    or ``[bucket, T, V]``. Row ``(i, j)`` belongs to ``seqs[i]`` at
    position ``before + j``, and only the rows ``j < after - before`` are
    kept: a step that verifies ``T`` positions a sequence and accepts
    one or two of them advances ``cached_len`` by as many, and a sequence
    that finished inside the step keeps what it advanced by. A whole
    prompt's program (``_prefill_fn``, ``[bucket, V]``, one sequence) is
    read the same way, a row a position; a chunk's is not captured, and
    its note is passed over. Raises where a wanted position has no row
    or two, where a call's result has fewer rows than a sequence
    advanced by, and where ``_decode_fn`` ran twice in one call or not
    at all."""
    rows: Dict[str, Dict[int, np.ndarray]] = {rid: {} for rid in wanted}
    pending = None  # (kind, logits) of the program that ran in this call
    for kind, value, *_ in captured:
        if kind in ("prefill", "decode"):
            if pending is not None:
                raise RuntimeError(f"check: two {kind} programs ran in one "
                                   f"call of the engine's step")
            pending = (kind, value)
        elif kind == "advanced":
            if pending is None:
                if len(value) != 1:  # a chunk is one sequence's
                    raise RuntimeError("check: a decode step ran no "
                                       "captured program")
                continue
            (ran, logits), pending = pending, None
            host = None  # the decode's logits, brought back once
            for i, (rid, before, after) in enumerate(value):
                for pos in wanted.get(rid, ()):
                    if not before <= pos < after:
                        continue
                    if pos in rows[rid]:
                        raise RuntimeError(
                            f"check: two rows for position {pos} of {rid}")
                    if ran == "prefill":
                        row = logits[pos - before]
                    else:
                        if host is None:
                            host = np.asarray(logits, np.float32)
                            host = host[:, None] if host.ndim == 2 else host
                        if pos - before >= host.shape[1]:
                            raise RuntimeError(
                                f"check: {rid} advanced by {after - before}"
                                f" in a step of {host.shape[1]} row(s)")
                        row = host[i, pos - before]
                    rows[rid][pos] = np.asarray(row, np.float32)
    missing = {rid: [p for p in wanted[rid] if p not in got]
               for rid, got in rows.items()}
    if any(missing.values()):
        raise RuntimeError(f"check: no row for positions "
                           f"{ {r: m for r, m in missing.items() if m} }")
    return rows


def install() -> None:
    """Have ``LLMDeployment`` build a ``ProbedEngine``."""
    from raytpu.inference import serving

    ProbedEngine.instances.clear()
    serving.InferenceEngine = ProbedEngine


def uninstall() -> None:
    from raytpu.inference import serving

    serving.InferenceEngine = InferenceEngine
