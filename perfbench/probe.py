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
wrapping ``_prefill_fn``/``_decode_fn``): the spans around the prefill
and the decode, and the sequences and live pages of each decode. They
are listed for the ``tracing`` PR in ``PERF.md``'s open questions.

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
from typing import List, Optional

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
        if self.captured is not None:
            self.captured.append(("prefill_id", seq.request_id))
        self._current.prefills += 1
        with self._span("pb.engine.prefill"):
            return super()._run_prefill(seq, out)

    def _run_decode(self, seqs, out):
        rec = self._current
        rec.decodes = len(seqs)
        # Pages the paged kernel must read this step: each sequence's
        # context (the token being written included) in whole pages.
        rec.live_pages = sum(self.cache.pages_for(s.cached_len + 1)
                             for s in seqs)
        if self.captured is not None:
            self.captured.append(("decode_ids",
                                  [s.request_id for s in seqs]))
        with self._span("pb.engine.decode"):
            return super()._run_decode(seqs, out)

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


def install() -> None:
    """Have ``LLMDeployment`` build a ``ProbedEngine``."""
    from raytpu.inference import serving

    ProbedEngine.instances.clear()
    serving.InferenceEngine = ProbedEngine


def uninstall() -> None:
    from raytpu.inference import serving

    serving.InferenceEngine = InferenceEngine
