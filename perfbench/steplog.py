"""The program's own record of its engine steps, read for the per-layer
metrics of the host path.

``InferenceEngine.step_log()`` gives one record per step: ``start``,
``end``, ``phases`` (``[name, t0, t1]``, taken with ``time.perf_counter``
where the work happens) and what the step ran. The engine the serve
replica built lives in this process (``probe.ProbedEngine.instances``), so
its stamps are on the clock of ``run.window``; the probe takes each record
out of the program's ring as its step ends, so a window may hold more
steps than the ring. A program without a step
log (the parent of the PR that brought it) gives ``None`` to every reader
here, and the result line leaves their metrics out.

For the device's idle time the records are put on the trace's clock by
the pairs the benchmark already has: ``run.traced_steps[i].start`` against
the ``i``-th ``pb.engine.step`` span of ``run.trace``, paired in order as
``paged_attn_roofline`` pairs them. The offset is their median. A span
opens a few microseconds after its stamp and, when another thread takes
the interpreter in between, milliseconds after: that is the benchmark's
own delay, not a disagreement of the clocks, so one late pair must not
decide. Clocks that disagree show in most pairs: if half of them lie
further than 100 us from the median (twice the median absolute deviation
over 200 us), nothing is read.

Idle is chip 0's: the traced window less the intervals in which an
operation ran. ``trace_reduce.idle_gaps`` names a whole gap by the span
open when it began; here every gap begins while the host waits for the
step it launched (``infer.decode.wait``) and runs on through sampling,
publishing and the next launch, so a gap is cut at the phase boundaries
it crosses and each piece goes to the innermost phase open over it.
"""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OFFSETS_AGREE_WITHIN_S = 200e-6
PREFILL_PHASES = ("infer.prefill", "infer.prefill_chunk")
Segment = Tuple[float, float, str]  # start, end, innermost phase


def engine_log(run) -> Optional[Dict]:
    """The whole step log of the run's engine; None if the program keeps
    none, or if records of the window's first steps were lost."""
    from perfbench import probe

    engines = probe.ProbedEngine.instances
    fetch = getattr(engines[-1], "step_log", None) if engines else None
    if fetch is None:
        return None
    log = fetch(0.0)
    if log["oldest_start"] is None or log["oldest_start"] > run.window[0]:
        return None
    return log


def window_steps(run) -> Optional[List[Dict]]:
    """The records of the steps that ended inside the window."""
    log = engine_log(run)
    if log is None:
        return None
    lo, hi = run.window
    return [s for s in log["steps"] if lo < s["end"] <= hi]


def phase_seconds(step: Dict, names: Iterable[str]) -> Optional[float]:
    """Total time of the step's phases called one of ``names``; None if
    it has none."""
    found = [t1 - t0 for name, t0, t1 in step["phases"] if name in names]
    return sum(found) if found else None


def phase_ms_p50(run, *names: str) -> Optional[float]:
    """Median, over the window's steps that hold such a phase, of the
    time the step spent in phases called one of ``names``."""
    steps = window_steps(run)
    if steps is None:
        return None
    seconds = [x for x in (phase_seconds(s, names) for s in steps)
               if x is not None]
    return 1e3 * statistics.median(seconds) if seconds else None


def step_gap_ms_p50(run) -> Optional[float]:
    """Median of the next step's start less this step's end."""
    steps = window_steps(run)
    if steps is None or len(steps) < 2:
        return None
    return 1e3 * statistics.median(
        b["start"] - a["end"] for a, b in zip(steps, steps[1:]))


def decode_period(steps: Sequence[Dict]) -> Dict[str, Optional[float]]:
    """What a stream's time per output token is made of, by the
    program's own stamps: ``step_period_ms_p50``, the median time from a
    plain decode step's start to the next step's; ``tokens_per_seq_step``,
    tokens the window's decodes gave out over the sequences they ran (1
    where the program does not draft); ``prefill_share_ms``, what the
    steps that held a prefill took beyond that median, spread over all
    the decoding steps. The first over the second is what
    ``tpot_p50_ms`` should read at the client; ``tpot_mean_ms`` lies over
    that by the third, by the plain steps' own tail (their mean over
    their median), by the seats a turnover leaves empty and by any
    stall."""
    pairs = [(a, b["start"] - a["start"]) for a, b in zip(steps, steps[1:])
             if a["decodes"]]
    plain = [1e3 * p for a, p in pairs if not a.get("prefills")]
    out = dict.fromkeys(("step_period_ms_p50", "tokens_per_seq_step",
                         "prefill_share_ms"))
    if plain:
        period = out["step_period_ms_p50"] = statistics.median(plain)
        out["tokens_per_seq_step"] = sum(
            a.get("emitted", a["decodes"]) for a, _ in pairs) \
            / sum(a["decodes"] for a, _ in pairs)
        out["prefill_share_ms"] = sum(
            1e3 * p - period for a, p in pairs if a.get("prefills")) \
            / len(pairs)
    return out


def largest_step_gap(steps: Sequence[Dict]
                     ) -> Tuple[Optional[float], Optional[str]]:
    """The longest time from one step's end to the next one's start, in
    ms, and the stepping loop's phase that covers most of it (at least
    half; ``"none"`` where none does: no request to step for, or the
    thread was not running). A run that stalled between two steps says
    so here, and where."""
    pairs = list(zip(steps, steps[1:]))
    if not pairs:
        return None, None
    a, b = max(pairs, key=lambda p: p[1]["start"] - p[0]["end"])
    lo, hi = a["end"], b["start"]
    covered = {}
    for name, t0, t1 in a["phases"] + b["phases"]:
        piece = min(t1, hi) - max(t0, lo)
        if piece > 0:
            covered[name] = covered.get(name, 0.0) + piece
    name = max(covered, key=covered.get, default="none")
    if covered.get(name, 0.0) < 0.5 * (hi - lo):
        name = "none"
    return 1e3 * (hi - lo), name


def longest_step(steps: Sequence[Dict]
                 ) -> Tuple[Optional[float], Optional[str]]:
    """The longest step, in ms, and the innermost phase it spent most of
    its time in: a pause inside a step (the wait for the device, the
    launch) is no gap between steps, and shows here."""
    if not steps:
        return None, None
    step = max(steps, key=lambda s: s["end"] - s["start"])
    spent: Dict[str, float] = {}
    for t0, t1, name in innermost_segments([step]):
        inside = min(t1, step["end"]) - max(t0, step["start"])
        if inside > 0:
            spent[name] = spent.get(name, 0.0) + inside
    return 1e3 * (step["end"] - step["start"]), max(spent, key=spent.get)


# ---- on the device trace's clock ---------------------------------------------


def clock_offset(run) -> Optional[float]:
    """Seconds to add to a ``perf_counter`` stamp to get the trace's
    time; None where the pairs do not agree."""
    from perfbench import trace_reduce

    marks = trace_reduce.spans(run.trace, "pb.engine.step")
    offsets = [m.start - r.start
               for m, r in zip(marks, run.traced_steps[:len(marks)])]
    if not offsets:
        return None
    middle = statistics.median(offsets)
    spread = 2.0 * statistics.median(abs(o - middle) for o in offsets)
    return middle if spread <= OFFSETS_AGREE_WITHIN_S else None


def innermost_segments(steps: Sequence[Dict]) -> List[Segment]:
    """The steps' phases flattened: disjoint segments in order of time,
    each named by the innermost phase open over it (``infer.step`` where
    a step is open and none of its phases is). Time no segment covers
    lies between steps. One thread steps, so phases nest."""
    spans = sorted(
        [(s["start"], -s["end"], "infer.step") for s in steps]
        + [(t0, -t1, name) for s in steps for name, t0, t1 in s["phases"]])
    out: List[Segment] = []
    stack: List[Tuple[str, float]] = []
    cursor = 0.0

    def advance(to: float) -> None:
        nonlocal cursor
        if stack and to > cursor:
            out.append((cursor, to, stack[-1][0]))
        cursor = max(cursor, to)

    for t0, neg_t1, name in spans:
        while stack and stack[-1][1] <= t0:
            advance(stack[-1][1])
            stack.pop()
        advance(t0)
        stack.append((name, -neg_t1))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return out


def idle_bucket(phase: Optional[str]) -> str:
    """Which of the four shares a piece of idle time belongs to."""
    if phase is None or phase.startswith("serve.llm."):
        return "between_steps"
    if phase == "infer.decode.wait":
        return "wait"
    if phase == "infer.decode.sample":
        return "sample"
    # infer.schedule, a prefill phase, infer.decode.launch, and the few
    # microseconds of infer.step and infer.decode that no phase covers:
    # the host on its way to the next launch.
    return "launch"


def split_idle(gaps: Sequence[Tuple[float, float]],
               segments: Sequence[Segment]) -> Dict[str, float]:
    """Seconds of ``gaps`` (sorted, disjoint, on the segments' clock) per
    bucket."""
    out = {"launch": 0.0, "wait": 0.0, "sample": 0.0, "between_steps": 0.0}
    j = 0
    for lo, hi in gaps:
        while j < len(segments) and segments[j][1] <= lo:
            j += 1
        covered = 0.0
        k = j
        while k < len(segments) and segments[k][0] < hi:
            s, e, name = segments[k]
            piece = min(e, hi) - max(s, lo)
            if piece > 0:
                out[idle_bucket(name)] += piece
                covered += piece
            k += 1
        out["between_steps"] += (hi - lo) - covered
    return out


def idle_pct(run, bucket: str) -> Optional[float]:
    """Share of the traced window in which chip 0 ran nothing and the
    host was in ``bucket``. The four shares sum to chip 0's idle share."""
    from perfbench import trace_reduce

    if run.trace is None or not run.trace.device:
        return None
    log = engine_log(run)
    offset = clock_offset(run) if log is not None else None
    if offset is None:
        return None
    window = trace_reduce.window_of(run.trace)
    busy = trace_reduce.clip(
        trace_reduce.busy_intervals(run.trace, min(run.trace.device)),
        window)
    gaps = [(s - offset, e - offset)
            for s, e in trace_reduce.subtract([window], busy)]
    seconds = split_idle(gaps, innermost_segments(log["steps"]))
    return 100.0 * seconds[bucket] / (window[1] - window[0])
