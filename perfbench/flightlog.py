"""What stopped the host, and which decode the chip was on: the readers
of the step log's ``pauses`` and of the ordinals a step record carries
since one decode is in flight (PR 57).

Two things are read here that ``steplog.py`` does not know.

**Host pauses.** The program keeps what stopped its interpreter on the
step log's clock (``raytpu.util.tracing.host_pauses``: ``[kind, t0, t1,
attrs]``, today ``host.gc``, a collection of the cycle collector with its
``generation``). They are asked of the program's module and not of the
engine's ``step_log()``, which the probe rebuilds from its own kept
records with the two keys it knows. A run needs no trace for them
(``gc_seconds``, ``gc_full_collections``); with one, the entries go onto
the trace's clock by ``steplog.clock_offset`` and are laid over chip 0's
idle gaps (``covered_seconds``). A step's record carries its thread's CPU
time (``cpu_s``, and ``wait_cpu_s`` inside ``infer.decode.wait``):
``offcpu_seconds`` is the wall time outside the wait that the thread did
not run.

**Ordinals.** A record's ``dispatched`` is the ordinal of the decode its
launch dispatched and ``fetched`` that of the decode its wait blocked on.
The device's side is the trace's ``XLA Modules`` line: one event an
execution of the decode program (``jit__decode(<fingerprint>)``), in the
order of the ordinals, followed by the decode's own sampler (or, of a
model that drafts, its accept and draft programs). ``pair`` lays the two
side by side: the ``i``-th decode event that began after the first traced
record's launch is decode ``n0 + i``, ``n0`` that record's ``dispatched``;
and it checks what the pairing implies, that each event starts after its
record's launch began and that the decode's last program ends before its
``fetched`` record's wait ended. Where more than a tenth of the pairs
fail, nothing is read: a pairing that has slipped by one fails in most.

A program whose records lack the fields (the parent of the PR that
brought them) gives ``None`` to every reader here.

    python3 perfbench/flightlog.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs the cell once as ``run.py`` does and adds to the result line, without
a trace, under ``host`` the metrics here that need none (``run.py`` reads
per-layer metrics in traced runs alone); with one, under ``long_gaps``
every gap of chip 0 over 10 ms with what covered it (``named_gaps``);
and under ``gc`` the window's kept collections by generation.
"""

from __future__ import annotations

import re
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

Interval = Tuple[float, float]

LONG_GAP_S = 10e-3  # what one step in flight does not ride out
OFFCPU_BLOCK = 64   # steps to a reading of ``step_offcpu_ms_p50``
PAIRS_MAY_FAIL = 0.1
# The offset between the clocks is a median of pairs that agree within
# ``steplog.OFFSETS_AGREE_WITHIN_S``: an order that holds on one clock is
# held on the other to half of it.
CLOCK_SLACK_S = 100e-6
MODULES_LINE = "XLA Modules"
MODULE = re.compile(r"^jit_(_[A-Za-z_]+?)(?:\(|\.|$)")
DECODE = "_decode"
# What a decode step dispatches behind its decode program: the sampler,
# or a drafting model's accept and draft programs.
FOLLOWS_A_DECODE = ("_sample", "_accept", "_draft")
WAIT, LAUNCH = "infer.decode.wait", "infer.decode.launch"
HOST_ONLY = ("gc_pause_pct", "gc_full_collections", "step_offcpu_ms_p50",
             "decode_carried_pct")


# ---- host pauses -------------------------------------------------------------


def host_pauses(run) -> Optional[List[list]]:
    """The program's pauses since the process began, oldest first; None
    where the program keeps none, or where its ring has dropped entries
    the window may have held."""
    from raytpu.util import tracing

    fetch = getattr(tracing, "host_pauses", None)
    if fetch is None:
        return None
    pauses = fetch(0.0)
    if len(pauses) >= getattr(tracing, "PAUSE_RING", len(pauses) + 1) \
            and pauses[0][1] > run.window[0]:
        return None
    return pauses


def gc_entries(pauses: Sequence[list]) -> List[list]:
    return [p for p in pauses if p[0] == "host.gc"]


def covered_seconds(gaps: Sequence[Interval], cover: Sequence[Interval]
                    ) -> float:
    """Seconds of ``gaps`` (disjoint) that an interval of ``cover`` lies
    over."""
    from perfbench import trace_reduce

    return sum(trace_reduce.measure(trace_reduce.clip(cover, gap))
               for gap in gaps)


def gc_seconds(pauses: Sequence[list], window: Interval) -> float:
    return covered_seconds(
        [window], [(t0, t1) for _, t0, t1, _ in gc_entries(pauses)])


def gc_full_collections(pauses: Sequence[list], window: Interval) -> int:
    """Generation-2 collections that ended inside the window."""
    lo, hi = window
    return sum(1 for _, _, t1, attrs in gc_entries(pauses)
               if attrs.get("generation") == 2 and lo < t1 <= hi)


def gc_pause_pct(run) -> Optional[float]:
    pauses = host_pauses(run)
    if pauses is None:
        return None
    lo, hi = run.window
    return 100.0 * gc_seconds(pauses, (lo, hi)) / (hi - lo)


def offcpu_seconds(step: Dict) -> Optional[float]:
    """Wall time of the step outside its wait less the CPU time its
    thread had there: the thread was blocked, or had lost the core or
    the interpreter. None for a record without ``cpu_s``. Not cut at 0:
    where the thread's CPU clock ticks (10 ms a tick on the chip's host,
    my chip runs, PR 57) a step reads 0 or a whole tick, and only sums
    over many steps mean anything."""
    from perfbench import steplog

    if "cpu_s" not in step:
        return None
    waited = steplog.phase_seconds(step, (WAIT,)) or 0.0
    wall = step["end"] - step["start"] - waited
    return wall - (step["cpu_s"] - step.get("wait_cpu_s", 0.0))


def plain_decode(step: Dict) -> bool:
    """A step that dispatched a decode and prefilled nothing."""
    return bool(step.get("decodes")) and not step.get("prefills")


def step_offcpu_ms_p50(steps: Sequence[Dict], block: int = OFFCPU_BLOCK
                       ) -> Optional[float]:
    """Median, over blocks of ``block`` consecutive plain decode steps,
    of the block's off-CPU time a step (the last, short block left out
    where there is a whole one). A block and not a step, because a
    thread's CPU clock may tick far coarser than a step: a block of 64
    steps resolves 0.16 ms a step at 10 ms a tick, and the median over
    blocks leaves out the block a pause fell into, as a median over
    steps would the step."""
    lost = [x for x in (offcpu_seconds(s) for s in steps if plain_decode(s))
            if x is not None]
    if not lost:
        return None
    blocks = [lost[i:i + block] for i in range(0, len(lost), block)]
    if len(blocks) > 1 and len(blocks[-1]) < block:
        blocks.pop()
    return 1e3 * max(0.0, statistics.median(
        sum(b) / len(b) for b in blocks))


def decode_carried_pct(steps: Sequence[Dict]) -> Optional[float]:
    """Of the records whose decode went out ahead, the share whose
    tokens went through the hand-over program."""
    carried = [s["carried"] for s in steps
               if s.get("ahead") and "carried" in s]
    return 100.0 * sum(carried) / len(carried) if carried else None


# ---- chip 0's idle gaps ------------------------------------------------------


def idle_gaps(trace) -> Tuple[Interval, List[Interval]]:
    """The traced window and chip 0's idle gaps inside it, on the trace's
    clock, as ``steplog.idle_pct`` takes them."""
    from perfbench import trace_reduce

    window = trace_reduce.window_of(trace)
    busy = trace_reduce.clip(
        trace_reduce.busy_intervals(trace, min(trace.device)), window)
    return window, trace_reduce.subtract([window], busy)


def long_gaps(gaps: Sequence[Interval], over: float = LONG_GAP_S
              ) -> List[Interval]:
    return [(s, e) for s, e in gaps if e - s > over]


def on_trace_clock(pauses: Sequence[list], offset: float) -> List[Interval]:
    return [(t0 + offset, t1 + offset) for _, t0, t1, _ in pauses]


def idle_long_gaps_pct(run) -> Optional[float]:
    if run.trace is None or not run.trace.device:
        return None
    window, gaps = idle_gaps(run.trace)
    return 100.0 * sum(e - s for s, e in long_gaps(gaps)) \
        / (window[1] - window[0])


def idle_in_gc_pct(run) -> Optional[float]:
    from perfbench import steplog

    if run.trace is None or not run.trace.device:
        return None
    pauses = host_pauses(run)
    offset = steplog.clock_offset(run) if pauses is not None else None
    if offset is None:
        return None
    window, gaps = idle_gaps(run.trace)
    return 100.0 * covered_seconds(
        gaps, on_trace_clock(gc_entries(pauses), offset)) \
        / (window[1] - window[0])


def named_gaps(run, over: float = LONG_GAP_S) -> Optional[List[Dict]]:
    """Every gap of chip 0 longer than ``over`` in the traced window with
    what covered it, for ``PERF.md``: the ``host.gc`` entries over it
    (generation, seconds inside the gap), the steps it crossed with a
    compile, a prefill, their ``cpu_s`` and wall time. Seconds are the
    step log's."""
    from perfbench import steplog

    if run.trace is None or not run.trace.device:
        return None
    log = steplog.engine_log(run)
    pauses = host_pauses(run)
    offset = steplog.clock_offset(run) if log is not None else None
    if offset is None or pauses is None:
        return None
    _, gaps = idle_gaps(run.trace)
    out = []
    for s, e in long_gaps(gaps, over):
        lo, hi = s - offset, e - offset
        over_it = [st for st in log["steps"]
                   if st["start"] < hi and st["end"] > lo]
        out.append({
            "at_s": lo - run.window[0], "seconds": hi - lo,
            "gc": [[attrs.get("generation"),
                    covered_seconds([(lo, hi)], [(t0, t1)]),
                    bool(attrs.get("stepping"))]
                   for _, t0, t1, attrs in gc_entries(pauses)
                   if t0 < hi and t1 > lo],
            "steps": [{
                "wall_s": st["end"] - st["start"],
                "cpu_s": st.get("cpu_s"),
                "wait_cpu_s": st.get("wait_cpu_s"),
                "offcpu_s": offcpu_seconds(st),
                "compiled": st.get("compiled"),
                "prefills": len(st.get("prefills", ())),
                "dispatched": st.get("dispatched"),
                "fetched": st.get("fetched"),
                "phase": steplog.longest_step([st])[1]} for st in over_it]})
    return out


# ---- the decodes on the device, by their ordinals ------------------------------


class Decode(NamedTuple):
    """Decode ``n`` on both clocks brought to the trace's: its program's
    event (``start``), the start of the next decode's (None for the last),
    the end of the last program the step dispatched behind it, and from
    the records the begin and end of the launch that dispatched it and
    the end of the wait that fetched it (None where no record of the log
    fetched it); ``plain`` where the step that dispatched it prefilled
    nothing (a prefill's first-token fetch empties the chip before the
    decode behind it), ``next_plain`` where the step that dispatched the
    *next* decode did not, so that nothing but this decode's own
    programs ran between the two starts."""

    n: int
    start: float
    next_start: Optional[float]
    last_end: float
    launch_began: float
    launch_ended: float
    wait_ended: Optional[float]
    plain: bool
    next_plain: bool


def module_name(event_name: str) -> Optional[str]:
    """``jit__decode(6103778470494593253)`` -> ``_decode``."""
    m = MODULE.match(event_name)
    return m.group(1) if m else None


def phase_of(step: Dict, name: str) -> Optional[Tuple[float, float]]:
    """The step's last phase called ``name`` (a drained step that then
    decodes has two waits: the second is its decode's)."""
    found = [(t0, t1) for phase, t0, t1 in step["phases"] if phase == name]
    return found[-1] if found else None


def pair(steps: Sequence[Dict], modules: Sequence, offset: float,
         traced_from: float, traced_steps: Optional[int] = None
         ) -> Optional[List[Decode]]:
    """The decodes of a traced window, each module event with the records
    of its ordinal. ``steps`` is the step log, ``modules`` chip 0's
    ``XLA Modules`` events, ``offset`` what a stamp of the log takes to
    the trace's clock, ``traced_from`` the log's time from which steps
    were traced (and ``traced_steps`` how many were, where known). None
    where the records carry no ordinals, where the trace holds no decode
    of a traced record, or where more than ``PAIRS_MAY_FAIL`` of the
    pairs break the order the pairing implies."""
    steps = [s for s in steps if "dispatched" in s]
    traced = [s for s in steps if s["start"] >= traced_from]
    if traced_steps is not None:
        traced = traced[:traced_steps]
    first = next((s for s in traced if s["dispatched"]
                  and phase_of(s, LAUNCH)), None)
    if first is None:
        return None
    by_dispatched = {s["dispatched"]: s for s in steps if s["dispatched"]}
    by_fetched = {s["fetched"]: s for s in steps if s.get("fetched")}
    last_traced = max(s["dispatched"] for s in traced)
    begins = phase_of(first, LAUNCH)[0] + offset
    events = sorted((e for e in modules if e.start >= begins - CLOCK_SLACK_S),
                    key=lambda e: e.start)
    decodes: List[Decode] = []
    failed = 0
    n = first["dispatched"]
    for i, event in enumerate(events):
        if module_name(event.name) != DECODE:
            continue
        record = by_dispatched.get(n)
        if record is None or n > last_traced:
            break
        last_end, j = event.end, i + 1
        while j < len(events) \
                and module_name(events[j].name) in FOLLOWS_A_DECODE:
            last_end, j = events[j].end, j + 1
        launch = phase_of(record, LAUNCH)
        fetch = by_fetched.get(n)
        wait = phase_of(fetch, WAIT) if fetch is not None else None
        after = by_dispatched.get(n + 1)
        if decodes:
            decodes[-1] = decodes[-1]._replace(next_start=event.start)
        one = Decode(
            n, event.start, None, last_end, launch[0] + offset,
            launch[1] + offset, wait[1] + offset if wait else None,
            plain_decode(record),
            after is not None and plain_decode(after))
        if one.start < one.launch_began - CLOCK_SLACK_S or (
                one.wait_ended is not None
                and one.last_end > one.wait_ended + CLOCK_SLACK_S):
            failed += 1
        decodes.append(one)
        n += 1
    if not decodes or failed > PAIRS_MAY_FAIL * len(decodes):
        return None
    return decodes


def paired(run) -> Optional[List[Decode]]:
    """``pair`` over a run's log and trace."""
    from perfbench import steplog

    if run.trace is None or not run.trace.device or not run.traced_steps:
        return None
    log = steplog.engine_log(run)
    offset = steplog.clock_offset(run) if log is not None else None
    if offset is None:
        return None
    modules = run.trace.device[min(run.trace.device)].get(MODULES_LINE, [])
    return pair(log["steps"], modules, offset, run.traced_steps[0].start,
                len(run.traced_steps))


def device_step_ms_p50(decodes: Sequence[Decode]) -> Optional[float]:
    """Median, over the plain decodes, of the time from a decode
    program's start to the next one's: the device's own step."""
    periods = [d.next_start - d.start for d in decodes
               if d.next_plain and d.next_start is not None]
    return 1e3 * statistics.median(periods) if periods else None


def host_lead_ms_p50(decodes: Sequence[Decode]) -> Optional[float]:
    """Median, over the decodes a plain step dispatched, of how long a
    dispatched decode waited for the chip: its program's start less the
    end of the launch that dispatched it, 0 where it began before that
    launch ended."""
    leads = [max(0.0, d.start - d.launch_ended) for d in decodes
             if d.plain]
    return 1e3 * statistics.median(leads) if leads else None


def fetch_lag_ms_p50(decodes: Sequence[Decode]) -> Optional[float]:
    """Median of the end of the wait that fetched a decode less the end
    of the last program of that decode: the copy back and the thread's
    way to the interpreter."""
    lags = [max(0.0, d.wait_ended - d.last_end) for d in decodes
            if d.wait_ended is not None]
    return 1e3 * statistics.median(lags) if lags else None


def gc_by_generation(run) -> Optional[Dict[str, Dict]]:
    """The window's kept ``host.gc`` entries by generation: how many
    ended in it, their seconds inside it, the longest, and how many the
    thread that steps made itself."""
    pauses = host_pauses(run)
    if pauses is None:
        return None
    lo, hi = run.window
    out: Dict[str, Dict] = {}
    for _, t0, t1, attrs in gc_entries(pauses):
        if not lo < t1 <= hi:
            continue
        row = out.setdefault(str(attrs.get("generation")), {
            "count": 0, "seconds": 0.0, "longest_s": 0.0, "stepping": 0})
        row["count"] += 1
        row["seconds"] += covered_seconds([(lo, hi)], [(t0, t1)])
        row["longest_s"] = max(row["longest_s"], t1 - t0)
        row["stepping"] += bool(attrs.get("stepping"))
    return out


# ---- an untraced run with the host's metrics ---------------------------------


def host_metrics(run) -> Dict[str, float]:
    """The per-layer metrics of this module that need no trace, by the
    names ``BENCHMARK.json`` gives them (less a ``.long``)."""
    from perfbench import byname, run as runner

    out = {}
    for name in HOST_ONLY:
        value = byname.load_reader([runner.HERE], name).read(run)
        if value is not None:
            out[name] = float(value)
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import json
    import os
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    if root not in sys.path:
        sys.path.insert(0, root)
    from perfbench import run as runner, serve_cell

    ap = argparse.ArgumentParser(description="one run of a serving cell, "
                                 "the host's metrics or the long gaps added")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    kept = []
    plain = serve_cell.run

    def keeping(**kwargs):
        kept.append(plain(**kwargs))
        return kept[-1]

    serve_cell.run = keeping
    try:
        result = runner.run_cell(benchmark, [here], args.workload,
                                 args.seed, args.seconds, bool(args.trace))
    finally:
        serve_cell.run = plain
    if kept and args.trace:
        data = kept[-1]["data"]
        result["long_gaps"] = named_gaps(data)
        # When the profiler was started and stopped, in the window's
        # seconds as the gaps' ``at_s`` is: a stop holds the interpreter.
        from perfbench import probe
        tracer = probe.ProbedEngine.instances[-1].tracer
        result["tracer"] = {
            "started_at_s": tracer.started_at - data.window[0],
            "stop_began_at_s": tracer.stopped_at - data.window[0]}
        # What ``device_step_ms_p50`` is held to: the same traced
        # records' own start-to-start period.
        from perfbench import steplog
        log = steplog.engine_log(data)
        if log is not None and data.traced_steps:
            traced = [s for s in log["steps"]
                      if s["start"] >= data.traced_steps[0].start]
            result["traced_records"] = steplog.decode_period(
                traced[:len(data.traced_steps)])
    elif kept:
        result["host"] = host_metrics(kept[-1]["data"])
    if kept:
        result["gc"] = gc_by_generation(kept[-1]["data"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
