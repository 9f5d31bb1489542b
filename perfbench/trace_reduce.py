"""From a profiler trace to numbers: device busy time, kernel time, the
heaviest device operations, the longest idle gaps and what the host was
doing in them.

A trace is read once into plain tuples (``load_xplane``), or from the
small JSON form the tests keep (``Trace.from_json``), and every per-layer
reader that needs the device works on that. What the v5e's trace looks
like (seen on the chip, PR 23): one plane ``/device:TPU:<n>`` per chip
with the lines ``XLA Modules``, ``XLA Ops`` (every HLO instruction that
ran, loops enclosing their bodies, named by its full HLO text ``%name =
type op(operands)``) and ``Async XLA Ops`` (copies and collectives from
start to done); the host's threads are lines of ``/host:CPU``, and a
``jax.profiler.TraceAnnotation`` is an event on its thread's line, on
the same clock.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # start, end, in seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)")
SPAN_PREFIX = "pb."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """``device[n][line]`` and ``host[line]`` are lists of events."""

    device: Dict[int, Dict[str, List[Event]]]
    host: Dict[str, List[Event]]

    @classmethod
    def from_json(cls, obj) -> "Trace":
        def events(rows):
            return [Event(n, s, e) for n, s, e in rows]
        return cls(
            device={int(k): {ln: events(r) for ln, r in v.items()}
                    for k, v in obj["device"].items()},
            host={ln: events(r) for ln, r in obj["host"].items()})

    def to_json(self):
        def rows(evs):
            return [[e.name, e.start, e.end] for e in evs]
        return {"device": {str(k): {ln: rows(r) for ln, r in v.items()}
                           for k, v in self.device.items()},
                "host": {ln: rows(r) for ln, r in self.host.items()}}


def load_xplane(path: str) -> Trace:
    """Read an ``.xplane.pb`` with JAX alone. Of the host only the
    benchmark's own spans (``pb.*``) are kept."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[int, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = device.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE, "XLA Modules"):
                    lines[line.name] = [
                        Event(ev.name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [Event(ev.name, ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9)
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX)]
                if spans:
                    host.setdefault(line.name, []).extend(spans)
    return Trace(device=device, host=host)


# ---- interval arithmetic ---------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The parts of ``a`` that no interval of ``b`` covers."""
    out: List[Interval] = []
    cover = union(b)
    for s, e in union(a):
        for cs, ce in cover:
            if ce <= s:
                continue
            if cs >= e:
                break
            if cs > s:
                out.append((s, cs))
            s = max(s, ce)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


# ---- device operations -------------------------------------------------------


def op_head(name: str) -> str:
    """``%fusion.12 = bf16[8,1600]{...} fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """The HLO opcode of an instruction text (``custom-call``, ``fusion``,
    ``while``...); the head without its index where there is no text."""
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", name.split(" = ", 1)[-1])
    return m.group(1) if m else re.sub(r"\.\d+$", "", op_head(name))


def op_label(name: str) -> str:
    """A short stable label: the head without its index (and without the
    ``.remat<n>`` the compiler gives a repeated copy of an instruction),
    and the result's element type and shape —
    ``convert bf16[48,1600,4800]``."""
    head = re.sub(r"(\.\d+|\.remat\d*)+$", "", op_head(name))
    m = re.search(r" = \(?([a-z]+\d*\[[\d,]*\])", name)
    return f"{head} {m.group(1)}" if m else head


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its duration less the events nested inside it (a
    loop encloses its body's instructions on the same line)."""
    out: List[List] = []
    stack: List[int] = []
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(ev.end, out[stack[-1]][0].end) - ev.start
        out.append([ev, ev.seconds])
        stack.append(len(out) - 1)
    return [(ev, max(t, 0.0)) for ev, t in out]


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that enclose no other event."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    return [ev for i, ev in enumerate(ordered)
            if i + 1 == len(ordered) or ordered[i + 1].start >= ev.end]


def spans(trace: Trace, name: Optional[str] = None) -> List[Event]:
    """The benchmark's spans (all, or those called ``name``) in order of
    their start, an enclosing span before the spans inside it."""
    return sorted((e for evs in trace.host.values() for e in evs
                   if name is None or e.name == name),
                  key=lambda e: (e.start, -e.end))


def window_of(trace: Trace) -> Interval:
    """The traced window: from the start of the first benchmark span in
    the trace to the end of the last, i.e. the whole steps the profiler
    saw (``pb.engine.step``, ``pb.train.step``). A device that sits idle
    before a step's first operation or after its last is idle inside the
    window, not outside it. A trace without any span (none of the
    benchmark's own runs) has only its device events to go by."""
    marks = spans(trace)
    if marks:
        return marks[0].start, max(e.end for e in marks)
    starts = [e.start for lines in trace.device.values()
              for evs in lines.values() for e in evs]
    ends = [e.end for lines in trace.device.values()
            for evs in lines.values() for e in evs]
    if not starts:
        raise ValueError("the trace holds neither a span nor a device "
                         "event")
    return min(starts), max(ends)


def busy_intervals(trace: Trace, chip: int) -> List[Interval]:
    lines = trace.device[chip]
    evs = lines.get(OPS_LINE) or lines.get("XLA Modules") or []
    return union((e.start, e.end) for e in evs)


def busy_seconds(trace: Trace, window: Optional[Interval] = None) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    window = window or window_of(trace)
    per_chip = [measure(clip(busy_intervals(trace, c), window))
                for c in sorted(trace.device)]
    return sum(per_chip) / len(per_chip)


def kernel_events(trace: Trace, match: Callable[[Event], bool],
                  within: Optional[Sequence[Interval]] = None
                  ) -> Dict[int, List[Event]]:
    """Per chip, the ``XLA Ops`` events ``match`` accepts; with
    ``within``, only those that lie wholly inside one of its intervals."""
    def inside(e: Event) -> bool:
        return within is None or any(lo <= e.start and e.end <= hi
                                     for lo, hi in within)
    return {c: [e for e in lines.get(OPS_LINE, []) if match(e) and inside(e)]
            for c, lines in trace.device.items()}


def is_pallas(ev: Event) -> bool:
    """A Pallas kernel is a custom call named after its JAX function;
    XLA's own custom calls are called ``custom-call.<n>``."""
    return op_kind(ev.name) == "custom-call" \
        and not op_head(ev.name).startswith("custom-call")


def collective_exposed_seconds(trace: Trace,
                               window: Optional[Interval] = None) -> float:
    """Seconds, averaged over chips, in which a collective was in flight
    and no other instruction ran on that chip."""
    window = window or window_of(trace)
    per_chip = []
    for chip, lines in sorted(trace.device.items()):
        ops = lines.get(OPS_LINE, [])
        coll = [(e.start, e.end) for ln in (OPS_LINE, ASYNC_LINE)
                for e in lines.get(ln, []) if COLLECTIVE.match(
                    op_head(e.name))]
        compute = [(e.start, e.end) for e in leaves(ops)
                   if not COLLECTIVE.match(op_head(e.name))]
        per_chip.append(measure(clip(subtract(coll, compute), window)))
    return sum(per_chip) / len(per_chip)


# ---- breakdown -----------------------------------------------------------------


def heaviest_ops(trace: Trace, window: Optional[Interval] = None,
                 top: int = 10) -> List[List]:
    """The device operations that took most time by themselves inside
    the window, summed by label over all chips and divided by the number
    of chips."""
    lo, hi = window or window_of(trace)
    total: Dict[str, float] = {}
    for lines in trace.device.values():
        for ev, t in self_times([e for e in lines.get(OPS_LINE, [])
                                 if lo <= e.start and e.end <= hi]):
            label = op_label(ev.name)
            total[label] = total.get(label, 0.0) + t
    n = max(1, len(trace.device))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[label, t / n] for label, t in ranked]


def idle_gaps(trace: Trace, window: Optional[Interval] = None,
              top: int = 10) -> List[List]:
    """Idle time of the first chip inside the window by what the host was
    doing: per benchmark span open when the gap began, the total; then
    the longest single gaps."""
    window = window or window_of(trace)
    chip = min(trace.device)
    busy = clip(busy_intervals(trace, chip), window)
    gaps = subtract([window], busy)
    marks = spans(trace)

    def label(t: float) -> str:
        inner = None
        for sp in marks:
            if sp.start > t:
                break
            if sp.end > t:
                inner = sp
        return f"inside {inner.name}" if inner else "no benchmark span open"

    labelled = [(label(s), e - s) for s, e in gaps]
    totals: Dict[str, float] = {}
    for name, sec in labelled:
        totals[name] = totals.get(name, 0.0) + sec
    out = [[f"total: {name}", sec]
           for name, sec in sorted(totals.items(), key=lambda kv: -kv[1])]
    longest = sorted(labelled, key=lambda kv: -kv[1])
    out += [[f"gap: {name}", sec] for name, sec in longest[:top - len(out)]]
    return out[:top]


if __name__ == "__main__":  # python perfbench/trace_reduce.py x.xplane.pb
    import sys

    tr = load_xplane(sys.argv[1])
    win = window_of(tr)
    print(json.dumps({
        "window_s": win[1] - win[0], "busy_s": busy_seconds(tr),
        "device_ops": heaviest_ops(tr), "idle_gaps": idle_gaps(tr)},
        indent=1))
