"""A serving cell: ``serve.run(LLMDeployment)`` under a closed loop of
clients or an open loop of arrivals, timed at the client.

Tokens are counted and stamped as they reach the client thread that
iterates ``handle.generate.remote_streaming``; the engine is observed
through ``probe.ProbedEngine``. Set-up is everything before the window:
weights, deployment, one warm request per program the mix can reach, the
logits check against the plain reference, and the lead-in traffic.
"""

from __future__ import annotations

import bisect
import gc
import statistics
import threading
import time
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from perfbench import probe, steplog, traffic
from perfbench.byname import load_family
from perfbench.train_cell import SEED_MASK, memory_peak_bytes

clock = time.perf_counter
JOIN_TIMEOUT_S = 120.0
# One warm request, cold: the program's compile is inside it. A request
# that outlasts this has met a dead engine (its step loop raised), and
# the run fails instead of waiting for ever.
COLD_REQUEST_TIMEOUT_S = 600.0


def consume_all(streams: Sequence["Stream"], handle, timeout_s: float
                ) -> None:
    """Run the streams to their end, each on a thread of its own."""
    never = threading.Event()
    threads = [threading.Thread(target=s.consume, args=(handle, never),
                                daemon=True) for s in streams]
    for th in threads:
        th.start()
    deadline = clock() + timeout_s
    for th in threads:
        th.join(max(0.0, deadline - clock()))
    if any(th.is_alive() for th in threads):
        raise RuntimeError(
            f"no end of a request within {timeout_s:.0f} s: the engine's "
            f"step loop has most likely died (see the errors above)")


class Stream:
    """One request as its client saw it."""

    def __init__(self, index: int, prompt: List[int], new_tokens: int,
                 due: Optional[float] = None,
                 sampling: Optional[Mapping] = None):
        self.index = index
        self.prompt = prompt
        self.new_tokens = new_tokens
        # ``traffic.request_sampling``: nothing, or how this client samples
        self.sampling = dict(sampling or {})
        self.due = due            # open loop: when it should have been sent
        self.sent: Optional[float] = None
        self.times: List[float] = []   # arrival of each token
        self.tokens: List[int] = []
        self.request_id = ""
        self.error: Optional[str] = None
        self.cancelled = False
        self.finished = False

    def consume(self, handle, stop: threading.Event, on_token=None) -> None:
        self.sent = clock()
        try:
            gen = handle.generate.remote_streaming(
                self.prompt, max_new_tokens=self.new_tokens,
                **self.sampling)
            self.request_id = gen.request_id
            for tok in gen:
                self.times.append(clock())
                self.tokens.append(int(tok))
                if on_token is not None:
                    on_token(self)
                if stop.is_set() and len(self.tokens) < self.new_tokens:
                    gen.close()
                    self.cancelled = True
                    return
            self.finished = True
        except Exception as e:  # a failed stream is a result, not a crash
            self.error = repr(e)

    def ok(self, vocab: int) -> bool:
        """``vocab`` is the rows the served model holds: the published
        vocabulary padded, any of which its output head can name."""
        return (self.error is None
                and all(0 <= t < vocab for t in self.tokens)
                and (len(self.tokens) == self.new_tokens if self.finished
                     else self.cancelled))


# ---- the window, on step boundaries --------------------------------------------


def step_clusters(times: Sequence[float], threshold: float
                  ) -> List[Tuple[float, float, int]]:
    """Token arrivals grouped into engine steps: a step's tokens reach the
    clients together, steps are further apart than ``threshold``.
    Returns (first, last, count) per group, in order."""
    out: List[List] = []
    for t in sorted(times):
        if out and t - out[-1][1] <= threshold:
            out[-1][1] = t
            out[-1][2] += 1
        else:
            out.append([t, t, 1])
    return [tuple(c) for c in out]


def aligned_window(times: Sequence[float], t_ref: float, seconds: float,
                   threshold: float) -> Tuple[float, float]:
    """Open at the end of the step that delivered the token stamped
    ``t_ref``; close at the end of the first step that ends ``seconds``
    or more later."""
    clusters = step_clusters(times, threshold)
    opened = next(c for c in clusters if c[0] <= t_ref <= c[1])
    t_open = opened[1]
    for c in clusters:
        if c[1] >= t_open + seconds:
            return t_open, c[1]
    raise RuntimeError("the traffic stopped before the window closed")


def gaps_in(streams: Sequence[Stream], window: Tuple[float, float]
            ) -> List[float]:
    """Every gap between consecutive tokens of one stream whose later
    token arrived inside the window."""
    lo, hi = window
    return [b - a for s in streams for a, b in zip(s.times, s.times[1:])
            if lo < b <= hi]


def delivered_in(streams: Sequence[Stream], window: Tuple[float, float]
                 ) -> int:
    """Tokens that arrived inside the window, all streams."""
    lo, hi = window
    # Stamps are a clock's, in order: the tokens inside are a slice.
    return sum(bisect.bisect_right(s.times, hi)
               - bisect.bisect_right(s.times, lo) for s in streams)


def tpot_mean(streams: Sequence[Stream], window: Tuple[float, float],
              clients: int) -> Optional[float]:
    """A stream's time per output token over all of the window: its
    seconds, times the ``clients`` of a closed loop (each has one request
    in flight or on its way at any time), over every token that arrived
    inside it. The rate's inverse, a stream: a turnover's queueing and
    prefill, a seat left empty, a chunk step and a stall are all in it,
    and a program that gives a stream two tokens a step halves it."""
    delivered = delivered_in(streams, window)
    return (window[1] - window[0]) * clients / delivered if delivered \
        else None


# Tokens to a run of ``tpot_p50_ms``. Long enough that a run crosses some
# tens of steps whatever a step yields (never zero, and a drafting
# program's acceptance is averaged inside it), short enough that a window
# holds about a thousand of them, so that their median is a plain step's
# period over what a step gives and leaves out the turnovers and the
# stalls: a per-layer reading, which says where ``tpot_mean_ms`` (all the
# window's time over all its tokens) got its time from, and no bound holds
# it. A constant of the metric, not of a mix: two cells read alike only
# while they cut alike.
TPOT_RUN = 64


def tpot_runs(streams: Sequence[Stream], window: Tuple[float, float]
              ) -> List[float]:
    """A stream's time per output token, over runs of ``TPOT_RUN`` tokens.

    Of each stream (one request), the tokens from its second on that
    arrived inside the window, in order, cut into consecutive runs of
    ``TPOT_RUN`` with the remainder dropped; a run's value is the time
    from the token before the run to the run's last token, over
    ``TPOT_RUN``. A run never spans two requests, and a request's first
    token (its queueing and prefill) is in none."""
    lo, hi = window
    out: List[float] = []
    for s in streams:
        start = max(1, bisect.bisect_right(s.times, lo))
        stop = bisect.bisect_right(s.times, hi)
        for first in range(start, stop - TPOT_RUN + 1, TPOT_RUN):
            out.append((s.times[first + TPOT_RUN - 1] - s.times[first - 1])
                       / TPOT_RUN)
    return out


def from_due(streams: Sequence[Stream], what: str) -> List[float]:
    """Of requests that had a due time (an open loop's), the seconds from
    when each was due to its first token (``"first_token"``) or to when
    the generator really sent it (``"sent"``): a request is timed from
    when its user wanted it, and a generator that runs late must show."""
    if what == "first_token":
        return [s.times[0] - s.due for s in streams
                if s.due is not None and s.times]
    return [s.sent - s.due for s in streams
            if s.due is not None and s.sent is not None]


def percentile(values: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---- set-up -----------------------------------------------------------------------


def deploy(family, cfg: Mapping, mix: Mapping, seed: int,
           timeout_s: float):
    from raytpu import serve

    probe.install()
    app = serve.LLMDeployment.bind(
        model=family.SERVE_MODEL, model_config=family.program_config(
            cfg, mix.get("model_overrides", ())),
        engine_options=dict(mix["engine_options"]), seed=seed & SEED_MASK)
    handle = serve.run(app, name="perfbench", route_prefix=None,
                       wait_for_ready_timeout_s=timeout_s)
    return handle, probe.ProbedEngine.instances[-1]


def shutdown() -> None:
    import raytpu
    from raytpu import serve

    try:
        serve.shutdown()
    finally:
        raytpu.shutdown()
        probe.uninstall()


def warm(handle, mix: Mapping, seed: int, vocab: int, rows: int
         ) -> List[Stream]:
    """One request at a time per entry of the mix's ``warmup``
    (``[prompt_tokens, new_tokens]``): the mix's author lists what reaches
    every program its traffic can reach, and nothing else."""
    done = []
    for i, (plen, new) in enumerate(mix["warmup"]):
        s = Stream(-1 - i, traffic.prompt_tokens(seed, i, plen, vocab,
                                                 stream=8), new,
                   sampling=traffic.request_sampling(mix, seed, -1 - i))
        consume_all([s], handle, COLD_REQUEST_TIMEOUT_S)
        if not s.ok(rows):
            raise RuntimeError(f"warm request {plen}+{new} failed: "
                               f"{s.error or s.tokens}")
        done.append(s)
    return done


def check_logits(handle, engine, family, cfg: Mapping, mix: Mapping,
                 seed: int) -> Dict:
    """Prefill and eight decoded positions of two seeded prompts, through
    the served engine's cache, against the reference's one forward pass
    over the same tokens and parameters. Logits, not tokens: the
    reference is teacher-forced over the tokens each stream received, so
    the comparison holds under a mix's own ``sampling`` too.

    The rows are the engine's by (request, position)
    (``probe.kept_rows``), whatever a step yields a sequence. Of each
    row, its largest difference over the prompt's largest reference
    logit; of those, the largest (``rel_err``, held to ``tolerance``),
    the least and the median (``rows_min``, ``rows_median``). A routed
    model's largest row is the token whose residual stream chose another
    expert than the reference's; a fault in the precision moves every
    row of the program it sits in. So the least is also taken a program:
    over each prompt's last row (``prefill_rows_min``: the whole-prompt
    program's, two rows) and over the rows after it
    (``decode_rows_min``: the decode program's, through the cache, the
    program the window times). A mix may hold ``decode_rows_min`` to
    ``check.rows_tolerance``: one minimum over both programs' rows would
    be met by a prompt's row where every decoded row is moved. The
    prompt's rows are two, too few for a least of their own to be held
    (a seed on which both took another expert is a sound run), and stay
    under ``tolerance``. Without ``rows_tolerance`` nothing new is
    judged."""
    import jax

    vocab = int(cfg["vocab_size"])
    held = family.vocab_rows_held(cfg)
    spec = mix["check"]
    positions = int(spec.get("decode_positions", 8))
    streams = [Stream(-100 - i, traffic.prompt_tokens(seed, i, n, vocab,
                                                      stream=9),
                      positions + 1,
                      sampling=traffic.request_sampling(mix, seed, -100 - i))
               for i, n in enumerate(spec["prompt_tokens"])]
    captured = engine.capture_logits()
    try:
        consume_all(streams, handle, COLD_REQUEST_TIMEOUT_S)
    finally:
        engine.stop_capture()
    if not all(s.ok(held) for s in streams):
        raise RuntimeError(f"check requests failed: "
                           f"{[s.error or len(s.tokens) for s in streams]}")
    # What the engine computed, per request and position: the prompt's
    # last row and the ``positions`` after it.
    rows = probe.kept_rows(captured, {
        s.request_id: range(len(s.prompt) - 1, len(s.prompt) + positions)
        for s in streams})
    width = max(len(s.prompt) for s in streams) + positions
    toks = np.zeros((len(streams), width), np.int32)
    for i, s in enumerate(streams):
        seq = s.prompt + s.tokens[:positions]
        toks[i, :len(seq)] = seq
    ref = np.asarray(jax.jit(
        lambda p, t: family.logits(cfg, p, t))(engine.params_given, toks))
    # Per program: a prompt's last row is the whole-prompt program's,
    # the rows after it are the decode program's.
    per_row = {"prefill": [], "decode": []}
    for i, s in enumerate(streams):
        n = len(s.prompt)
        got = np.stack([rows[s.request_id][p]
                        for p in range(n - 1, n + positions)])
        want = ref[i, n - 1:n + positions]
        if got.shape != want.shape:
            raise RuntimeError(f"check: engine gave {got.shape} logits, "
                               f"reference {want.shape}")
        moved = np.abs(got - want).max(-1) / np.abs(want).max()
        per_row["prefill"] += [float(x) for x in moved[:1]]
        per_row["decode"] += [float(x) for x in moved[1:]]
    every = np.asarray(per_row["prefill"] + per_row["decode"])
    out = {"rel_err": float(every.max()),
           "rows_min": float(every.min()),
           "rows_median": float(np.median(every)),
           "prefill_rows_min": min(per_row["prefill"]),
           "decode_rows_min": min(per_row["decode"], default=None),
           "per_row": per_row,
           "tolerance": float(spec["tolerance"]),
           "rows_tolerance": float(spec["rows_tolerance"])
           if "rows_tolerance" in spec else None}
    if out["rows_tolerance"] is not None and not per_row["decode"]:
        raise ValueError("check.rows_tolerance holds the decoded rows, and "
                         "decode_positions is 0")
    out["ok"] = bool(
        np.isfinite(every).all() and out["rel_err"] <= out["tolerance"]
        and (out["rows_tolerance"] is None
             or out["decode_rows_min"] <= out["rows_tolerance"]))
    return out


# ---- traffic --------------------------------------------------------------------


def trace_from(tracer: Optional[probe.Tracer], engine, start_at: float
               ) -> Optional[threading.Thread]:
    """Trace the first ``tracer.seconds`` after ``start_at`` from a thread
    of its own: starting and stopping the profiler blocks for a while, and
    must hold up neither the arrivals nor the window's end."""
    if tracer is None:
        return None

    def body() -> None:
        time.sleep(max(0.0, start_at - clock()))
        engine.tracer = tracer
        tracer.start()
        time.sleep(tracer.seconds)
        tracer.stop()

    th = threading.Thread(target=body, name="pb-tracer", daemon=True)
    th.start()
    return th


def run_closed(handle, engine, mix: Mapping, seed: int, vocab: int,
               seconds: float, tracer: Optional[probe.Tracer]) -> Dict:
    plan = traffic.closed_schedule(mix, seed)["clients"]
    stop = threading.Event()
    opened = threading.Event()
    streams: List[List[Stream]] = [[] for _ in plan]
    ref_client = int(mix.get("window_opens_after_client", 0))

    def client(c: int) -> None:
        for k, req in enumerate(plan[c]):
            s = Stream(req["index"], traffic.prompt_tokens(
                seed, req["index"], req["prompt_len"], vocab),
                req["new_tokens"], sampling=traffic.request_sampling(
                    mix, seed, req["index"]))
            streams[c].append(s)
            mark = (lambda _s: opened.set()) \
                if c == ref_client and k == 1 else None
            s.consume(handle, stop, mark)
            if stop.is_set() or s.error:
                return

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"pb-client-{c}", daemon=True)
               for c in range(len(plan))]
    for th in threads:
        th.start()
    if not opened.wait(JOIN_TIMEOUT_S * 3):
        stop.set()
        raise RuntimeError("the first wave never turned over")
    t_ref = streams[ref_client][1].times[0]
    tracing = trace_from(tracer, engine, t_ref)
    # Past the nominal end by a few steps, so the closing step is whole.
    step_s = statistics.median(
        b - a for s in streams[ref_client][:1]
        for a, b in zip(s.times, s.times[1:]))
    time.sleep(max(0.0, t_ref + seconds + max(1.0, 4 * step_s) - clock()))
    if tracing:
        tracing.join(JOIN_TIMEOUT_S)
    stop.set()
    for th in threads:
        th.join(JOIN_TIMEOUT_S)
    flat = [s for c in streams for s in c]
    alive = [th.name for th in threads if th.is_alive()]
    window = aligned_window([t for s in flat for t in s.times], t_ref,
                            seconds, 0.25 * step_s)
    return {"streams": flat, "window": window, "stuck_threads": alive}


def run_open(handle, engine, mix: Mapping, seed: int, vocab: int,
             seconds: float, tracer: Optional[probe.Tracer]) -> Dict:
    lead = float(mix.get("lead_in_s", 3.0))
    drain = float(mix.get("drain_s", 30.0))
    plan = traffic.open_schedule(mix, seed, lead + seconds)["arrivals"]
    stop = threading.Event()   # set only to cancel what is left at the end
    streams: List[Stream] = []
    t0 = clock() + 0.05
    threads: List[threading.Thread] = []
    window = (t0 + lead, t0 + lead + seconds)
    tracing = trace_from(tracer, engine, window[0])
    for arrival in plan:
        due = t0 + arrival["due_s"]
        if due > window[1]:
            break
        s = Stream(arrival["index"], traffic.prompt_tokens(
            seed, arrival["index"], arrival["prompt_len"], vocab),
            arrival["new_tokens"], due, traffic.request_sampling(
                mix, seed, arrival["index"]))
        streams.append(s)
        time.sleep(max(0.0, due - clock()))
        th = threading.Thread(target=s.consume, args=(handle, stop),
                              name=f"pb-arrival-{arrival['index']}",
                              daemon=True)
        th.start()
        threads.append(th)
    time.sleep(max(0.0, window[1] - clock()))
    if tracing:
        tracing.join(JOIN_TIMEOUT_S)
    # Every request due inside the window is waited for, up to one
    # request's time past its end; what is still running then has stalled.
    deadline = window[1] + drain
    for th in threads:
        th.join(max(0.0, deadline - clock()))
    stuck = [th.name for th in threads if th.is_alive()]
    stop.set()
    for th in threads:
        th.join(5.0)
    return {"streams": streams, "window": window, "stuck_threads": stuck}


# ---- one run ----------------------------------------------------------------------


def run(*, cell, cfg, mix, dirs, seed, seconds, trace_dir, trace_seconds,
        devices, process_start, marks, log) -> Dict:
    from perfbench.rundata import RunData

    import raytpu

    family = load_family(dirs, cfg)
    vocab = int(cfg["vocab_size"])        # prompts draw below this
    rows = family.vocab_rows_held(cfg)    # outputs may name any row held

    def progress(what: str) -> None:
        log("progress", {"at_s": round(clock() - process_start, 1),
                         "done": what})

    t = clock()
    raytpu.init()
    marks["fabric"] = clock() - t
    t = clock()
    handle, engine = deploy(family, cfg, mix, seed,
                            float(mix.get("deploy_timeout_s", 1200.0)))
    progress("deploy")
    try:
        # LLMDeployment makes the parameters, then builds its engine.
        marks["weights"] = engine.init_started - t
        marks["deploy"] = clock() - engine.init_started
        t = clock()
        warm(handle, mix, seed, vocab, rows)
        marks["programs_and_warm_traffic"] = clock() - t
        progress("warm traffic")
        programs = engine.steps[-1].compiles if engine.steps else 0
        t = clock()
        check = check_logits(handle, engine, family, cfg, mix, seed)
        marks["check"] = clock() - t
        progress("check")
        tracer = probe.Tracer(trace_dir, trace_seconds) if trace_dir \
            else None
        # The collector in one state at every window's opening. A full
        # collection of this process (650,000 objects) holds the
        # interpreter for 0.25 s with the chip idle, and XL's traffic
        # brings one every 42-47 s: where set-up's last one fell decided
        # whether a 40 s window held one (12 of 13 runs) or none, 0.6 % of
        # its tokens (my chip runs, PR 29). Collected here, the next is
        # due past the window's end; a program that leaves more behind a
        # step has it inside, and pays for it here as it would in service.
        gc.collect()
        t = clock()
        runner = {"closed": run_closed, "open": run_open}[mix["kind"]]
        out = runner(handle, engine, mix, seed, vocab, seconds, tracer)
        marks["lead_in_traffic"] = out["window"][0] - t
        progress("window")
        peak = max(memory_peak_bytes(d) for d in devices)
        memory_stats = dict(devices[0].memory_stats() or {})
    finally:
        shutdown()
    progress("shutdown")

    lo, hi = window = out["window"]
    streams: List[Stream] = out["streams"]
    steps = [r for r in engine.steps if lo < r.end <= hi]
    # Running totals are differenced against the last step before the
    # window, so that what its first step compiled or preempted counts.
    before = next((r for r in reversed(engine.steps) if r.end <= lo), None)
    marks["setup_s"] = lo - process_start
    log("setup", {k: round(v, 3) for k, v in marks.items()}
        | {"programs": programs, "check_rel_err": check["rel_err"],
           "check_rows_min": check["rows_min"],
           "check_rows_median": check["rows_median"],
           "check_prefill_rows_min": check["prefill_rows_min"],
           "check_decode_rows_min": check["decode_rows_min"]})

    if mix["kind"] == "open":
        judged = [s for s in streams if lo <= s.due <= hi]
        bad = [s for s in judged if not (s.finished and s.ok(rows))]
    else:
        judged = [s for s in streams if s.times and s.times[-1] > lo]
        bad = [s for s in judged if not s.ok(rows)]
    gaps = gaps_in(streams, window)
    delivered = delivered_in(streams, window)
    # No step before the window (no warm-up): every compile counts.
    compiles = steps[-1].compiles - (before.compiles if before else 0) \
        if steps else 0
    e2e = {"setup_s": lo - process_start,
           "out_tokens_per_s": delivered / (hi - lo)}
    if gaps:
        e2e["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    mean = tpot_mean(streams, window, int(mix["clients"])) \
        if mix["kind"] == "closed" else None
    if mean is not None:
        # The end-to-end metric of the cells whose rate and tail are not
        # held to the bounds the steadier cells keep (PERF.md section 2).
        e2e["tpot_mean_ms"] = 1e3 * mean
    # The same between turnovers and stalls: the median over runs of
    # ``TPOT_RUN`` tokens, read per layer beside it.
    runs = tpot_runs(streams, window)
    if runs:
        e2e["tpot_p50_ms"] = 1e3 * statistics.median(runs)
    ttft, late = (from_due(judged, what) for what in ("first_token",
                                                      "sent"))
    if ttft:
        e2e["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    turnovers = [s for s in streams
                 if s.finished and lo < s.times[-1] <= hi]

    data = RunData(
        cell=cell, cfg=cfg, mix=mix, family=family, chips=len(devices),
        peaks=None, window=window, end_to_end=e2e,
        memory_peak_bytes=int(peak), streams=streams, engine_steps=steps,
        step_before=before,
        traced_steps=[r for r in engine.steps if r.traced])
    # The program's own record of the window's steps: a run that stalled
    # between two of them, or inside one, says so, and in which phase.
    logged = steplog.window_steps(data) or []
    step_gap_ms, step_gap_phase = steplog.largest_step_gap(logged)
    longest_ms, longest_phase = steplog.longest_step(logged)

    def in_flight(at: float) -> int:
        """Requests sent and not yet finished at ``at``: a backlog that
        grows over the window means the offered rate is past the knee."""
        return sum(1 for s in streams if s.sent is not None and s.sent <= at
                   and not (s.finished and s.times[-1] <= at))
    log("window", {
        "seconds": hi - lo, "tokens": delivered, "requests": len(judged),
        "engine_steps": len(steps),
        "decode_steps": sum(1 for r in steps if r.decodes),
        "prefills": sum(r.prefills for r in steps),
        "prefill_tokens": steps[-1].prefill_tokens - before.prefill_tokens
        if steps and before else None,
        "turnovers": len(turnovers),
        "in_flight_at_open": in_flight(lo), "in_flight_at_close":
        in_flight(hi),
        "generator_late_p95_ms": 1e3 * percentile(late, 95) if late
        else None,
        "largest_gap_ms": 1e3 * max(gaps) if gaps else None,
        "largest_step_gap_ms": step_gap_ms,
        "largest_step_gap_phase": step_gap_phase,
        "longest_step_ms": longest_ms, "longest_step_phase": longest_phase,
        "median_gap_ms": 1e3 * statistics.median(gaps) if gaps else None,
        # Here too, for the cells that are not held to the mean; and what
        # the median of the runs has to agree with, from the program's
        # own step log: a plain decode step's period over the tokens a
        # sequence is given a step.
        "tpot_mean_ms": e2e.get("tpot_mean_ms"),
        "tpot_runs": len(runs), "tpot_p50_ms": e2e.get("tpot_p50_ms"),
        "tpot_quartiles_ms": [1e3 * percentile(runs, q) for q in (25, 75)]
        if runs else None, **steplog.decode_period(logged),
        # Here too, for the cells that report it per layer: a traced
        # run's is the profiler's as much as the program's.
        "p95_gap_ms": e2e.get("itl_p95_ms"),
        "first_step_index": engine.steps.index(steps[0]) if steps else None,
        "last_step_index": engine.steps.index(steps[-1]) if steps else None,
        "steps_from_open_to_first_turnover": next(
            (i for i, r in enumerate(steps) if r.prefills), None),
        "compiles_in_window": compiles, "failed": len(bad),
        "memory_stats": memory_stats,
        "stuck_threads": out["stuck_threads"]})
    return {"data": data, "xplane": tracer.xplane() if tracer else None,
            "correct": check["ok"] and not bad and compiles == 0
            and not out["stuck_threads"],
            "attempted": len(judged), "failed": len(bad),
            # Every number ``correct`` was decided from, beside its limit.
            "compared": {
                "check_rel_err": [check["rel_err"], check["tolerance"]],
                "check_decode_rows_min": [check["decode_rows_min"],
                                          check["rows_tolerance"]],
                "check_prefill_rows_min": [check["prefill_rows_min"],
                                           None],
                "failed_requests": [len(bad), 0],
                "compiles_in_window": [compiles, 0],
                "stuck_threads": [len(out["stuck_threads"]), 0]}}
