"""The check's rows by (request, position): ``probe.kept_rows`` over
synthetic captures (a step of one row a sequence, a step of two of which
one or two are kept, a sequence that ends on its first row; a missing and
a doubled position raise); the old bookkeeping, by step count, over the
same capture; a mix's ``sampling`` in every request's call and nothing
new in the call without it; ``check.rows_tolerance`` judged where a mix
has it and nowhere else. Then on the CPU through ``run.run_cell``: a
test-only engine whose decode step yields one or two tokens a sequence
(``two_token_engine.py``) reads ``correct`` true under sampling and
false when it keeps a row too many; a routed tiny model whose router is
not renormalised passes the largest row's limit and fails the least."""

import json
import math
import os
import threading

import numpy as np
import pytest

from perfbench import probe, run, serve_cell, traffic
from perfbench.serve_cell import Stream
from perfbench.tests.test_rehearsal import (REHEARSAL, SEED, benchmark_with,
                                            names)
from perfbench.tests.two_token_engine import TwoTokenEngine

HERE = os.path.dirname(os.path.abspath(__file__))
TWOTOKEN = os.path.join(HERE, "twotoken")
ROWS = os.path.join(HERE, "rows")
MELLUM = os.path.join(HERE, "mellum")
V = 7
SERVING_MIXES = ("batch-decode", "moe-batch-decode", "latent-decode")
# Their clients sample: K-EXAONE's since PR 42, Mellum's since PR 46 (so
# that the seed does not decide how far the routing collapses).
SAMPLED_MIXES = ("long-decode", "selfdraft-decode")


def committed_mix(name):
    with open(os.path.join(run.HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


# ---- the bookkeeping over synthetic captures -------------------------------------


def row(request, position, draft=0):
    """A row that names what it belongs to."""
    return np.full(V, 100.0 * request + position + 0.5 * draft, np.float32)


def prefill_of(rid, request, plen, bucket=8):
    logits = np.stack([row(request, p) if p < plen else np.zeros(V, "f")
                       for p in range(bucket)])
    return [("prefill_id", rid), ("prefill", logits),
            ("advanced", [(rid, 0, plen)])]


def decode_of(steps, width=None):
    """``steps``: ``(rid, request, before, after)`` a sequence. ``width``
    None: ``[bucket, V]``; else ``[bucket, width, V]`` whose rows past
    what a sequence kept are a rejected draft's."""
    bucket = len(steps) + 1  # a padding row
    shape = (bucket, V) if width is None else (bucket, width, V)
    logits = np.zeros(shape, np.float32)
    for i, (_, request, before, after) in enumerate(steps):
        for j in range(width or 1):
            value = row(request, before + j, draft=j >= after - before)
            if width is None:
                logits[i] = value
            else:
                logits[i, j] = value
    return [("decode_ids", [s[0] for s in steps]), ("decode", logits),
            ("advanced", [(rid, b, a) for rid, _, b, a in steps])]


def old_rows(captured, plen, positions):
    """The bookkeeping as it stood before (request, position): rows in
    the order the steps appended them (``serve_cell.check_logits`` at
    6364696, word for word)."""
    rows = {rid: [] for rid in plen}
    pending_prefill = None
    decode_ids = []
    for kind, value, *_ in captured:
        if kind == "prefill_id":
            pending_prefill = value
        elif kind == "prefill":
            rows[pending_prefill].append(
                np.asarray(value[plen[pending_prefill] - 1], np.float32))
        elif kind == "decode_ids":
            decode_ids = value
        elif kind == "decode":
            got = np.asarray(value, np.float32)
            for i, rid in enumerate(decode_ids):
                if rid in rows and len(rows[rid]) <= positions:
                    rows[rid].append(got[i])
    return rows


def expect(rows, request, positions):
    assert sorted(rows) == list(positions)
    for p in positions:
        np.testing.assert_array_equal(rows[p], row(request, p))


def test_one_row_a_sequence_reads_as_the_step_count_did():
    # Two requests decoding together, beside one that was there before.
    captured = prefill_of("a", 1, 5) + prefill_of("b", 2, 3)
    for k in range(4):
        captured += decode_of([("x", 9, 4 + k, 5 + k), ("a", 1, 5 + k, 6 + k),
                               ("b", 2, 3 + k, 4 + k)])
    wanted = {"a": range(4, 9), "b": range(2, 7)}
    rows = probe.kept_rows(captured, wanted)
    expect(rows["a"], 1, range(4, 9))
    expect(rows["b"], 2, range(2, 7))
    old = old_rows(captured, {"a": 5, "b": 3}, 4)
    for rid in wanted:
        np.testing.assert_array_equal(
            np.stack(old[rid]), np.stack([rows[rid][p] for p in wanted[rid]]))


def test_two_rows_a_step_keep_what_each_sequence_advanced_by():
    captured = prefill_of("a", 1, 5) + prefill_of("b", 2, 3)
    # a advances by 2, 1, 2; b by 1, 2, 2: the same positions either way.
    captured += decode_of([("a", 1, 5, 7), ("b", 2, 3, 4)], width=2)
    captured += decode_of([("a", 1, 7, 8), ("b", 2, 4, 6)], width=2)
    captured += decode_of([("a", 1, 8, 10), ("b", 2, 6, 8)], width=2)
    rows = probe.kept_rows(captured, {"a": range(4, 10), "b": range(2, 8)})
    expect(rows["a"], 1, range(4, 10))   # never a rejected draft's row
    expect(rows["b"], 2, range(2, 8))


def test_a_sequence_that_ends_on_its_first_row_keeps_one():
    captured = prefill_of("a", 1, 5) \
        + decode_of([("a", 1, 5, 7)], width=2) \
        + decode_of([("a", 1, 7, 8)], width=2)   # its last token: one row
    expect(probe.kept_rows(captured, {"a": range(4, 8)})["a"], 1,
           range(4, 8))


def test_rows_past_what_is_wanted_are_passed_over():
    captured = prefill_of("a", 1, 5) + decode_of([("a", 1, 5, 6)]) \
        + decode_of([("a", 1, 6, 7)])
    expect(probe.kept_rows(captured, {"a": range(4, 6)})["a"], 1,
           range(4, 6))


@pytest.mark.parametrize("fault,captured,match", [
    ("missing", prefill_of("a", 1, 5) + decode_of([("a", 1, 6, 7)]),
     "no row for positions"),
    ("missing-first", decode_of([("a", 1, 5, 6)]), "no row for positions"),
    ("doubled", prefill_of("a", 1, 5) + decode_of([("a", 1, 5, 6)])
     + decode_of([("a", 1, 5, 7)], width=2), "two rows for position 5"),
    ("advanced-past-the-step", prefill_of("a", 1, 5)
     + decode_of([("a", 1, 5, 7)]), "advanced by 2 in a step of 1 row"),
    ("two-programs-in-one-call", prefill_of("a", 1, 5)
     + decode_of([("a", 1, 5, 6)])[:2] + decode_of([("a", 1, 5, 6)]),
     "two decode programs ran in one call"),
    ("no-program-in-a-decode", prefill_of("a", 1, 5)
     + [("decode_ids", ["a", "b"]),
        ("advanced", [("a", 5, 6), ("b", 3, 4)])], "no captured program"),
])
def test_a_capture_that_breaks_the_contract_raises(fault, captured, match):
    with pytest.raises(RuntimeError, match=match):
        probe.kept_rows(captured, {"a": range(4, 7)})


def test_a_chunk_is_not_captured_and_its_note_is_passed_over():
    captured = [("prefill_id", "a"), ("advanced", [("a", 0, 4)])] \
        + [("prefill_id", "b")] + prefill_of("b", 2, 3)[1:]
    expect(probe.kept_rows(captured, {"b": range(2, 3)})["b"], 2, [2])


# ---- what a request asks for -----------------------------------------------------------


class Handle:
    """``handle.generate.remote_streaming`` as the streams call it."""

    def __init__(self, tokens=(3, 4)):
        self.generate = self
        self.tokens = tokens
        self.calls = []
        self.lock = threading.Lock()

    def remote_streaming(self, *args, **kwargs):
        with self.lock:   # the check's streams call from two threads
            self.calls.append((args, kwargs))
            handle, n = self, len(self.calls)

        class Gen:
            request_id = f"r{n}"

            def __iter__(self):
                return iter(handle.tokens)

            def close(self):
                pass
        return Gen()


def consume(stream):
    handle = Handle()
    stream.consume(handle, threading.Event())
    assert stream.error is None, stream.error
    return handle.calls


@pytest.mark.parametrize("name", SERVING_MIXES)
def test_a_committed_mix_sends_todays_call(name):
    mix = committed_mix(name)
    assert "sampling" not in mix
    for index in (-100, -1, 0, 5):
        assert traffic.request_sampling(mix, SEED, index) == {}
        s = Stream(index, [1, 2, 3], 2,
                   sampling=traffic.request_sampling(mix, SEED, index))
        assert consume(s) == [(([1, 2, 3],), {"max_new_tokens": 2})]


@pytest.mark.parametrize("name", SAMPLED_MIXES)
def test_a_committed_mix_that_samples_asks_it_of_every_request(name):
    mix = committed_mix(name)
    assert mix["sampling"] == {"temperature": 1.0} and mix["sampling_why"]
    asked = [traffic.request_sampling(mix, SEED, i)
             for i in (-100, -1, 0, 5)]
    assert all(a["temperature"] == 1.0 and a["top_k"] == 0 for a in asked)
    assert len({a["seed"] for a in asked}) == 4
    s = Stream(5, [1, 2, 3], 2, sampling=asked[3])
    assert consume(s) == [(([1, 2, 3],), {"max_new_tokens": 2, **asked[3]})]


def test_a_mix_with_sampling_says_how_every_request_samples():
    mix = {"sampling": {"temperature": 0.7, "top_k": 40}}
    asked = [traffic.request_sampling(mix, SEED, i)
             for i in (-101, -100, -2, -1, 0, 1, 2)]
    assert all(a["temperature"] == 0.7 and a["top_k"] == 40 for a in asked)
    seeds = [a["seed"] for a in asked]
    assert len(set(seeds)) == len(seeds)       # a stream of its own each
    assert all(0 <= x < 2**31 for x in seeds)
    # From the run's seed and the index alone: the same again, another
    # for another run.
    assert asked[4] == traffic.request_sampling(mix, SEED, 0)
    assert asked[4]["seed"] != traffic.request_sampling(mix, SEED + 1,
                                                        0)["seed"]
    s = Stream(0, [1, 2, 3], 2, sampling=asked[4])
    assert consume(s) == [(([1, 2, 3],), {
        "max_new_tokens": 2, "temperature": 0.7, "top_k": 40,
        "seed": asked[4]["seed"]})]
    with pytest.raises(ValueError, match="no field"):
        traffic.request_sampling({"sampling": {"top_p": 0.9}}, SEED, 0)


def test_first_16_requests_of_batch_decode_are_what_they_were():
    """``requests_per_client`` went from 16 to 24 (a faster step must
    not end the traffic before the window closes): every client's first
    16 requests are the same, in the same order."""
    mix = committed_mix("batch-decode")
    assert mix["requests_per_client"] == 24
    now = traffic.closed_schedule(mix, SEED)["clients"]
    was = traffic.closed_schedule(dict(mix, requests_per_client=16),
                                  SEED)["clients"]
    assert [c[:16] for c in now] == was
    assert all(len(c) == 24 for c in now)
    # Client 0 alone outlasts a window at a step a third shorter.
    assert sum(r["new_tokens"] for r in now[0]) == 32 + 23 * 256


# ---- rows_tolerance -------------------------------------------------------------------


class CheckedEngine:
    """An engine for ``check_logits`` alone: what it captures is made from
    the reference's rows, each moved by ``moved[k]`` of the largest
    logit."""
    params_given = None

    def __init__(self, handle, moved, rows=3):
        self.handle, self.moved, self.rows = handle, moved, rows
        self.captured = []

    def capture_logits(self):
        return self.captured

    def stop_capture(self):
        streams = [(f"r{i + 1}", a[0]) for i, (a, _) in
                   enumerate(self.handle.calls)]
        k = 0
        for rid, prompt in streams:
            n = len(prompt)
            request = {4: 1, 6: 2}[n]   # the reference's batch index + 1
            rows = [reference_row(request, p)
                    for p in range(n - 1, n - 1 + self.rows)]
            for j, r in enumerate(rows):
                r[0] += self.moved[k] * SCALE
                k += 1
                self.captured += [
                    ("decode_ids", [rid]), ("decode", r[None]),
                    ("advanced", [(rid, n - 1 + j, n + j)])]


SCALE = 50.0


def reference_row(request, position):
    r = np.zeros(V, np.float32)
    r[1] = SCALE if position % 2 else -SCALE   # the prompt's largest logit
    r[2] = request + position / 10.0
    return r


class Family:
    @staticmethod
    def vocab_rows_held(cfg):
        return V

    @staticmethod
    def logits(cfg, params, toks):
        import jax.numpy as jnp

        return jnp.stack([jnp.stack([
            jnp.asarray(reference_row(i + 1, p))
            for p in range(toks.shape[1])]) for i in range(toks.shape[0])])


@pytest.mark.parametrize("rows_tolerance,moved,ok", [
    (None, [0.2, 0.25, 0.3, 0.2, 0.22, 0.28], True),    # absent: not judged
    (0.04, [0.2, 0.25, 0.3, 0.2, 0.22, 0.28], False),   # every row moved
    (0.04, [0.01, 0.3, 0.02, 0.01, 0.02, 0.015], True),  # one row swapped
    (0.04, [0.01, 0.5, 0.02, 0.01, 0.02, 0.015], False),  # over the largest
    # Rows 0 and 3 are the prompts' own (the whole-prompt program's), the
    # others the decode program's. Every decoded row moved, the prompts'
    # right: the least over all six reads 0.01, and it is not correct.
    (0.04, [0.01, 0.2, 0.25, 0.012, 0.22, 0.28], False),
    # Both prompts' rows swapped an expert, the decoded rows are right.
    (0.04, [0.3, 0.01, 0.02, 0.2, 0.02, 0.015], True),
    (0.04, [0.01, math.nan, 0.02, 0.01, 0.02, 0.015], False),
])
def test_rows_tolerance_is_judged_where_a_mix_has_it(rows_tolerance, moved,
                                                     ok):
    spec = {"prompt_tokens": [4, 6], "decode_positions": 2,
            "tolerance": 0.45}
    if rows_tolerance is not None:
        spec["rows_tolerance"] = rows_tolerance
    handle = Handle(tokens=(3, 4, 5))
    got = serve_cell.check_logits(
        handle, CheckedEngine(handle, moved), Family, {"vocab_size": V},
        {"check": spec}, SEED)
    assert got["ok"] is ok
    assert got["rows_tolerance"] == rows_tolerance
    if not any(map(math.isnan, moved)):
        assert got["rel_err"] == pytest.approx(max(moved), rel=1e-5)
        assert got["rows_min"] == pytest.approx(min(moved), rel=1e-5)
        assert got["rows_median"] == pytest.approx(np.median(moved),
                                                   rel=1e-5)
        assert got["prefill_rows_min"] == pytest.approx(
            min(moved[0], moved[3]), rel=1e-5)
        assert got["decode_rows_min"] == pytest.approx(
            min(moved[1:3] + moved[4:]), rel=1e-5)
        assert got["per_row"]["prefill"] == pytest.approx(
            [moved[0], moved[3]], rel=1e-5)


def test_rows_tolerance_needs_decoded_rows():
    handle = Handle(tokens=(3,))
    with pytest.raises(ValueError, match="decode_positions is 0"):
        serve_cell.check_logits(
            handle, CheckedEngine(handle, [0.01, 0.01], rows=1), Family,
            {"vocab_size": V}, {"check": {
                "prompt_tokens": [4, 6], "decode_positions": 0,
                "tolerance": 0.45, "rows_tolerance": 0.04}}, SEED)


@pytest.mark.parametrize("name,least,control", [
    ("latent-decode", 0.0167, 0.0852), ("long-decode", 0.0120, 0.0646)])
def test_the_two_routed_long_cells_hold_the_least_moved_row(name, least,
                                                            control):
    check = committed_mix(name)["check"]
    # ``least``: the right program's worst reading on the chip;
    # ``control``: the least any control read there. The limit stands
    # twice over the one and twice under the other, and under the largest
    # row's (PERF.md, PR 41).
    assert 2 * least < check["rows_tolerance"] < check["tolerance"]
    assert 2 * check["rows_tolerance"] < control
    for other in ("batch-decode", "moe-batch-decode"):
        assert "rows_tolerance" not in committed_mix(other)["check"]


# ---- through the command path, on the CPU --------------------------------------------


def test_both_bookkeepings_read_one_capture_alike(tmp_path, monkeypatch):
    """A plain engine's check, greedy requests: the rows by (request,
    position) are the rows the step count gave, in the same order, and
    ``check_rel_err`` is computed from them."""
    seen = {}
    kept_rows = probe.kept_rows

    def both(captured, wanted):
        rows = kept_rows(captured, wanted)
        positions = len(next(iter(wanted.values()))) - 1
        old = old_rows(captured, {rid: r[0] + 1 for rid, r in wanted.items()},
                       positions)
        for rid, r in wanted.items():
            np.testing.assert_array_equal(
                np.stack(old[rid]), np.stack([rows[rid][p] for p in r]))
        seen["requests"] = len(wanted)
        return rows

    monkeypatch.setattr(probe, "kept_rows", both)
    bench = benchmark_with({"tiny-closed": ("xl-batch-decode", 1)})
    result = run.run_cell(bench, [REHEARSAL, run.HERE], "tiny-closed", SEED,
                          1.0, False, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    assert seen["requests"] == 2
    compared = result["compared"]
    assert list(result)[-1] == "compared"
    assert compared["check_decode_rows_min"][1] is None   # not judged
    assert compared["check_prefill_rows_min"][1] is None  # nowhere judged
    assert 0 <= compared["check_decode_rows_min"][0] \
        <= compared["check_rel_err"][0] <= compared["check_rel_err"][1]
    engine = probe.ProbedEngine.instances[-1]
    assert all(s["sampled_stochastic"] == 0
               for s in engine.step_log()["steps"])


@pytest.fixture
def two_token_probe():
    """The benchmark's probe around the test engine: ``_run_decode`` is
    noted from outside, the two decodes run inside it. The probe's own
    class is given the test engine for its base (and not a subclass put
    in its place): the serve layer keeps the first deployment class a
    process pickled, and with it the engine class that one named, so a
    second class would never be built in a test session."""
    bases = probe.ProbedEngine.__bases__
    probe.ProbedEngine.__bases__ = (TwoTokenEngine,)
    try:
        yield
    finally:
        probe.ProbedEngine.__bases__ = bases


def two_token_cell(monkeypatch, tmp_path, traced, misreport=False,
                   like="xl-batch-decode"):
    monkeypatch.setattr(TwoTokenEngine, "misreport", misreport)
    monkeypatch.setattr(TwoTokenEngine, "accepted", 0)
    monkeypatch.setattr(TwoTokenEngine, "rejected", 0)
    bench = benchmark_with({"tiny-sampled-closed": (like, 1)})
    result = run.run_cell(
        bench, [TWOTOKEN, REHEARSAL, run.HERE], "tiny-sampled-closed", SEED,
        1.5, traced, require_tpu=False, work_dir=str(tmp_path))
    return bench, result, probe.ProbedEngine.instances[-1]


@pytest.mark.parametrize("traced", [False, True])
def test_a_step_of_two_tokens_is_checked_and_timed(traced, tmp_path,
                                                   monkeypatch,
                                                   two_token_probe):
    bench, result, engine = two_token_cell(monkeypatch, tmp_path, traced)
    assert isinstance(engine, TwoTokenEngine)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    # Both outcomes of a step happened, many times over.
    assert TwoTokenEngine.accepted > 20 and TwoTokenEngine.rejected > 20
    steps = engine.step_log()["steps"]
    assert any(s["sampled_stochastic"] for s in steps)
    compared = result["compared"]
    assert compared["check_decode_rows_min"][0] \
        <= compared["check_decode_rows_min"][1]
    assert compared["compiles_in_window"] == [0, 0]
    got = result["metrics"]
    if traced:
        for name in ("decode_batch_mean", "serve_overhead_ms",
                     "engine_step_ms_p50", "compiles_in_window"):
            assert math.isfinite(got[name]["value"]), name
        assert 0 < got["decode_batch_mean"]["value"] <= 4
    else:
        assert set(got) == names(bench, "end_to_end", "xl-batch-decode")
        assert all(math.isfinite(m["value"]) and m["value"] > 0
                   for m in got.values())


@pytest.mark.parametrize("traced", [False, True])
def test_a_step_of_two_tokens_has_a_time_per_output_token(
        traced, tmp_path, monkeypatch, two_token_probe):
    """The cell of a self-drafting program is held to ``tpot_mean_ms``,
    the window over what its steps gave a stream, and reads
    ``tpot_p50_ms`` per layer: two tokens of a step reach a stream
    together, so a gap in three is zero here and neither reading is;
    the median of runs lies under a step's length, because some steps
    gave two."""
    monkeypatch.setattr(serve_cell, "TPOT_RUN", 4)   # requests of 16 tokens
    bench, result, engine = two_token_cell(
        monkeypatch, tmp_path, traced, like="kexaone-selfdraft-decode")
    assert result["correct"] is True, result
    got = result["metrics"]
    assert TwoTokenEngine.accepted > 20 and TwoTokenEngine.rejected > 20
    steps = [r.end - r.start for r in engine.steps if r.decodes]
    if traced:
        assert 0.4 * 1e3 * min(steps) < got["tpot_p50_ms"]["value"] \
            < 1e3 * max(steps)
        return
    assert set(got) == names(bench, "end_to_end",
                             "kexaone-selfdraft-decode") \
        == {"tpot_mean_ms", "setup_s"}
    # Every step gave a stream one token or two; turnovers, prefills and
    # waits come on top of the decode steps.
    assert got["tpot_mean_ms"]["value"] > 0.5 * 1e3 * min(steps)


def test_a_row_too_many_reads_not_correct(tmp_path, monkeypatch,
                                          two_token_probe):
    _, result, _ = two_token_cell(monkeypatch, tmp_path, False,
                                  misreport=True)
    assert result["correct"] is False
    read, limit = result["compared"]["check_rel_err"]
    assert read > limit
    assert result["failed"] == 0   # the streams themselves were well


@pytest.mark.parametrize("mix,correct", [("tiny-rows-right", True),
                                         ("tiny-rows-no-renorm", False)])
def test_a_router_not_renormalised_fails_the_least_moved_row_alone(
        mix, correct, tmp_path):
    bench = benchmark_with({mix: ("mellum2-long-decode", 1)},
                           config="tiny-mellum")
    result = run.run_cell(bench, [ROWS, MELLUM, run.HERE], mix, SEED, 1.0,
                          False, require_tpu=False, work_dir=str(tmp_path))
    assert result["correct"] is correct, result
    compared = result["compared"]
    # The largest row's limit passes either program ...
    assert compared["check_rel_err"][0] <= compared["check_rel_err"][1]
    # ... the least-moved row's is what tells them apart.
    assert (compared["check_decode_rows_min"][0]
            <= compared["check_decode_rows_min"][1]) is correct
    assert result["failed"] == 0


def test_chip_rows_reads_the_check_at_many_seeds(capsys):
    """``chip_rows.py``: the same deploy and the same ``check_logits`` a
    run makes, seed after seed in one process."""
    from perfbench.tests import chip_rows

    assert chip_rows.main([
        "tiny", "--config", "tiny-mellum:tiny-rows-right", "--dirs", ROWS,
        MELLUM, "--seeds", "5", "6", "--cpu"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    assert [line["seed"] for line in lines[:2]] == [5, 6]
    assert all(line["ok"] and line["rows_min"] <= line["rows_median"]
               <= line["rel_err"] for line in lines[:2])
    assert lines[2]["seeds"] == 2 and lines[2]["all_ok"] is True
    assert lines[2]["rows_min"] == [min(x["rows_min"] for x in lines[:2]),
                                    max(x["rows_min"] for x in lines[:2])]
    for line in lines[:2]:
        rows = line["per_row"]
        assert len(rows["prefill"]) == 2 and len(rows["decode"]) > 2
        assert line["decode_rows_min"] == min(rows["decode"])
        assert line["prefill_rows_min"] == min(rows["prefill"])
    # A control through the same comparison: one field of the program's
    # configuration changed, which the reference never sees.
    assert chip_rows.main([
        "tiny", "--config", "tiny-mellum:tiny-rows-right", "--dirs", ROWS,
        MELLUM, "--seeds", "5", "--cpu", "--override",
        "norm_topk_prob=false"]) == 0
    control = json.loads([
        line for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")][-1])
    assert control["ok"] is False
    assert control["rel_err"] <= control["tolerance"]
    assert control["decode_rows_min"] > control["rows_tolerance"]
