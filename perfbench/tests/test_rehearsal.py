"""Every cell end to end on the CPU at a tiny size: the same command path
(``run.run_cell``), the same runners, generator, probe and readers, with
kernels interpreted and, for the sharded cell, four virtual devices. The
rehearsal configuration and mixes live in ``rehearsal/`` and are in no
``BENCHMARK.json``; the result names the CPU as its device and carries no
device metric. Also shown here: a model family, a configuration, a mix,
a cell and a per-layer reader are added by new files and appended entries
alone (``additions/``: a tiny Llama, trained and served).
"""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import run

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal")
ADDITIONS = os.path.join(HERE, "additions")
SEED = 2**31 + 11  # the driver's seeds are this large

# rehearsal mix -> (the real cell whose metrics it reports, chips)
CELLS = {"tiny-train": ("medium-train", 1),
         "tiny-closed": ("xl-batch-decode", 1),
         "tiny-open": ("open", 1),
         "tiny-train-fsdp4": ("xl-train-fsdp4", 4)}

# No cell of BENCHMARK.json runs an open loop yet (PERF.md, Open
# questions). Its rehearsal reports these of the listed metrics, and
# ``ttft_p95_ms``, which the runner takes for every open mix.
OPEN_REPORTS = ("itl_p95_ms", "serve_overhead_ms", "preemptions",
                "engine_step_ms_p50", "compiles_in_window",
                "paged_attn_roofline", "device_idle_pct.serve")
OPEN_ADDS = {
    "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
                    "bound": 0.1, "source": "host_clock",
                    "workloads": ["open"]}],
    "per_layer": []}


def benchmark_with(cells, config="tiny-gpt2"):
    """The real ``BENCHMARK.json`` with rehearsal entries appended."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": config, "source": "rehearsal",
                             "file": "-", "reduced": [], "why": "-"})
    for kind, entries in copy.deepcopy(OPEN_ADDS).items():
        bench[kind].extend(entries)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in OPEN_REPORTS:
            m["workloads"].append("open")
    for name, (like, chips) in cells.items():
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": name, "chips": chips,
                                   "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    return bench


def names(bench, kind, like):
    return {m["name"] for m in bench[kind]
            if "workloads" not in m or like in m["workloads"]}


@pytest.mark.parametrize("cell", list(CELLS))
def test_cell_end_to_end(cell, tmp_path):
    bench = benchmark_with(CELLS)
    result = run.run_cell(bench, [REHEARSAL, run.HERE], cell, SEED, 2.0,
                          False, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == names(bench, "end_to_end",
                                           CELLS[cell][0])
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert "breakdown" not in result


def held_cells():
    """The cells whose rate and tail the steadier cells' bounds do not hold
    (PERF.md section 2), as ``BENCHMARK.json`` has them: the ``workloads``
    of ``tpot_mean_ms``."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return next(m["workloads"] for m in json.load(f)["end_to_end"]
                    if m["name"] == "tpot_mean_ms")


HELD = held_cells()


@pytest.mark.parametrize("like", HELD)
def test_a_held_cell_reports_the_mean_time_per_token_and_set_up(
        like, tmp_path, short_runs):
    """The last line of a held cell has ``tpot_mean_ms`` and ``setup_s``
    and no third metric, every ``.long`` entry lists every held cell, and
    the mean is the window's seconds times the streams over its tokens:
    the rate's inverse, a stream."""
    bench = benchmark_with({"tiny-closed": (like, 1)})
    assert names(bench, "end_to_end", like) == {"tpot_mean_ms", "setup_s"}
    twins = [m for m in bench["per_layer"] if m["name"].endswith(".long")]
    assert twins
    for m in twins:
        assert m["moves"] == "tpot_mean_ms"
        assert m["workloads"] == HELD + ["tiny-closed"], m["name"]
    result = run.run_cell(bench, [REHEARSAL, run.HERE], "tiny-closed", SEED,
                          2.0, False, require_tpu=False,
                          work_dir=str(tmp_path))
    assert result["correct"] is True, result
    got = result["metrics"]
    assert set(got) == {"tpot_mean_ms", "setup_s"}
    assert got["tpot_mean_ms"]["unit"] == "ms"
    assert got["tpot_mean_ms"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-closed", "tiny-open"])
def test_cell_traced_reports_per_layer_metrics_without_device_numbers(
        cell, tmp_path):
    bench = benchmark_with(CELLS)
    result = run.run_cell(bench, [REHEARSAL, run.HERE], cell, SEED, 2.0,
                          True, require_tpu=False, work_dir=str(tmp_path))
    assert result["correct"] is True, result
    wanted = names(bench, "per_layer", CELLS[cell][0])
    got = set(result["metrics"])
    assert got and got <= wanted
    # A CPU trace has no TPU plane and the CPU no peaks: every device
    # metric is left out, none is invented.
    device_metrics = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"} \
        | {"mfu_pct", "hbm_peak_gb.train", "hbm_peak_gb.serve"}
    assert not got & device_metrics
    assert wanted - got <= device_metrics
    assert "busy_s" not in result["device"]
    if cell != "tiny-train":
        assert result["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("cell,like,traced", [
    ("tinier-train", "medium-train", True),
    ("tinier-closed", "xl-batch-decode", False)])
def test_additions_are_new_files_and_appended_entries(cell, like, traced,
                                                      tmp_path):
    """A family the benchmark has no file for (a tiny Llama: its program
    config, reference and counts in ``families/llama.py``), its
    configuration, two mixes, two cells and one reader, all from a
    directory of their own; no file of the benchmark is touched."""
    added = str(tmp_path / "added")
    shutil.copytree(ADDITIONS, added)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        before = f.read()
    bench = benchmark_with({cell: (like, 1)}, config="tiny-llama")
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s_chip", "workloads": ["tinier-train"]})
    dirs = [added, run.HERE]
    family = run.load_family(dirs, run.load_json(dirs, "configs",
                                                 "tiny-llama"))
    assert family.__file__.startswith(added)
    assert family.SERVE_MODEL == "llama"
    result = run.run_cell(bench, dirs, cell, SEED, 1.0, traced,
                          require_tpu=False, work_dir=str(tmp_path / "w"))
    # ``correct`` is the new family's own reference against its program:
    # the first step's loss, or the served logits through the paged cache.
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    if traced:
        assert result["metrics"]["steps_in_window"]["value"] \
            == result["attempted"]
    else:
        assert set(result["metrics"]) == names(bench, "end_to_end", like)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        assert f.read() == before


def test_an_unknown_family_is_refused_by_name():
    cfg = dict(run.load_json([REHEARSAL], "configs", "tiny-gpt2"),
               family="no-such-family")
    with pytest.raises(FileNotFoundError, match="families/no-such-family"):
        run.load_family([run.HERE], cfg)


def test_the_command_fails_off_a_tpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "medium-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "found none" in proc.stderr
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())


def test_readers_agree_with_benchmark_json():
    """``BENCHMARK.json`` alone says what a per-layer metric is (layer,
    unit, source, what it moves); a reader is a ``read`` and nothing
    else, found by the entry's name."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        reader = run.load_reader([run.HERE], m["name"])
        assert m["moves"] in e2e
        assert callable(reader.read)
        assert not {"LAYER", "UNIT", "MOVES", "SOURCE"} & set(vars(reader))
    for w in bench["workloads"]:
        cfg = run.load_json([run.HERE], "configs", w["config"])
        run.load_json([run.HERE], "traffic", w["traffic"])
        family = run.load_family([run.HERE], cfg)
        for part in ("SERVE_MODEL", "program_config", "train_parts",
                     "vocab_rows_held", "param_count",
                     "train_flops_per_token", "kv_shape", "logits", "loss"):
            assert hasattr(family, part), part
