"""A per-layer metric moves one end-to-end metric, and every cell it
lists reports that one. A cell whose rate and tail spread too widely for
a bound reports ``itl_p50_ms`` instead of them (PERF.md section 2), so
each reader it shares with the other serving cells stands a second time
under ``<name>.long``: the same ``read``, another ``MOVES``."""

import json
import os
import types

import pytest

from perfbench import byname, run
from perfbench.rundata import RunData

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
LAYERS = {m["name"]: m for m in BENCH["per_layer"]}
TAKEN = ("out_tokens_per_s", "itl_p95_ms")   # end to end elsewhere
SPLIT = sorted(n for n in LAYERS if n.endswith(".long")
               and n[:-5] not in TAKEN)


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_there_is_something_to_test():
    assert len(SPLIT) >= 20
    assert E2E["itl_p50_ms"]["workloads"] == ["mellum2-long-decode"]


@pytest.mark.parametrize("name", SPLIT)
def test_a_split_reader_is_its_base_under_another_name(name):
    base = byname.load_reader([run.HERE], name[:-5])
    split = byname.load_reader([run.HERE], name)
    assert split.read is base.read
    assert (split.LAYER, split.UNIT, split.SOURCE) \
        == (base.LAYER, base.UNIT, base.SOURCE)
    assert split.MOVES == "itl_p50_ms" != base.MOVES
    ours, theirs = LAYERS[name], LAYERS[name[:-5]]
    assert {k: ours[k] for k in ("unit", "better", "source", "layer")} \
        == {k: theirs[k] for k in ("unit", "better", "source", "layer")}
    # Between them they list a cell once, each under the end-to-end
    # metric that the cell reports.
    assert not set(cells_of(ours)) & set(cells_of(theirs))
    for entry in (ours, theirs):
        for cell in cells_of(entry):
            assert cell in cells_of(E2E[entry["moves"]]), (entry, cell)


@pytest.mark.parametrize("name", TAKEN)
def test_a_metric_is_end_to_end_or_per_layer_in_a_cell_never_both(name):
    reader = byname.load_reader([run.HERE], name + ".long")
    entry = LAYERS[name + ".long"]
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    assert (entry["unit"], entry["better"]) \
        == (E2E[name]["unit"], E2E[name]["better"])
    assert not set(cells_of(entry)) & set(cells_of(E2E[name]))
    data = RunData(cell={}, cfg={}, mix={}, family=None, chips=1,
                   peaks=None, window=(0.0, 1.0),
                   end_to_end={name: 12.5}, memory_peak_bytes=0)
    assert reader.read(data) == 12.5
    data.end_to_end.clear()
    assert reader.read(data) is None      # nothing to read: left out


def test_every_cell_reports_set_up_one_more_and_a_layer():
    for cell in CELLS:
        ends = {n for n, m in E2E.items() if cell in cells_of(m)}
        assert "setup_s" in ends and len(ends) >= 2, cell
        assert any(cell in cells_of(m) for m in LAYERS.values()), cell
    for m in LAYERS.values():
        for cell in cells_of(m):
            assert cell in cells_of(E2E[m["moves"]]), (m["name"], cell)


def test_the_median_gap_is_of_all_gaps_in_the_window():
    """``itl_p50_ms`` beside ``itl_p95_ms``: the same gaps, their median."""
    from perfbench import serve_cell

    stream = types.SimpleNamespace(
        times=[0.0, 0.010, 0.020, 0.030, 0.130, 0.140])
    gaps = serve_cell.gaps_in([stream], (0.005, 1.0))
    assert len(gaps) == 5
    assert serve_cell.percentile(gaps, 50) == pytest.approx(0.010)
    assert serve_cell.percentile(gaps, 95) > 0.05
