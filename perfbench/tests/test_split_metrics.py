"""A per-layer metric moves one end-to-end metric, and every cell it
lists reports that one. A cell whose rate and tail spread too widely for
the steadier cells' bounds reports ``tpot_mean_ms`` instead of them
(PERF.md section 2: the window's time per output token a stream, under a
bound of its own), so each quantity it shares with the other serving
cells is entered a second time in ``BENCHMARK.json`` as ``<name>.long``,
moving ``tpot_mean_ms``. ``BENCHMARK.json`` alone says what moves what: a
reader is a ``read`` and nothing else, and ``<name>.long`` is read by
``layer_metrics/<name>.py`` (``byname.load_reader`` takes a tag off a name
that has no file). ``.long`` means "of a held cell", not a long context.
Which cells are held, and which entries are twins, is read here from
``BENCHMARK.json``: a cell that joins them edits no test."""

import json
import os
import types

import pytest

from perfbench import byname, run
from perfbench.rundata import RunData
from perfbench.tests.test_rehearsal import HELD

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
LAYERS = {m["name"]: m for m in BENCH["per_layer"]}
TWINS = sorted(n for n in LAYERS if n.endswith(".long"))
TAKEN = sorted(n for n in TWINS if n[:-5] in E2E)   # end to end elsewhere
SPLIT = sorted(set(TWINS) - set(TAKEN))
SAME = ("unit", "better", "source", "layer")


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_there_is_something_to_test():
    assert len(SPLIT) >= 20 and len(TAKEN) == 2 and len(HELD) >= 2
    assert {"itl_p50_ms", "tpot_p50_ms"}.isdisjoint(E2E)
    assert 0 < E2E["tpot_mean_ms"]["bound"] <= 0.1
    assert not [m["name"] for m in LAYERS.values()
                if m["moves"] not in E2E]
    assert cells_of(LAYERS["tpot_p50_ms"]) == HELD


@pytest.mark.parametrize("cell", HELD)
def test_a_held_cell_reports_the_mean_and_nothing_else_bounded(cell):
    ends = {n for n, m in E2E.items() if cell in cells_of(m)}
    assert ends == {"tpot_mean_ms", "setup_s"}
    listed = {n for n, m in LAYERS.items() if cell in cells_of(m)}
    # Every ``.long`` entry lists it (the shared readers, the rate and
    # the tail), and no base entry that has such a twin does.
    assert set(TWINS) <= listed
    assert not {n[:-5] for n in TWINS} & listed
    assert all(LAYERS[n]["moves"] == "tpot_mean_ms" for n in listed)
    for name in TWINS:
        assert LAYERS[name]["workloads"] == HELD, name


@pytest.mark.parametrize("name", SPLIT)
def test_a_split_entry_is_read_by_its_base_reader(name):
    base = byname.load_reader([run.HERE], name[:-5])
    split = byname.load_reader([run.HERE], name)
    assert split is base and callable(base.read)
    assert not os.path.exists(os.path.join(
        run.HERE, "layer_metrics", f"{name}.py"))
    ours, theirs = LAYERS[name], LAYERS[name[:-5]]
    assert {k: ours[k] for k in SAME} == {k: theirs[k] for k in SAME}
    assert ours["moves"] == "tpot_mean_ms" != theirs["moves"]
    # Between them they list a cell once, each under the end-to-end
    # metric that the cell reports.
    assert not set(cells_of(ours)) & set(cells_of(theirs))
    for entry in (ours, theirs):
        for cell in cells_of(entry):
            assert cell in cells_of(E2E[entry["moves"]]), (entry, cell)


@pytest.mark.parametrize("name", TAKEN)
def test_a_metric_is_end_to_end_or_per_layer_in_a_cell_never_both(name):
    reader, entry, end = byname.load_reader([run.HERE], name), \
        LAYERS[name], E2E[name[:-5]]
    assert (entry["unit"], entry["better"], entry["source"]) \
        == (end["unit"], end["better"], end["source"])
    assert not set(cells_of(entry)) & set(cells_of(end))
    data = RunData(cell={}, cfg={}, mix={}, family=None, chips=1,
                   peaks=None, window=(0.0, 1.0),
                   end_to_end={name[:-5]: 12.5}, memory_peak_bytes=0)
    assert reader.read(data) == 12.5
    data.end_to_end.clear()
    assert reader.read(data) is None      # nothing to read: left out


def test_a_name_with_no_file_loses_its_tags_and_an_unknown_one_raises(
        tmp_path):
    d = tmp_path / "layer_metrics"
    d.mkdir()
    (d / "a.b.py").write_text("def read(run):\n    return 1.0\n")
    (d / "a.b.c.py").write_text("def read(run):\n    return 2.0\n")
    dirs = [str(tmp_path)]
    assert byname.load_reader(dirs, "a.b").read(None) == 1.0
    assert byname.load_reader(dirs, "a.b.long").read(None) == 1.0
    assert byname.load_reader(dirs, "a.b.long.more").read(None) == 1.0
    assert byname.load_reader(dirs, "a.b.c").read(None) == 2.0   # own file
    for unknown in ("a", "a.long", "b.b"):
        with pytest.raises(FileNotFoundError):
            byname.load_reader(dirs, unknown)


def test_the_median_of_runs_is_read_per_layer_from_the_run():
    reader = byname.load_reader([run.HERE], "tpot_p50_ms")
    data = RunData(cell={}, cfg={}, mix={}, family=None, chips=1,
                   peaks=None, window=(0.0, 1.0),
                   end_to_end={"tpot_p50_ms": 9.5, "tpot_mean_ms": 9.8},
                   memory_peak_bytes=0)
    assert reader.read(data) == 9.5
    data.end_to_end.clear()       # no stream got 65 tokens: nothing read
    assert reader.read(data) is None
    assert LAYERS["tpot_p50_ms"]["moves"] == "tpot_mean_ms"


def test_every_cell_reports_set_up_one_more_and_a_layer():
    for cell in CELLS:
        ends = {n for n, m in E2E.items() if cell in cells_of(m)}
        assert "setup_s" in ends and len(ends) >= 2, cell
        assert any(cell in cells_of(m) for m in LAYERS.values()), cell
    for m in LAYERS.values():
        for cell in cells_of(m):
            assert cell in cells_of(E2E[m["moves"]]), (m["name"], cell)


def test_the_median_gap_is_of_all_gaps_in_the_window():
    """``median_gap_ms`` of the ``window`` line beside ``itl_p95_ms``:
    the same gaps, their median; ``tpot_p50_ms`` over the same stamps
    (here in runs of 2) spreads a long gap over its run."""
    from perfbench import serve_cell

    stream = types.SimpleNamespace(
        times=[0.0, 0.010, 0.020, 0.030, 0.130, 0.140])
    gaps = serve_cell.gaps_in([stream], (0.005, 1.0))
    assert len(gaps) == 5
    assert serve_cell.percentile(gaps, 50) == pytest.approx(0.010)
    assert serve_cell.percentile(gaps, 95) > 0.05
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(serve_cell, "TPOT_RUN", 2)
        runs = serve_cell.tpot_runs([stream], (0.005, 1.0))
    assert runs == [pytest.approx(0.010), pytest.approx(0.055)]
