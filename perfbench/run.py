"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is ``configs/<config>.json``, its traffic mix
``traffic/<traffic>.json``, the configuration's model family
``families/<family>.py`` and each per-layer metric a reader
``layer_metrics/<metric>.py``, all found by name (``byname.py``). The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, traced ``breakdown``,
and last ``compared``: each number ``correct`` was decided from beside
its limit, which are also the last lines of standard error. Off a TPU,
or on a chip the peaks table does not hold, the command exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()  # before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Mapping, Optional, Sequence  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.byname import (load_family, load_json,  # noqa: E402,F401
                              load_reader)

TRACE_SECONDS = 6.0  # the traced part of a window: a few steps or seconds


def log(tag: str, payload: Mapping) -> None:
    """An earlier line of the output, for people and for ``PERF.md``."""
    print(f"[perfbench] {tag} {json.dumps(payload, sort_keys=True)}",
          flush=True)


def metrics_of(entries: Sequence[Mapping], cell: str) -> List[Mapping]:
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def device_block(devices, memory_peak_bytes: int) -> Dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}


def run_cell(benchmark: Mapping, dirs: Sequence[str], workload: str,
             seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True,
             work_dir: Optional[str] = None) -> Dict:
    """Run the cell and return the result object. ``require_tpu=False`` is
    for the CPU rehearsals of the benchmark's own tests: the result then
    names the CPU as its device and holds no device metric."""
    cell = next((w for w in benchmark["workloads"]
                 if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cfg = load_json(dirs, "configs", cell["config"])
    mix = load_json(dirs, "traffic", cell["traffic"])
    load_family(dirs, cfg)  # an unknown family fails before JAX starts

    import jax

    from perfbench.peaks import peaks_for
    from raytpu.util import compile_cache

    devices = jax.devices()
    peaks = None
    if require_tpu:
        if devices[0].platform != "tpu":
            raise SystemExit(
                f"perfbench measures a TPU and found none: JAX reports "
                f"{devices[0].platform!r}")
        if len(devices) < cell["chips"]:
            raise SystemExit(
                f"{workload} needs {cell['chips']} chip(s), JAX reports "
                f"{len(devices)}")
        try:
            peaks = peaks_for(devices[0].device_kind)
        except KeyError as e:
            raise SystemExit(str(e)) from None
    compile_cache.enable()
    marks = {"import": time.perf_counter() - PROCESS_START}

    work_dir = work_dir or os.path.join(ROOT, ".perfbench_work")
    trace_dir = os.path.join(work_dir, f"trace-{workload}")
    trace_seconds = min(float(seconds), float(mix.get("trace_seconds",
                                                      TRACE_SECONDS)))
    kind = mix["kind"]
    if kind == "train":
        from perfbench import train_cell as runner
    elif kind in ("closed", "open"):
        from perfbench import serve_cell as runner
    else:
        raise SystemExit(f"unknown traffic kind {kind!r}")
    outcome = runner.run(
        cell=cell, cfg=cfg, mix=mix, dirs=list(dirs), seed=seed,
        seconds=float(seconds),
        trace_dir=trace_dir if trace else None,
        trace_seconds=trace_seconds, devices=devices[:cell["chips"]],
        process_start=PROCESS_START, marks=marks, log=log)
    data = outcome["data"]
    data.peaks = peaks
    if trace and outcome.get("xplane"):
        from perfbench import trace_reduce

        data.trace = trace_reduce.load_xplane(outcome["xplane"])
    shutil.rmtree(trace_dir, ignore_errors=True)

    result = {"correct": bool(outcome["correct"]),
              "attempted": int(outcome["attempted"]),
              "failed": int(outcome["failed"])}
    metrics: Dict[str, Dict] = {}
    if not trace:
        for m in metrics_of(benchmark["end_to_end"], workload):
            if m["name"] in data.end_to_end:
                metrics[m["name"]] = {"value": data.end_to_end[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in metrics_of(benchmark["per_layer"], workload):
            value = load_reader(dirs, m["name"]).read(data)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device_block(devices, data.memory_peak_bytes)
    if trace and data.trace is not None and data.trace.device:
        from perfbench import trace_reduce

        win = trace_reduce.window_of(data.trace)
        result["device"]["busy_s"] = trace_reduce.busy_seconds(data.trace)
        result["device"]["window_s"] = win[1] - win[0]
        result["breakdown"] = {
            "device_ops": trace_reduce.heaviest_ops(data.trace),
            "idle_gaps": trace_reduce.idle_gaps(data.trace)}
    # Last: each number ``correct`` was decided from, ``[read, limit]``
    # (a limit of null: read and printed, not judged).
    result["compared"] = outcome["compared"]
    return result


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    result = run_cell(benchmark, [HERE], args.workload, args.seed,
                      args.seconds, bool(args.trace))
    sys.stdout.flush()
    for name, (read, limit) in result["compared"].items():
        print(f"[perfbench] compared {name} {read} limit {limit}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
