"""Files found by the names ``BENCHMARK.json`` and the configuration
files give: ``configs/<config>.json``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.py`` and ``families/<family>.py``, in the first
of ``dirs`` that holds one. The command searches the benchmark's own
directory; the tests put directories of additions before it."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Dict, Sequence


def load_json(dirs: Sequence[str], kind: str, name: str) -> Dict:
    for d in dirs:
        path = os.path.join(d, kind, f"{name}.json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(f"no {kind}/{name}.json under {list(dirs)}")


def load_module(dirs: Sequence[str], kind: str, name: str):
    """The module ``<kind>/<name>.py``, loaded once per path."""
    for d in dirs:
        path = os.path.join(d, kind, f"{name}.py")
        if os.path.exists(path):
            key = "perfbench_" + "".join(
                c if c.isalnum() else "_" for c in os.path.abspath(path))
            if key not in sys.modules:
                spec = importlib.util.spec_from_file_location(key, path)
                module = importlib.util.module_from_spec(spec)
                sys.modules[key] = module
                try:
                    spec.loader.exec_module(module)
                except BaseException:
                    del sys.modules[key]
                    raise
            return sys.modules[key]
    raise FileNotFoundError(f"no {kind}/{name}.py under {list(dirs)}")


def load_reader(dirs: Sequence[str], name: str):
    """The reader of the per-layer metric ``name``:
    ``layer_metrics/<name>.py`` or, for a metric that is another one
    under a second name (``<base>.<tag>``: a per-layer metric moves one
    end-to-end metric, so a quantity whose cells report different ones
    is entered once for each, and read by the same code), the reader of
    the name with its last tag taken off."""
    while True:
        try:
            return load_module(dirs, "layer_metrics", name)
        except FileNotFoundError:
            if "." not in name:
                raise
        name = name.rpartition(".")[0]


def load_family(dirs: Sequence[str], cfg: Dict):
    """The family file a configuration names (``"family"``)."""
    return load_module(dirs, "families", cfg["family"])
