"""``perfbench/layer_metrics/flash_fwd_per_bwd.py``, the counter that says
whether a block under remat keeps the flash kernel's residuals (2 forward
calls a backward where it saves nothing, 1 where it keeps them), held in
tier 1: the cases of ``perfbench/tests/test_flash_fwd_per_bwd.py``,
collected here too, so that a change to what the reader counts fails
where every PR's tests run. What the program itself builds is
``tests/test_ops.py::TestRematKeepsTheKernelsResiduals``'s."""

import json
import os

from perfbench.tests.test_flash_fwd_per_bwd import (  # noqa: F401
    BENCH, test_forward_calls_per_backward,
    test_nothing_to_read_without_a_backward_or_a_trace)


def test_the_entry_lists_the_training_cells():
    """The benchmark's own case of this name looks the entry up by place
    (the last of ``per_layer``), which held until PR 55 appended its own
    after it; that file is the benchmark's and waits for a ``benchmark``
    PR (``PERF.md`` section 7). Here the entry is found by name."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "flash_fwd_per_bwd"]
    assert entry == {
        "name": "flash_fwd_per_bwd", "unit": "ratio", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s_chip",
        "workloads": ["medium-train", "xl-train-fsdp4"]}
