"""``perfbench/layer_metrics/flash_fwd_per_bwd.py``, the counter that says
whether a block under remat keeps the flash kernel's residuals (2 forward
calls a backward where it saves nothing, 1 where it keeps them), held in
tier 1: the cases of ``perfbench/tests/test_flash_fwd_per_bwd.py``,
collected here too, so that a change to what the reader counts fails
where every PR's tests run. What the program itself builds is
``tests/test_ops.py::TestRematKeepsTheKernelsResiduals``'s."""

from perfbench.tests.test_flash_fwd_per_bwd import (  # noqa: F401
    test_forward_calls_per_backward,
    test_nothing_to_read_without_a_backward_or_a_trace,
    test_the_entry_lists_the_training_cells)
