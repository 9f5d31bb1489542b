"""Whole steps inside the window (a reader added as a file)."""

LAYER, UNIT = "trainer", "count"
MOVES, SOURCE = "train_tokens_per_s_chip", "program_counter"


def read(run):
    return len(run.step_ends) - 1
