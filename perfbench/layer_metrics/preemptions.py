"""Sequences the scheduler preempted inside the window: the change of
``stats()["num_preemptions"]`` from the last step before the window to
the window's last step."""


def read(run):
    return run.counted_in_window("preemptions")
