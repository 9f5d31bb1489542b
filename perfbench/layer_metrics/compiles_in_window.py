"""Programs the engine traced inside the window: the change of the sum
of ``stats()``'s three compile dictionaries from the last step before
the window to the window's last step. Must be 0; a run where it is
not is reported as incorrect."""


def read(run):
    return run.counted_in_window("compiles")
