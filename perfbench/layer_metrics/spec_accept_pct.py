"""Share of the drafts a verify step checked that its verification kept:
over the window's steps, the step records' ``accepted`` over their
``drafted`` (one draft a decoding sequence a step). A program whose step
yields one token a sequence drafts nothing, its records have neither
field, and nothing is read."""


def read(run):
    from perfbench import steplog

    steps = steplog.window_steps(run) or ()
    drafted = sum(s.get("drafted", 0) for s in steps)
    if not drafted:
        return None
    return 100.0 * sum(s.get("accepted", 0) for s in steps) / drafted
