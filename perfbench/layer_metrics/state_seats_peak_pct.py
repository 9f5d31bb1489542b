"""Largest share of the seats in the state arrays that sequences held at
the end of any engine step of the window: the step records'
``state_seats`` over the engine's ``max_num_seqs`` (a seat a sequence
slot). A seat is taken with a sequence's pages and given back with them,
so in a closed loop that keeps every slot busy it reads 100; less says
that admission stopped short of the seats, more than the slots cannot be.
``None`` for a program whose records carry no ``state_seats``."""


def read(run):
    from perfbench import steplog

    steps = steplog.window_steps(run) or ()
    if not steps or any("state_seats" not in s for s in steps):
        return None
    return 100.0 * max(s["state_seats"] for s in steps) \
        / run.mix["engine_options"]["max_num_seqs"]
