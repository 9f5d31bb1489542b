"""Of the window's steps whose decode went out ahead of the fetch before
it (``ahead`` 1), the share whose tokens went through the hand-over
program (``carried`` 1: the batch had moved since the decode in flight,
so ``_carry_fn`` gathered each row's id on the device) and were not the
in-flight ids as they lay. ``None`` where no step went out ahead (a
model that drafts keeps nothing in flight) and for a program whose
records lack the field."""


def read(run):
    from perfbench import flightlog, steplog

    steps = steplog.window_steps(run)
    return flightlog.decode_carried_pct(steps) if steps else None
