"""Share of the window's decode steps whose decode was dispatched before
the ids of the step before it had been fetched: of the step records with
``decodes``, those whose ``ahead`` is 1. Such a step's tokens are the ids
in flight, where they lie on the device, so the chip goes from one step
to the next with no host in between; the others waited for a round trip
(a sequence joined or left, the first step after an idle engine). A
program that drafts keeps nothing in flight and reads 0. ``None`` for a
program whose records lack the field (the parent of the PR that brought
it)."""


def read(run):
    from perfbench import steplog

    steps = steplog.window_steps(run)
    if steps is None:
        return None
    ahead = [s["ahead"] for s in steps if s.get("decodes") and "ahead" in s]
    return 100.0 * sum(ahead) / len(ahead) if ahead else None
