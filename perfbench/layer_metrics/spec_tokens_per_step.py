"""Tokens a decoding sequence is given a step: over the window's steps,
the step records' ``emitted`` (tokens the decode gave out) over their
``decodes`` (sequences it ran), counted where the program drafts. Between
1, every draft rejected, and 2. Nothing is read of a program that does
not draft (no ``drafted`` in its records)."""


def read(run):
    from perfbench import steplog

    steps = [s for s in steplog.window_steps(run) or ()
             if s.get("drafted")]
    decodes = sum(s["decodes"] for s in steps)
    if not decodes:
        return None
    return sum(s["emitted"] for s in steps) / decodes
