"""Mean number of sequences in a decode step inside the window: what the
engine appends to ``stats()["decode_batch_hist"]``, read at the call into
the decode."""


def read(run):
    sizes = [r.decodes for r in run.engine_steps if r.decodes]
    return sum(sizes) / len(sizes) if sizes else None
