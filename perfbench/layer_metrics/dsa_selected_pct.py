"""Share of the cached positions a decode step's indexers scored that
its attention then read: median over the window's plain decode steps of
the step record's ``dsa_rows_selected`` over its ``dsa_rows_scored`` (one
layer's, summed over the step's sequences). ``index_topk`` over the
context while it is longer than that, 100 while it is not: it holds the
mechanism engaged at the contexts the cell was sized for, and a program
that read every row would not have the fields. ``None`` for a program
whose records lack them (a model without an indexer, or the parent of the
PR that brought it)."""


def read(run):
    import statistics

    from perfbench import steplog

    steps = steplog.window_steps(run)
    if steps is None:
        return None
    shares = [100.0 * s["dsa_rows_selected"] / s["dsa_rows_scored"]
              for s in steps if s.get("decodes") and not s.get("prefills")
              and s.get("dsa_rows_scored")]
    return statistics.median(shares) if shares else None
