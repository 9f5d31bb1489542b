"""Output tokens delivered to the clients per second of the window,
as ``serve_cell.run`` takes it in every serving cell (``out_tokens_per_s``),
for the cells that are held end to end to ``tpot_mean_ms``, its inverse
a stream, under a bound of their own (PERF.md section 2): read here,
with none, so that the ledger keeps it in the other cells' unit."""


def read(run):
    return run.end_to_end.get("out_tokens_per_s")
