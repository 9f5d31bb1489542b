"""95th percentile of the gap between consecutive tokens of one stream,
as ``serve_cell.run`` takes it in every serving cell (``itl_p95_ms``),
for the cells where its runs spread too widely to be held to a bound:
read here, with none, while ``tpot_mean_ms`` is the cell's end-to-end
metric (PERF.md section 2)."""


def read(run):
    return run.end_to_end.get("itl_p95_ms")
