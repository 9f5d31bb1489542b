"""95th percentile of the gap between consecutive tokens of one stream,
as ``serve_cell.run`` takes it in every serving cell (``itl_p95_ms``),
for the cells where its runs spread too widely to be held to a bound:
read here, with none, while ``itl_p50_ms`` is the cell's end-to-end
metric (PERF.md section 2)."""

LAYER = "serve path"
UNIT = "ms"
MOVES = "itl_p50_ms"
SOURCE = "host_clock"


def read(run):
    return run.end_to_end.get("itl_p95_ms")
