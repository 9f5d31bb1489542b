"""Output tokens delivered to the clients per second of the window,
as ``serve_cell.run`` takes it in every serving cell (``out_tokens_per_s``),
for the cells where its runs spread too widely to be held to a bound:
read here, with none, while ``itl_p50_ms`` is the cell's end-to-end
metric (PERF.md section 2)."""

LAYER = "serve path"
UNIT = "tokens/s"
MOVES = "itl_p50_ms"
SOURCE = "host_clock"


def read(run):
    return run.end_to_end.get("out_tokens_per_s")
