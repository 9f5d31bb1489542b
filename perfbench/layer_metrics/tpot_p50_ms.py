"""A stream's time per output token between turnovers and stalls: the
median over runs of 64 tokens (``serve_cell.tpot_runs``), which reads a
plain step's period over the tokens a step gives a sequence. Beside
``tpot_mean_ms``, the same over all the window's time and tokens, it
says whether a change came from the plain steps or from what the mean
holds on top of them (turnovers' prefill steps, empty seats, stalls). A
window in which no stream got 65 tokens has no run, and nothing is
read."""


def read(run):
    return run.end_to_end.get("tpot_p50_ms")
