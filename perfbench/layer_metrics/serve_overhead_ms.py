"""Host time per token above the engine: the median gap between tokens at
the client less the median of the benchmark's span around ``engine.step``
inside the window. Source: client stamps and ``ProbedEngine.step``."""

import statistics


def read(run):
    from perfbench.serve_cell import gaps_in

    gaps = gaps_in(run.streams, run.window)
    steps = [r.end - r.start for r in run.engine_steps if r.decodes]
    if not gaps or not steps:
        return None
    return 1e3 * (statistics.median(gaps) - statistics.median(steps))
