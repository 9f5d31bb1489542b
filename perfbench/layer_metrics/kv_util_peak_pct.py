"""Largest share of the KV pool's pages owned by sequences at the end of
any engine step of the window (``PagedKVCache.utilization()``, the value
behind ``stats()["kv_utilization"]``)."""


def read(run):
    if not run.engine_steps:
        return None
    return 100.0 * max(r.kv_utilization for r in run.engine_steps)
