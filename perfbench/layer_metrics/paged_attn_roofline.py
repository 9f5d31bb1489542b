"""The paged-attention kernel's share of its roofline in the traced part
of the window. Time: the device trace's events named ``_paged_pallas*``
(the kernel is a custom call named after its JAX function) that lie
inside a ``pb.engine.step`` span of the trace. Least time: the pool
bytes the decodes of those same steps had to read (every live page's K
and V rows in every layer, from the pages the benchmark counted at the
call into the decode; layers, KV heads and head size from the family file) over
the peak bandwidth; decode at one query token a sequence is bound by
those bytes, not by FLOPs. Steps and spans are paired in order: both
come from the one thread that steps the engine, and only the step in
flight when the profiler stopped can have lost its span."""


def read(run):
    from perfbench import roofline, trace_reduce

    if run.trace is None or run.peaks is None:
        return None
    marks = trace_reduce.spans(run.trace, "pb.engine.step")
    steps = run.traced_steps[:len(marks)]
    events = trace_reduce.kernel_events(
        run.trace, lambda e: trace_reduce.op_head(e.name).startswith(
            "_paged_pallas"),
        within=[(m.start, m.end) for m in marks[:len(steps)]])
    seconds = sum(e.seconds for evs in events.values() for e in evs)
    if seconds <= 0:
        return None
    layers, heads, head_dim, itemsize = run.family.kv_shape(run.cfg)
    pages = sum(r.live_pages for r in steps if r.decodes)
    bytes_ = layers * roofline.paged_attn_bytes(
        pages, run.mix["engine_options"]["page_size"], heads, head_dim,
        itemsize)
    return roofline.roofline_share_pct(0.0, bytes_, seconds, run.peaks)
