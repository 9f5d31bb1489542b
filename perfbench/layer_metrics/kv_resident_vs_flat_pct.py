"""Bytes of KV pages sequences own over two kinds of pool against what the
same sequences would own if every layer were a full one, at the step of
the window that owns the most. From the program's step records:
``pages_owned_full`` and ``pages_owned_window``, the pages owned in one
pool of each kind when a step ends (sequences still in their prompts and
a chunk's burst among them; a full layer's pool holds every sequence's
whole context, so its count is what a window layer would own if it were
a full one), and the layers of each kind from the family file
(``layers_by_kind``). Pages of the window pools that ``slide`` failed to
give back would show here. ``None`` for a program or a family without
them."""


def read(run):
    from perfbench import steplog

    by_kind = getattr(run.family, "layers_by_kind", None)
    steps = [s for s in steplog.window_steps(run) or ()
             if s.get("pages_owned_full")]
    if by_kind is None or not steps:
        return None
    full, window = by_kind(run.cfg)
    owned, flat = max(
        (full * s["pages_owned_full"] + window * s["pages_owned_window"],
         (full + window) * s["pages_owned_full"]) for s in steps)
    return 100.0 * owned / flat
