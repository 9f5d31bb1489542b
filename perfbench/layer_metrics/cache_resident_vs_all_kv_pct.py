"""Bytes the sequences of the traced decode steps hold in the cache of a
model whose layers do not all keep keys and values, against what the same
sequences would hold if every layer did. From the program's step records:
``live_pages`` (pages a decode step's sequences own in one attention
layer: their whole contexts) and ``state_seats`` (sequences that hold a
seat in the state arrays when the step ends). Held: the live pages' K and
V rows in the attention layers (``kv_shape``: those layers alone) plus
``state_bytes_per_seq`` a seat (the layers that keep a state and no pool:
the same bytes however long the sequence). If every layer kept keys and
values: the same pages in all ``num_hidden_layers`` layers of the
configuration as run, at the attention layers' row. ``None`` for a family
without ``state_bytes_per_seq`` and for a program whose records carry no
``state_seats``."""


def read(run):
    from perfbench import roofline

    per_seq = getattr(run.family, "state_bytes_per_seq", None)
    fields = [getattr(r.program, "fields", None) or {}
              for r in run.traced_steps if r.decodes]
    if per_seq is None or not fields \
            or any("state_seats" not in f for f in fields):
        return None
    layers, heads, head_dim, itemsize = run.family.kv_shape(run.cfg)
    pages = sum(f["live_pages"] for f in fields)
    a_layer = roofline.paged_attn_bytes(
        pages, run.mix["engine_options"]["page_size"], heads, head_dim,
        itemsize)
    if a_layer <= 0:
        return None
    held = layers * a_layer + per_seq(run.cfg) * sum(
        f["state_seats"] for f in fields)
    return 100.0 * held / (run.cfg["num_hidden_layers"] * a_layer)
