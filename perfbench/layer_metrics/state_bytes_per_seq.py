"""Bytes one sequence holds in the state arrays of the layers that keep a
state and no pool, whatever its length: the step records' ``state_bytes``
over ``state_seats`` (``PagedKVCache.state_bytes``: a seat's row in every
state array, each at its own item size), of the window's last step that
held a seat. A KDA layer keeps a float32 matrix a head and its
convolutions' tails in the model's dtype: 6 x (2,097,152 + 73,728) as
configured. ``None`` for a program whose records lack the fields."""


def read(run):
    for r in reversed(run.engine_steps):
        fields = getattr(r.program, "fields", None) or {}
        if fields.get("state_seats"):
            return float(fields["state_bytes"]) / fields["state_seats"]
    return None
