"""Bytes one cached token costs in the pools of every layer, as held:
the step record's ``kv_bytes_per_token`` (``PagedKVCache.token_bytes``:
the pools' bytes over the tokens they hold), of the window's last step.
A layer of K and V pools costs two rows of ``kv_heads x head_dim``; a
latent-attention layer one row (640 lanes held for 576 published:
15,360 over 12 layers where the architecture's are 13,824). ``None`` for
a program whose records lack it."""


def read(run):
    for r in reversed(run.engine_steps):
        fields = getattr(r.program, "fields", None) or {}
        if "kv_bytes_per_token" in fields:
            return float(fields["kv_bytes_per_token"])
    return None
