"""The loop a decode step's inputs were built by before the block tables
were kept between steps (``engine._run_decode`` / ``_run_verify`` up to PR
48), a sequence at a time through ``cache.slot`` and the lists of pages:
the reference the vectorised launch and the kept tables are held to.

``table_from_lists`` is the old ``PagedKVCache.table_array``;
``watch_decode`` compares everything an engine's decode program is handed,
call by call, with what that loop builds from the cache's lists at that
moment, so a table passed again from the last step is caught the moment it
is stale. The tokens of a step dispatched ahead are the ids in flight, on
the device: those are held to what the sequences are given at the fetch."""

import numpy as np

from raytpu.inference.engine import _bucket_for


def table_from_lists(cache, ids, width, batch, kind=0):
    out = np.zeros((batch, width), dtype=np.int32)
    for i, sid in enumerate(ids):
        first, table = cache._logical_pages(sid, kind)
        out[i, first:first + len(table)] = table
    return out


def loop_inputs(eng, seqs, ahead):
    """``(seats, tokens, positions, dests a kind, tables a kind, context
    lengths, live pages in a full layer, in a window layer)`` of a decode
    step over ``seqs`` that writes ``ahead`` positions a sequence."""
    cache = eng.cache
    b = len(seqs)
    bucket = _bucket_for(b, eng.decode_buckets)
    ids = [s.request_id for s in seqs]
    width = _bucket_for(max(cache.num_seq_pages(r) for r in ids),
                        eng.page_buckets)
    tokens = np.zeros(bucket, dtype=np.int32)
    positions = np.zeros(bucket, dtype=np.int32)
    context = np.ones(bucket, dtype=np.int32)
    seats = np.zeros(bucket, dtype=np.int32)
    dests = [np.tile(np.arange(ahead, dtype=np.int32), (bucket, 1))
             for _ in cache.kinds]
    live = live_window = 0
    for i, seq in enumerate(seqs):
        pos = seq.cached_len
        tokens[i] = seq.tokens[-1]
        positions[i] = pos
        context[i] = pos + 1
        if cache.total_seats:
            seats[i] = cache.seat(seq.request_id)
        live += cache.pages_for(pos + ahead)
        for kind in cache.kinds:
            if kind:
                live_window += cache.pages_read(pos + ahead - 1, 1)
            dests[kind][i] = [cache.slot(seq.request_id, pos + j, kind)
                              for j in range(ahead)]
    if ahead == 1:
        dests = [d[:, 0] for d in dests]
    tables = [table_from_lists(cache, ids, width, bucket, kind)
              for kind in cache.kinds]
    return seats, tokens, positions, dests, tables, context, live, live_window


def watch_decode(eng):
    """From now on hold every call of ``eng``'s decode program to
    :func:`loop_inputs`. Returns the list the calls are noted in: a
    ``(kinds whose table was the last call's array again, arrays the launch
    put)`` each, read from the step's record when the step has ended."""
    calls, seen = [], {}
    run, decode = eng._run_decode, eng._decode_fn
    ahead = 2 if eng._drafting is not None else 1
    carried = 2 if eng.cache.state or ahead == 2 else 0  # state, seats

    def same(got, want, what):
        got = [np.asarray(x) for x in (got if isinstance(got, tuple)
                                       else (got,))]
        assert len(got) == len(want), what
        for kind, (g, w) in enumerate(zip(got, want)):
            assert g.dtype == w.dtype and g.shape == w.shape, (what, kind)
            assert np.array_equal(g, w), (what, kind, g, w)

    def watched(*args):
        seats, tokens, positions, dests, tables, context, live, window = \
            loop_inputs(eng, seen["seqs"], ahead)
        given = args[3 + carried:]
        if carried:
            same(args[4], [seats], "seats")
        fields = eng.recorder.open.fields
        if fields.get("ahead"):
            # The ids of the step in flight, which the host has not
            # seen: held to what it emits once ``run`` has fetched them.
            seen["ahead"] = np.asarray(given[0])
            assert seen["ahead"].dtype == tokens.dtype \
                and seen["ahead"].shape == tokens.shape
        else:
            same(given[0], [tokens], "tokens")
        same(given[1], [positions], "positions")
        same(given[2], dests, "dests")
        same(given[3], tables, "tables")
        if ahead == 1:
            same(given[4], [context], "context_lens")
        assert (fields["live_pages"], fields["live_pages_full"],
                fields["live_pages_window"]) == (live, live, window)
        assert fields["table_width"] == tables[0].shape[1]
        assert all(type(fields[k]) is int for k in (
            "live_pages", "live_pages_window", "window_pages_released",
            "table_width", "tables_reused"))
        tables = given[3] if isinstance(given[3], tuple) else (given[3],)
        seen["reused"] = sum(a is b for a, b in zip(
            tables, seen.get("tables", ())))
        seen["tables"] = tables
        return decode(*args)

    def noted(seqs, out):
        seen["seqs"] = list(seqs)
        flight = getattr(eng, "_flight", None)
        carried = flight is not None and not eng._same_batch(flight.seqs,
                                                             seqs)
        n = run(seqs, out)
        fields = eng.recorder.open.fields
        assert fields["tables_reused"] == seen["reused"]
        # The tokens are no put of an ahead step's, and two (the rows'
        # sources, the joiners' tokens) where the batch has moved:
        # counted as one, so that a caller's sums read as they did.
        calls.append((fields["tables_reused"], fields["host_puts"]
                      + fields["ahead"] - 2 * carried))
        if fields["ahead"]:
            # Fetched inside ``run``: a live row's id in flight is the
            # token the sequence was given, and a joiner's the host's last
            # (none of these tests ends a sequence with a row in flight).
            given = seen.pop("ahead")[:len(seqs)].tolist()
            assert given == [s.tokens[-1] for s in seqs]
        return n

    eng._run_decode, eng._decode_fn = noted, watched
    return calls
