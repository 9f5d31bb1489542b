"""A test-only engine whose decode step yields one or two tokens a
sequence: self-drafting in its plainest form, for the rehearsal of the
harness's contract with such a step (``probe.kept_rows``). Not a program
anyone serves, and nothing of ``raytpu/`` is changed by it.

A step runs the plain decode program twice. The first call feeds each
sequence's last token at its position ``p`` and its row samples the next
token (the request's own sampler: seed and position). The second call
feeds a *draft* at ``p + 1``. For a seeded half of the rows (by the
request's seed and the tokens it has) the draft is
that next token, so the second row is the model's at ``p + 1``, the
engine keeps it, emits the token sampled from it too and advances the
sequence by two. For the other half the draft is another token (a draft
the verification rejects): the row is dropped, the sequence advances by
one, and the next step writes position ``p + 1`` again with the right
token. ``_decode_fn``'s first result is the two calls' logits stacked,
``[bucket, 2, V]``. A sequence that ends on its first token keeps one
row whatever its draft was.

``misreport`` (a class flag a test sets) makes the engine keep the first
rejected draft's row of every step as well: a row too many, of a
position whose token the stream never fed, which the check has to read
as not correct.
"""

from __future__ import annotations

import numpy as np

from raytpu.inference.engine import InferenceEngine, _bucket_for


class TwoTokenEngine(InferenceEngine):
    misreport = False
    accepted = 0   # second rows kept, over every instance (the tests read)
    rejected = 0

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        assert not self._two_kinds, "the test engine knows one kind of pool"
        plain, jnp = self._decode_fn, self._jnp

        def verify(params, ks, vs, tokens, positions, dests, tables,
                   context_lens, rows, wrong, dests2):
            """Two plain decodes: ``(logits[bucket, 2, V], ks, vs, ids of
            the first rows, ids of the second)``. ``wrong[i]``: row
            ``i``'s draft is not the token its first row sampled."""
            first, ks, vs = plain(params, ks, vs, tokens, positions, dests,
                                  tables, context_lens)
            ids = self._sample_fn(first, *rows, positions)
            vocab = first.shape[-1]
            drafts = jnp.where(wrong, (ids + 1) % vocab, ids)
            second, ks, vs = plain(params, ks, vs, drafts, positions + 1,
                                   dests2, tables, context_lens + 1)
            ids2 = self._sample_fn(second, *rows, positions + 1)
            return jnp.stack([first, second], axis=1), ks, vs, ids, ids2

        self._decode_fn = verify

    def _run_decode(self, seqs, out):
        recorder = self.recorder
        with recorder.phase("infer.decode") as dec:
            with recorder.phase("infer.decode.launch"):
                b = len(seqs)
                bucket = _bucket_for(b, self.decode_buckets)
                # The scheduler secured one more slot a sequence; the
                # second position asks for its own, and a sequence that
                # gets none writes its draft to the scratch page.
                roomy = [self.cache.extend(s.request_id, s.cached_len + 2)
                         and s.cached_len + 2 <= self.max_model_len
                         for s in seqs]
                ids = [s.request_id for s in seqs]
                P = _bucket_for(max(self.cache.num_seq_pages(r)
                                    for r in ids), self.page_buckets)
                tokens = np.zeros(bucket, dtype=np.int32)
                positions = np.zeros(bucket, dtype=np.int32)
                dests = np.zeros(bucket, dtype=np.int32)
                dests2 = np.zeros(bucket, dtype=np.int32)
                context_lens = np.ones(bucket, dtype=np.int32)
                wrong = np.ones(bucket, dtype=bool)
                live_pages = 0
                for i, seq in enumerate(seqs):
                    pos = seq.cached_len
                    tokens[i] = seq.tokens[-1]
                    positions[i] = pos
                    dests[i] = self.cache.slot(seq.request_id, pos)
                    if roomy[i]:
                        dests2[i] = self.cache.slot(seq.request_id, pos + 1)
                    context_lens[i] = pos + 1
                    live_pages += self.cache.pages_for(pos + 1)
                    # The seeded half: by the request's seed and how far
                    # it has come, so that any three steps of a sequence
                    # hold an accepted draft and a rejected one.
                    wrong[i] = not roomy[i] or bool(
                        (len(seq.generated) // 2 + seq.sampling.seed) % 2)
                dec.attrs.update(batch=b, bucket=bucket)
                recorder.open.fields.update(
                    decodes=b, bucket=bucket, table_width=P,
                    live_pages=live_pages, live_pages_full=live_pages)
                rows, stochastic = self._sampling_rows(seqs, bucket)
                _, ks, vs, first, second = self._decode_fn(
                    self._params, self.cache.k, self.cache.v,
                    self._put(tokens), self._put(positions),
                    self._put(dests), self._put(self.cache.table_array(
                        ids, P, batch=bucket)), self._put(context_lens),
                    rows, self._put(wrong), self._put(dests2))
                self.cache.k, self.cache.v = ks, vs
            with recorder.phase("infer.decode.wait"):
                recorder.open.fields["sampled_stochastic"] += 2 * stochastic
                first, second = np.asarray(first), np.asarray(second)
            with recorder.phase("infer.decode.sample"):
                misreported = False
                for i, seq in enumerate(seqs):
                    seq.cached_len += 1
                    self._emit(seq, int(first[i]), out)
                    if seq.finish_reason is not None or not roomy[i]:
                        continue
                    keep = not wrong[i]
                    if wrong[i] and self.misreport and not misreported:
                        keep = misreported = True
                    if keep:
                        seq.cached_len += 1
                        self._emit(seq, int(second[i]), out)
                    TwoTokenEngine.accepted += bool(keep)
                    TwoTokenEngine.rejected += not keep
        return b
