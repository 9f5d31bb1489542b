"""Disaggregated serving plane: tensor-parallel replicas, prefix-aware
routing, and prefill/decode KV handoff.

Covers the PR's contracts:

- the prefix-routing policy is a deterministic pure function of the
  (digests, summaries, probes, rng) snapshot, longest match first with
  power-of-two queue tie-break, and falls back to the blind policy on
  zero matches or saturation;
- a tensor-parallel (tp=2) engine over the 8-device virtual CPU mesh
  is token-identical to tp=1;
- a decode replica wired to a prefill peer grafts the prompt's KV
  prefix over the streaming handoff — token-identical to a
  single-replica run, with the decode engine prefilling ONLY the tail
  (proven on prefill-token counters) and zero KV blobs (RTP020);
- chaos: a failing stream aborts cleanly on both sides (no leaked pin
  sequences) and the request falls back to a colocated prefill with
  identical tokens; orphaned source pins die by TTL sweep;
- with ``RAYTPU_PREFIX_ROUTING`` on, streams sharing a system prompt
  concentrate on the replica that holds its pages, so the shared
  prefix prefills at most once per replica (here: exactly once).
"""

import dataclasses
import random
import threading
import time

import jax.numpy as jnp
import pytest

import raytpu
from raytpu import serve
from raytpu.cluster import constants as tuning
from raytpu.inference import disagg
from raytpu.inference import engine as engine_mod
from raytpu.models.llama import Llama, LlamaConfig, init_params
from raytpu.serve._private import prefix_router
from raytpu.util import failpoints

LCFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                           attn_impl="reference", remat=False)
ENGINE_OPTIONS = {"page_size": 8, "max_num_seqs": 4, "max_model_len": 64}

# 19 tokens at page_size 8: two FULL pages (16 tokens) are cacheable /
# shippable, the 3-token tail always prefills on the serving replica.
PROMPT = list(range(1, 20))
COVERED = 16


@pytest.fixture(scope="module")
def reference():
    """Greedy reference decode over the SAME weights every deployment
    in this file builds (init is deterministic in the seed)."""
    model = Llama(LCFG)
    params = init_params(model, LCFG, seed=0, batch=1)

    def decode(prompt, n_new):
        toks = list(prompt)
        outs = []
        for _ in range(n_new):
            logits = model.apply({"params": params}, jnp.asarray([toks]))
            tok = int(jnp.argmax(logits[0, len(toks) - 1]))
            toks.append(tok)
            outs.append(tok)
        return outs

    return decode


def _dep(**kw):
    opts = dict(ENGINE_OPTIONS)
    opts.update(kw.pop("engine_options", {}))
    return serve.LLMDeployment._target(engine_options=opts, seed=0, **kw)


# -- routing policy (pure function) ------------------------------------------


def _summaries(spec):
    """spec: {rid: [digests]} -> the (rid, handle, digests) snapshot."""
    return [(rid, f"handle-{rid}", d) for rid, d in sorted(spec.items())]


class TestPrefixRoutingPolicy:
    def test_longest_match_wins(self):
        summ = _summaries({"a": ["d0"], "b": ["d0", "d1", "d2"],
                           "c": ["d0", "d1"]})
        pick = prefix_router.select_replica(
            ["d0", "d1", "d2", "d3"], summ, lambda h: 0, 10,
            random.Random(0))
        assert pick == "handle-b"

    def test_no_match_falls_back_to_blind(self):
        summ = _summaries({"a": ["x"], "b": []})
        assert prefix_router.select_replica(
            ["d0"], summ, lambda h: 0, 10, random.Random(0)) is None

    def test_saturated_winner_falls_back_to_blind(self):
        summ = _summaries({"a": ["d0"]})
        assert prefix_router.select_replica(
            ["d0"], summ, lambda h: 10, 10, random.Random(0)) is None

    def test_chain_match_stops_at_first_miss(self):
        # A replica advertising a LATER digest without the earlier ones
        # cannot happen with chain hashing, but the walk must still
        # stop at the first miss rather than count disjoint hits.
        assert prefix_router.match_len(["d0", "d1", "d2"],
                                       ["d1", "d2"]) == 0
        assert prefix_router.match_len(["d0", "d1", "d2"],
                                       ["d0", "d2"]) == 1

    def test_deterministic_for_seeded_snapshot(self):
        """THE determinism contract: same snapshot + same seed => same
        decision, every time, independent of summary arrival order."""
        spec = {f"r{i}": ["d0", "d1"] for i in range(6)}
        qlens = {f"handle-r{i}": i % 3 for i in range(6)}
        picks = set()
        for _ in range(20):
            shuffled = _summaries(spec)
            random.Random(123).shuffle(shuffled)  # arrival order varies
            picks.add(prefix_router.select_replica(
                ["d0", "d1", "d2"], shuffled, qlens.__getitem__, 10,
                random.Random(42)))
        assert len(picks) == 1

    def test_pow2_tie_break_prefers_shorter_queue(self):
        spec = {"a": ["d0"], "b": ["d0"]}
        qlens = {"handle-a": 5, "handle-b": 1}
        pick = prefix_router.select_replica(
            ["d0"], _summaries(spec), qlens.__getitem__, 10,
            random.Random(0))
        assert pick == "handle-b"

    def test_prompt_digests_agree_with_replica_summary(self):
        """Client-side chain digests match what a replica that actually
        prefilled the prompt advertises — the equality routing needs."""
        dep = _dep()
        try:
            list(dep.generate(PROMPT, max_new_tokens=2))
            summary = dep.prefix_summary()
            assert summary["page_size"] == 8
            want = prefix_router.prompt_digests(PROMPT[:COVERED], 8)
            assert len(want) == 2
            assert set(want) <= set(summary["digests"])
        finally:
            dep.shutdown()


    def test_summary_never_waits_for_the_engine_lock(self):
        """The replica's event loop probes the summary from its health
        check. A step holds the engine lock for as long as a compile
        takes (seconds, cold on a chip); a probe that waited for it
        froze every stream and got the replica replaced."""
        import threading

        dep = _dep()
        try:
            list(dep.generate(PROMPT, max_new_tokens=2))
            got = []
            with dep._cv:  # a step in progress
                probe = threading.Thread(
                    target=lambda: got.append(dep.prefix_summary()))
                probe.start()
                probe.join(timeout=5.0)
                assert not probe.is_alive(), "probe blocked on the lock"
            want = prefix_router.prompt_digests(PROMPT[:COVERED], 8)
            assert set(want) <= set(got[0]["digests"])
        finally:
            dep.shutdown()


# -- tensor-parallel engine ---------------------------------------------------


class TestTensorParallelEngine:
    def test_tp2_is_token_identical_to_tp1(self, reference):
        dep = _dep(engine_options={"tp": 2})
        try:
            eng = dep._engine
            assert dict(eng.mesh.shape) == {"tp": 2}
            out = list(dep.generate(PROMPT, max_new_tokens=8))
            assert out == reference(PROMPT, 8)
            # The KV pool really is sharded along the kv-head axis.
            sharding = eng.cache.k[0].sharding
            assert sharding.spec[2] == "tp"
        finally:
            dep.shutdown()

    def test_tp_requires_divisible_kv_heads(self):
        with pytest.raises(ValueError, match="not divisible"):
            _dep(engine_options={"tp": 3})


# -- prefill/decode handoff ---------------------------------------------------


class TestDisaggHandoff:
    def test_handoff_is_token_identical_and_tail_only(self, reference,
                                                      monkeypatch):
        """The acceptance test: decode pulls the prompt's two full KV
        pages from the prefill peer over a multi-chunk stream, prefills
        ONLY the 3-token tail, and the stream is token-identical."""
        # Force a many-chunk pull so offsets/short-read checks matter.
        monkeypatch.setattr(tuning, "KV_STREAM_CHUNK_BYTES", 1000)
        prefill = _dep(role="prefill")
        decode = _dep(role="decode", prefill=prefill)
        try:
            before = engine_mod._prefill_tokens_total.value
            pages_before = disagg._handoff_pages_total.value
            bytes_before = disagg._handoff_bytes_total.value

            out = list(decode.generate(PROMPT, max_new_tokens=8))
            assert out == reference(PROMPT, 8)

            # Prefill side paid the full prompt (its export prefill,
            # +1 discarded sampled token's worth of prefill compute is
            # token-counted as the 19 prompt tokens); decode side paid
            # ONLY the tail past the grafted pages.
            delta = engine_mod._prefill_tokens_total.value - before
            assert delta == len(PROMPT) + (len(PROMPT) - COVERED)
            assert disagg._handoff_pages_total.value - pages_before == 2
            # Wire volume: layers * {k,v} * pages * page_bytes, exactly.
            cache = decode._engine.cache
            page_bytes = (8 * cache.num_kv_heads * cache.head_dim
                          * jnp.dtype(cache.dtype).itemsize)
            want = cache.num_layers * 2 * 2 * page_bytes
            assert disagg._handoff_bytes_total.value - bytes_before == want
            # The source pin was released through kv_export_end.
            assert prefill._handoff_source.open_exports() == 0

            # Second request sharing the prefix: the decode replica now
            # holds the pages locally, so NO second handoff happens.
            pages_mid = disagg._handoff_pages_total.value
            out2 = list(decode.generate(PROMPT[:COVERED] + [31, 32, 33],
                                        max_new_tokens=4))
            assert out2 == reference(PROMPT[:COVERED] + [31, 32, 33], 4)
            assert disagg._handoff_pages_total.value == pages_mid
        finally:
            decode.shutdown()
            prefill.shutdown()

    def test_short_prompt_never_pulls(self):
        """Prompts without a full shippable page skip the peer hop."""
        prefill = _dep(role="prefill")
        decode = _dep(role="decode", prefill=prefill)
        try:
            before = disagg._handoff_pages_total.value
            out = list(decode.generate([1, 2, 3], max_new_tokens=2))
            assert len(out) == 2
            assert disagg._handoff_pages_total.value == before
            assert prefill._handoff_source.open_exports() == 0
        finally:
            decode.shutdown()
            prefill.shutdown()


@pytest.mark.slow
@pytest.mark.chaos
class TestDisaggChaos:
    def test_stream_failure_falls_back_to_local_prefill(self, reference):
        """A prefill peer dying mid-stream (armed failpoint on the pull
        path) must free the staged pages on the sink, release the pin
        on the source, and retry colocated — token-identically."""
        prefill = _dep(role="prefill")
        decode = _dep(role="decode", prefill=prefill)
        try:
            fallbacks = disagg._handoff_fallbacks_total.value
            aborts = disagg._handoff_aborts_total.value
            failpoints.cfg("disagg.pull_chunk", "1*raise(ConnectionError)")
            try:
                out = list(decode.generate(PROMPT, max_new_tokens=8))
            finally:
                failpoints.clear()
            assert out == reference(PROMPT, 8)
            assert disagg._handoff_fallbacks_total.value == fallbacks + 1
            assert disagg._handoff_aborts_total.value == aborts + 1
            # Both sides clean: no sink pin survives the abort, the
            # source pin was released via the finally-path export_end.
            assert decode._engine.cache.num_sequences() == 0
            assert prefill._engine.cache.num_sequences() == 0
            assert prefill._handoff_source.open_exports() == 0
        finally:
            decode.shutdown()
            prefill.shutdown()

    def test_source_read_failure_also_falls_back(self, reference):
        prefill = _dep(role="prefill")
        decode = _dep(role="decode", prefill=prefill)
        try:
            failpoints.cfg("disagg.read_chunk", "1*raise(OSError)")
            try:
                out = list(decode.generate(PROMPT, max_new_tokens=4))
            finally:
                failpoints.clear()
            assert out == reference(PROMPT, 4)
            assert decode._engine.cache.num_sequences() == 0
            assert prefill._handoff_source.open_exports() == 0
        finally:
            decode.shutdown()
            prefill.shutdown()

    def test_orphaned_export_dies_by_ttl_sweep(self, monkeypatch):
        """A decode peer that vanishes after begin never calls end; the
        source's TTL sweep frees the pinned pages."""
        prefill = _dep(role="prefill")
        try:
            meta = prefill.kv_export_begin(PROMPT)
            assert meta is not None and meta["num_pages"] == 2
            assert prefill._handoff_source.open_exports() == 1
            monkeypatch.setattr(tuning, "KV_HANDOFF_TTL_S", 0.0)
            with prefill._cv:
                swept = prefill._handoff_source.sweep(
                    now=time.monotonic() + 1.0)
            assert swept == 1
            assert prefill._handoff_source.open_exports() == 0
            assert prefill._engine.cache.num_sequences() == 0
        finally:
            prefill.shutdown()


# -- serve-plane integration --------------------------------------------------


@pytest.fixture
def serve_instance():
    raytpu.shutdown()
    raytpu.init(num_cpus=4)
    yield raytpu
    serve.shutdown()
    raytpu.shutdown()


@pytest.mark.slow
class TestServePlaneE2E:
    def test_disagg_over_the_wire_via_handles(self, serve_instance,
                                              reference):
        """Full serve composition: a decode deployment bound to a
        prefill deployment's handle pulls KV through the replica wire
        path (_HandlePeer), token-identically."""
        prefill_node = serve.LLMDeployment.options(
            name="llm-prefill", role="prefill").bind(
                engine_options=ENGINE_OPTIONS, seed=0, role="prefill")
        app = serve.LLMDeployment.options(
            name="llm-decode", role="decode").bind(
                engine_options=ENGINE_OPTIONS, seed=0, role="decode",
                prefill=prefill_node)
        handle = serve.run(app, name="llm-disagg", route_prefix=None)
        pages_before = disagg._handoff_pages_total.value
        out = list(handle.generate.remote_streaming(PROMPT,
                                                    max_new_tokens=8))
        assert out == reference(PROMPT, 8)
        # Local-backend replicas share this process, so the module
        # counter observed the decode replica's graft.
        assert disagg._handoff_pages_total.value - pages_before == 2

    def test_prefix_routing_concentrates_shared_prefix(
            self, serve_instance, reference, monkeypatch):
        """THE routing acceptance count: with prefix routing on, four
        sequential streams sharing a 16-token system prompt across TWO
        replicas prefill the shared pages exactly once — the first
        request seeds one replica, every later request follows the
        digests there (prefill-token counters prove it)."""
        monkeypatch.setattr(tuning, "PREFIX_ROUTING", 1)
        monkeypatch.setattr(tuning, "PREFIX_SUMMARY_TTL_S", 0.0)
        app = serve.LLMDeployment.options(num_replicas=2).bind(
            engine_options=ENGINE_OPTIONS, seed=0)
        handle = serve.run(app, name="llm-routed", route_prefix=None)
        system = list(range(1, 17))
        tails = [[31, 32, 33], [41, 42, 43], [51, 52, 53], [61, 62, 63]]

        before = engine_mod._prefill_tokens_total.value
        for tail in tails:
            out = list(handle.generate.remote_streaming(
                system + tail, max_new_tokens=4))
            assert out == reference(system + tail, 4)
        delta = engine_mod._prefill_tokens_total.value - before
        # First stream pays system+tail (19); every follow-up routed to
        # the replica holding the pages and paid only its 3-token tail.
        assert delta == 19 + 3 * (len(tails) - 1)

    def test_routing_off_never_touches_prefix_machinery(
            self, serve_instance, monkeypatch):
        """Decision-identity when off: with RAYTPU_PREFIX_ROUTING unset
        (the default) the router must never enter the prefix path — no
        digests, no summary probes, no RNG draws."""
        from raytpu.serve._private.router import Router

        assert tuning.PREFIX_ROUTING == 0

        def _boom(self, args, kwargs):
            raise AssertionError("prefix path entered with routing off")

        monkeypatch.setattr(Router, "_choose_by_prefix", _boom)
        app = serve.LLMDeployment.bind(engine_options=ENGINE_OPTIONS,
                                       seed=0)
        handle = serve.run(app, name="llm-blind", route_prefix=None)
        out = list(handle.generate.remote_streaming([1, 2, 3, 4],
                                                    max_new_tokens=3))
        assert len(out) == 3
