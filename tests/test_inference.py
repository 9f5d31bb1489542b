"""Inference subsystem tests: paged KV cache, continuous-batching
scheduler, engine correctness (batched output == non-batched reference
for llama AND gpt2), compile-once-per-bucket discipline, preemption-
recompute, sampling invariance, and the jit-placement AST lint."""

import ast
import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import (InferenceEngine, PagedKVCache, SamplingParams,
                              Scheduler, Sequence)
from raytpu.inference.sampling import sample, sample_token
from raytpu.models import gpt2 as gpt2_mod
from raytpu.models import llama as llama_mod
from raytpu.models.gpt2 import GPT2, GPT2Config
from raytpu.models.gpt2 import init_params as gpt2_init
from raytpu.models.llama import Llama, LlamaConfig
from raytpu.models.llama import init_params as llama_init
from raytpu.models.mixtral import (JoyAIConfig, MellumConfig, Mixtral,
                                   OlmoeConfig)
from raytpu.models.mixtral import init_params as mixtral_init

LCFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                           attn_impl="reference", remat=False)
GCFG = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32,
                           attn_impl="reference", remat=False)


@pytest.fixture(scope="module")
def llama_model():
    model = Llama(LCFG)
    return model, llama_init(model, LCFG, seed=0, batch=1)


@pytest.fixture(scope="module")
def gpt2_model():
    model = GPT2(GCFG)
    return model, gpt2_init(model, GCFG, seed=0, batch=1)


def reference_greedy(model, params, prompt, n_new):
    """Non-batched, non-cached decode: full forward over the growing
    sequence, argmax at the last position — ground truth."""
    toks = list(prompt)
    outs = []
    for _ in range(n_new):
        logits = model.apply({"params": params}, jnp.asarray([toks]))
        tok = int(jnp.argmax(logits[0, len(toks) - 1]))
        toks.append(tok)
        outs.append(tok)
    return outs


class TestPagedKVCache:
    def make(self, pages=9, page_size=4):
        return PagedKVCache(num_layers=2, num_pages=pages, page_size=page_size,
                            num_kv_heads=2, head_dim=8)

    def test_layout_and_accounting(self):
        c = self.make()
        # A token's two heads of 8 side by side in one row of 16.
        assert c.k[0].shape == (9, 4, 2 * 8) and len(c.k) == 2
        assert c.v[1].shape == c.k[0].shape and c.k[0].dtype == jnp.float32
        assert (c.num_kv_heads, c.head_dim) == (2, 8)
        assert c.total_pages == 8 and c.free_pages() == 8
        assert c.pages_for(1) == 1 and c.pages_for(4) == 1
        assert c.pages_for(5) == 2 and c.pages_for(0) == 0

    def test_allocate_extend_free(self):
        c = self.make()
        assert c.allocate("a", 6)  # 2 pages
        assert c.used_pages() == 2 and c.utilization() == pytest.approx(0.25)
        assert c.extend("a", 8)  # still 2 pages
        assert c.used_pages() == 2
        assert c.extend("a", 9)  # 3rd page
        assert c.used_pages() == 3
        table = c.block_table("a")
        assert len(table) == 3 and 0 not in table  # page 0 is scratch
        c.free("a")
        assert c.free_pages() == 8
        c.free("a")  # idempotent

    def test_allocation_is_all_or_nothing(self):
        c = self.make(pages=4)  # 3 usable
        assert c.allocate("a", 8)  # 2 pages
        free_before = c.free_pages()
        assert not c.allocate("b", 8)  # needs 2, only 1 free
        assert c.free_pages() == free_before
        assert not c.extend("a", 17)  # needs 3 more, has 1
        assert len(c.block_table("a")) == 2

    def test_double_allocate_raises(self):
        c = self.make()
        assert c.allocate("a", 1)
        with pytest.raises(ValueError):
            c.allocate("a", 1)

    def test_slot_math(self):
        c = self.make()
        c.allocate("a", 10)  # 3 pages
        table = c.block_table("a")
        assert c.slot("a", 0) == table[0] * 4
        assert c.slot("a", 5) == table[1] * 4 + 1
        assert c.slot("a", 9) == table[2] * 4 + 1
        with pytest.raises(IndexError):
            c.slot("a", 12)

    def test_table_array_pads_with_scratch(self):
        c = self.make()
        c.allocate("a", 6)
        arr = c.table_array(["a"], max_pages=4, batch=3)
        assert arr.shape == (3, 4) and arr.dtype == np.int32
        assert list(arr[0][:2]) == c.block_table("a")
        assert not arr[0][2:].any() and not arr[1].any()

    def test_prefill_dests_pad_into_page0(self):
        c = self.make()
        c.allocate("a", 5)
        dests = c.prefill_dests("a", 5, bucket=8)
        assert dests.shape == (8,)
        for i in range(5):
            assert dests[i] == c.slot("a", i)
        assert all(0 <= d < 4 for d in dests[5:])  # page-0 slots


class TestKeptTables:
    """The block tables a program takes are kept a row a sequence and
    written where the lists are (ISSUE 49): after every mutation they are
    what the lists build (``kept_tables.table_from_lists``, the old
    ``table_array``), and the batch forms of ``slot`` and ``slide`` are
    the one-sequence ones."""

    def make(self, kinds, window_seqs=5):
        if kinds == 1:
            return PagedKVCache(2, 80, 4, 1, 8, seats=window_seqs)
        return PagedKVCache(
            4, 80, 4, 1, 8, layer_windows=(8, None, 8, None),
            window_pages=PagedKVCache.window_pool_pages(8, 4, window_seqs, 6),
            window_burst=6)

    def held_to_the_lists(self, c, lens):
        from kept_tables import table_from_lists

        ids = sorted(lens)
        width = max([c.num_seq_pages(r) for r in ids] + [1]) + 2
        rows = c.rows(ids, len(ids) + 2)
        assert c.table_width(rows) == width - 2 or not ids
        for kind in c.kinds:
            want = table_from_lists(c, ids, width, len(ids) + 2, kind)
            got = c.table_array(ids, width, batch=len(ids) + 2, kind=kind)
            assert got.dtype == np.int32 and np.array_equal(got, want)
            # Every position a sequence holds, and of a window table
            # those it has slid past, which lie in the scratch page.
            for sid, row in zip(ids, rows):
                pos = np.arange(lens[sid], dtype=np.int32)
                one = np.full(len(pos), row)
                if kind and lens[sid]:
                    first, pages = c.window_table(sid)
                    pos = pos[:(first + len(pages)) * c.page_size]
                    one = one[:len(pos)]
                assert c.slots(one, pos, kind).tolist() \
                    == [c.slot(sid, int(p), kind) for p in pos]

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("kinds", [1, 2])
    def test_the_kept_table_is_the_lists_after_every_mutation(
            self, kinds, seed):
        rng = np.random.default_rng(seed)
        c = self.make(kinds)
        twin = self.make(kinds)  # slid a sequence at a time
        lens, versions, retired = {}, {}, []
        for turn in range(120):
            free = [r for r in "abcde" if r not in lens]
            op = rng.choice(["allocate", "grow", "grow", "grow", "free"])
            if op == "allocate" and free:
                sid, n = free[0], int(rng.integers(1, 30))
                share = [r for r in lens if lens[r] >= 8]
                if kinds == 1 and share and rng.random() < 0.5:
                    # A prefix grafted in: another sequence's first pages.
                    pages = c.block_table(share[0])[:2]
                    n = max(n, 9)
                    for x in (c, twin):
                        assert x.allocate_shared(sid, n, pages)
                    assert c.block_table(sid)[:2] == pages
                else:
                    for x in (c, twin):
                        assert x.allocate(sid, n)
                # A prompt written in chunks of 6, its window slid on.
                for lo in range(0, n, 6):
                    for x in (c, twin):
                        x.slide(sid, lo, min(lo + 6, n))
                for x in (c, twin):
                    x.slide(sid, n, n)
                lens[sid] = n
            elif op == "grow" and lens:
                # A decode step of every live sequence, over the edges
                # of pages: one or two positions each.
                ids = sorted(lens)
                ahead = int(rng.integers(1, 3))
                for sid in ids:
                    for x in (c, twin):
                        assert x.extend(sid, lens[sid] + ahead)
                rows = c.rows(ids)
                before = {k: c.table_version(rows, k) for k in c.kinds}
                tables = {k: c.table_array(ids, 12, kind=k) for k in c.kinds}
                lo = np.array([lens[r] for r in ids], dtype=np.int32)
                released = c.slide_rows(ids, rows, lo, lo + ahead)
                assert released == sum(
                    twin.slide(r, lens[r], lens[r] + ahead) for r in ids)
                for k in c.kinds:
                    # The version stands still only where the rows do.
                    same = np.array_equal(
                        tables[k], c.table_array(ids, 12, kind=k))
                    assert (c.table_version(rows, k) == before[k]) == same
                written = lo[:, None] + np.arange(ahead, dtype=np.int32)
                for k in c.kinds:
                    assert c.slots(rows, written, k).tolist() == [
                        [c.slot(r, lens[r] + j, k) for j in range(ahead)]
                        for r in ids]
                for sid in ids:
                    lens[sid] += int(rng.integers(1, ahead + 1))
            elif op == "free" and lens:
                # Freed, as a preempted sequence is: its name comes back
                # under "allocate", in whatever row is free then.
                sid = sorted(lens)[int(rng.integers(len(lens)))]
                for x in (c, twin):
                    x.free(sid)
                del lens[sid]
                retired.append(sid)
            self.held_to_the_lists(c, lens)
            for sid in lens:
                assert c.block_table(sid) == twin.block_table(sid)
                if kinds == 2:
                    assert c.window_table(sid) == twin.window_table(sid)
        assert retired and len(c._rows) == len(lens)
        for sid in list(lens):
            c.free(sid)
        assert not any(t.any() for t in c._table)
        assert c.free_pages() == c.total_pages
        assert c.window_pages_owned() == 0

    def test_a_position_beyond_the_allocation_is_refused(self):
        c = self.make(1)
        c.allocate("a", 10)  # 3 pages
        rows = c.rows(["a"], 2)
        assert c.slots(rows, np.array([11, 0])).tolist() \
            == [c.slot("a", 11), 0]
        with pytest.raises(IndexError):
            c.slots(rows, np.array([12, 0]))
        with pytest.raises(IndexError):
            c.slots(rows, np.array([[11, 12], [0, 1]]))
        assert c.seats(["a"], 3).tolist() == [c.seat("a"), 0, 0]

    def test_the_tables_grow_with_the_sequences_and_the_widths(self):
        c = PagedKVCache(1, 400, 2, 1, 4)
        for i in range(20):
            assert c.allocate(f"s{i}", 3 * i + 1)
        ids = [f"s{i}" for i in range(20)]
        from kept_tables import table_from_lists

        assert np.array_equal(c.table_array(ids, 64, batch=32),
                              table_from_lists(c, ids, 64, 32))
        assert len(set(c.rows(ids).tolist())) == 20 and c.rows(ids).min() > 0


class _FakePageCache(PagedKVCache):
    """Real cache minus the JAX arrays (scheduler never touches them)."""

    def __init__(self, num_pages, page_size):
        super().__init__(num_layers=1, num_pages=num_pages,
                         page_size=page_size, num_kv_heads=1, head_dim=1)


class TestScheduler:
    def make(self, pages=9, page_size=4, max_num_seqs=8):
        cache = _FakePageCache(pages, page_size)
        return cache, Scheduler(cache, max_num_seqs=max_num_seqs,
                                max_model_len=64)

    def seq(self, rid, prompt_len):
        return Sequence(request_id=rid, prompt=list(range(1, prompt_len + 1)))

    def test_fifo_admission_and_merge_with_decodes(self):
        _, sched = self.make()
        a = self.seq("a", 6)
        sched.add(a)
        plan = sched.schedule()
        assert plan.prefills == [a] and plan.decodes == []
        a.cached_len = a.prefill_len
        a.generated.append(1)
        b = self.seq("b", 3)
        sched.add(b)
        plan = sched.schedule()
        # New prefill merges with the in-flight decode in one iteration.
        assert plan.prefills == [b] and plan.decodes == [a]

    def test_admission_respects_page_budget(self):
        cache, sched = self.make(pages=4)  # 3 usable
        a, b = self.seq("a", 8), self.seq("b", 8)  # 2 pages each
        sched.add(a)
        sched.add(b)
        plan = sched.schedule()
        assert plan.prefills == [a]  # b doesn't fit
        assert list(sched.waiting) == [b]

    def test_admission_respects_max_num_seqs(self):
        _, sched = self.make(max_num_seqs=1)
        a, b = self.seq("a", 2), self.seq("b", 2)
        sched.add(a)
        sched.add(b)
        assert sched.schedule().prefills == [a]
        assert list(sched.waiting) == [b]

    def test_preempts_youngest_under_page_pressure(self):
        cache, sched = self.make(pages=5)  # 4 usable
        a, b = self.seq("a", 8), self.seq("b", 7)  # 2 pages each
        sched.add(a)
        sched.add(b)
        assert sched.schedule().prefills == [a, b]
        a.cached_len, b.cached_len = 8, 7
        a.generated.append(1)
        b.generated.append(1)
        # a needs a 3rd page for token 9; none free -> b (youngest) is
        # preempted-to-recompute and no admission happens this round.
        plan = sched.schedule()
        assert plan.preempted == [b] and plan.prefills == []
        assert plan.decodes == [a]
        assert b.cached_len == 0 and b.state == "waiting"
        assert sched.num_preemptions == 1
        assert list(sched.waiting) == [b]  # front of the queue
        # b resumes later with prompt+generated prefilled, nothing resampled.
        assert b.prefill_len == 7  # 8 known tokens, newest decoded next

    def test_abort_everywhere(self):
        cache, sched = self.make()
        a, b = self.seq("a", 4), self.seq("b", 4)
        sched.add(a)
        sched.add(b)
        sched.schedule()
        assert sched.abort("a")  # running
        assert cache.num_sequences() == 1
        assert not sched.abort("a")  # idempotent
        assert sched.abort("b")
        assert cache.free_pages() == cache.total_pages
        assert not sched.has_unfinished()


class TestEngineLlama:
    def make_engine(self, params, **kw):
        kw.setdefault("page_size", 8)
        kw.setdefault("max_num_seqs", 4)
        kw.setdefault("max_model_len", 64)
        return InferenceEngine(LCFG, params, **kw)

    def test_single_request_matches_reference(self, llama_model):
        model, params = llama_model
        eng = self.make_engine(params)
        prompt = list(range(1, 10))
        (out,) = eng.generate([prompt], SamplingParams(max_new_tokens=6))
        assert out == reference_greedy(model, params, prompt, 6)

    def test_staggered_requests_share_decode_and_match(self, llama_model):
        model, params = llama_model
        eng = self.make_engine(params)
        pa, pb = list(range(1, 12)), [7, 3, 9]
        eng.add_request("a", pa, SamplingParams(max_new_tokens=8))
        results = {"a": [], "b": []}

        def drain(outs):
            for o in outs:
                results[o.request_id].append(o.token_id)

        drain(eng.step())  # a prefills
        drain(eng.step())  # a decodes alone
        eng.add_request("b", pb, SamplingParams(max_new_tokens=5))
        while eng.has_unfinished():
            drain(eng.step())
        assert results["a"] == reference_greedy(model, params, pa, 8)
        assert results["b"] == reference_greedy(model, params, pb, 5)
        stats = eng.stats()
        # They provably shared iterations: some step decoded batch 2.
        assert max(stats["decode_batch_hist"]) >= 2
        assert 1 in stats["decode_batch_hist"]

    def test_decode_compiles_once_per_bucket(self, llama_model):
        _, params = llama_model
        eng = self.make_engine(params)
        prompts = [list(range(1, 4 + i)) for i in range(4)]
        eng.generate(prompts, SamplingParams(max_new_tokens=6))
        stats = eng.stats()
        # Batch composition changed every few iterations (staggered
        # finishes) but each bucket size compiled exactly once.
        assert stats["decode_compiles"]
        assert all(v == 1 for v in stats["decode_compiles"].values())
        assert all(v == 1 for v in stats["prefill_compiles"].values())

    def test_prefill_buckets_compile_once_per_length_bucket(self,
                                                            llama_model):
        _, params = llama_model
        eng = self.make_engine(params)
        # Two prompts in the same bucket (16), one in the next (32).
        for rid, plen in (("a", 5), ("b", 9), ("c", 20)):
            eng.add_request(rid, list(range(1, plen + 1)),
                            SamplingParams(max_new_tokens=2))
        while eng.has_unfinished():
            eng.step()
        assert eng.stats()["prefill_compiles"] == {"16": 1, "32": 1}

    def test_preemption_recompute_preserves_output(self, llama_model):
        model, params = llama_model
        # 5 usable pages of 4 tokens: two growing sequences can't both
        # stay resident, forcing preempt-to-recompute mid-generation.
        eng = InferenceEngine(LCFG, params, page_size=4, num_pages=6,
                              max_num_seqs=2, max_model_len=24)
        pa, pb = list(range(1, 8)), list(range(20, 25))
        outs = eng.generate([pa, pb], SamplingParams(max_new_tokens=8))
        assert eng.stats()["num_preemptions"] >= 1
        assert outs[0] == reference_greedy(model, params, pa, 8)
        assert outs[1] == reference_greedy(model, params, pb, 8)
        assert eng.cache.free_pages() == eng.cache.total_pages

    def test_temperature_sampling_batch_invariant(self, llama_model):
        _, params = llama_model
        sampling = SamplingParams(max_new_tokens=6, temperature=0.8,
                                  top_k=12, seed=123)
        solo = self.make_engine(params).generate([[5, 6, 7]], sampling)[0]
        eng = self.make_engine(params)
        batched = eng.generate([[5, 6, 7], list(range(1, 9))], sampling)[0]
        assert solo == batched  # per-request RNG: batching is invisible
        assert len(solo) == 6

    def test_stop_tokens_and_length_finish(self, llama_model):
        model, params = llama_model
        prompt = list(range(1, 10))
        first = reference_greedy(model, params, prompt, 1)[0]
        eng = self.make_engine(params)
        eng.add_request("s", prompt, SamplingParams(
            max_new_tokens=8, stop_token_ids=(first,)))
        outs = []
        while eng.has_unfinished():
            outs.extend(eng.step())
        assert len(outs) == 1 and outs[0].finished
        assert outs[0].finish_reason == "stop"
        eng2 = self.make_engine(params)
        eng2.add_request("l", prompt, SamplingParams(max_new_tokens=2))
        outs = []
        while eng2.has_unfinished():
            outs.extend(eng2.step())
        assert outs[-1].finish_reason == "length"
        assert eng2.cache.free_pages() == eng2.cache.total_pages

    def test_request_validation(self, llama_model):
        _, params = llama_model
        eng = self.make_engine(params)
        with pytest.raises(ValueError):
            eng.add_request("e", [])
        with pytest.raises(ValueError):
            eng.add_request("e", list(range(64)))  # no room to generate

    def test_metrics_and_spans(self, llama_model):
        from raytpu.inference import engine as engine_mod
        from raytpu.util import tracing

        _, params = llama_model
        eng = self.make_engine(params)
        before = engine_mod._decode_tokens_total.value
        tracing.enable_tracing()
        try:
            eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
            names = {s["name"] for s in tracing.get_spans()}
        finally:
            tracing.disable_tracing()
            tracing.clear_spans()
        assert {"infer.prefill", "infer.decode"} <= names
        assert engine_mod._decode_tokens_total.value >= before + 2
        assert engine_mod._running_gauge.value == 0
        assert engine_mod._kv_util_gauge.value == 0.0


# ---------------------------------------------------------------------------
# Sampling on the device: one sampler for the prefill's first token and
# every decode's, keyed by the request's seed and the row's position.
# ---------------------------------------------------------------------------

_F32 = dict(dtype=jnp.float32, attn_impl="reference", remat=False)
SERVED = {
    "gpt2": (GCFG, lambda: gpt2_init(GPT2(GCFG), GCFG, seed=0, batch=1)),
    "llama": (LCFG, lambda: llama_init(Llama(LCFG), LCFG, seed=0, batch=1)),
    **{name: (c, lambda c=c: mixtral_init(Mixtral(c), c, seed=1))
       for name, c in (
           ("olmoe", dataclasses.replace(
               OlmoeConfig.tiny(), paged_attn="reference", **_F32)),
           ("mellum", dataclasses.replace(
               MellumConfig.tiny(), paged_attn="reference", **_F32)),
           ("joyai", dataclasses.replace(
               JoyAIConfig.tiny(), paged_attn="reference", **_F32)))},
}


def keep_sampled(eng):
    """Every call of the engine's sampler from now on, as ``(logits rows
    sampled, temperature, top_k, seed, position, ids)`` on the host."""
    calls, fn = [], eng._sample_fn

    def kept(logits, temperature, top_k, seed, position):
        ids = fn(logits, temperature, top_k, seed, position)
        calls.append((np.asarray(logits).reshape(-1, logits.shape[-1]),
                      *(np.asarray(a) for a in (temperature, top_k, seed,
                                                position, ids))))
        return ids

    eng._sample_fn = kept
    return calls


def oracle_probs(logits, temperature, top_k):
    """The distribution ``sample_token`` draws a stochastic row from."""
    scaled = np.asarray(logits, np.float64) / max(temperature, 1e-6)
    if 0 < top_k < scaled.shape[0]:
        scaled = np.where(scaled >= np.sort(scaled)[-top_k], scaled, -np.inf)
    probs = np.exp(scaled - scaled.max())
    return probs / probs.sum()


class TestDeviceSampling:
    N = 4096          # draws a frequency is taken over
    TOLERANCE = 0.03  # of a frequency: 3.8 sigma of 4,096 draws at p = 1/2

    @staticmethod
    def draw(logits, temperature, top_k, seed, position):
        """``sample`` jitted, over host values broadcast to the rows."""
        rows = np.asarray(logits, np.float32)
        n = rows.shape[0]
        full = lambda x, dt: jnp.asarray(np.broadcast_to(
            np.asarray(x, dt), (n,)))
        return np.asarray(jax.jit(sample)(
            jnp.asarray(rows), full(temperature, np.float32),
            full(top_k, np.int32), full(seed, np.uint32),
            full(position, np.int32)))

    @pytest.mark.parametrize("family", sorted(SERVED))
    def test_greedy_ids_are_the_argmax_of_the_steps_logits(self, family):
        cfg, init = SERVED[family]
        eng = InferenceEngine(cfg, init(), page_size=4, max_num_seqs=4,
                              max_model_len=64, prefill_chunk=16)
        calls = keep_sampled(eng)
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
                   for n in (5, 11, 23)]  # the last one in two chunks
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=5))
        assert len(calls) >= 3 + 4 and [len(o) for o in outs] == [5, 5, 5]
        for logits, temperature, _, _, _, ids in calls:
            assert ids.dtype == np.int32 and not temperature.any()
            assert ids.tolist() == np.argmax(logits, axis=-1).tolist()
        # Each prompt's first token is its prefill's one sampled row.
        firsts = [c[5].tolist() for c in calls if c[0].shape[0] == 1
                  and len(c[5]) == 1][:3]
        assert sorted(f[0] for f in firsts) == sorted(o[0] for o in outs)
        assert all(s["sampled_stochastic"] == 0
                   for s in eng.step_log()["steps"])
        assert all(v == 1 for v in eng.stats()["sample_compiles"].values())

    def test_argmax_takes_the_first_maximum_as_numpy_does(self):
        logits = np.zeros((3, 9), np.float32)
        logits[0, [2, 5]] = 1.0
        logits[1, [8, 0]] = 3.0
        assert self.draw(logits, 0.0, 0, 0, 0).tolist() \
            == np.argmax(logits, axis=-1).tolist() == [2, 0, 0]

    @pytest.mark.parametrize("temperature,top_k", [
        (0.7, 5), (1.0, 0), (1.5, 3), (0.3, 16)])
    def test_stochastic_rows_follow_the_oracles_distribution(
            self, temperature, top_k):
        row = np.random.default_rng(7).normal(size=16).astype(np.float32)
        probs = oracle_probs(row, temperature, top_k)
        logits = np.broadcast_to(row, (self.N, 16))
        # Over seeds at one position, and over positions of one seed.
        for seed, position in ((np.arange(self.N), 3),
                               (11, np.arange(self.N))):
            ids = self.draw(logits, temperature, top_k, seed, position)
            freq = np.bincount(ids, minlength=16) / self.N
            assert np.abs(freq - probs).max() < self.TOLERANCE
            assert not freq[probs == 0].any()  # none outside the top k
        # The oracle itself, by the same yardstick.
        rng = np.random.default_rng(5)
        params = SamplingParams(temperature=temperature, top_k=top_k)
        drawn = [sample_token(row, params, rng) for _ in range(self.N)]
        freq = np.bincount(drawn, minlength=16) / self.N
        assert np.abs(freq - probs).max() < self.TOLERANCE

    def test_top_k_of_zero_or_past_the_vocabulary_masks_nothing(self):
        logits = np.zeros((self.N, 8), np.float32)  # every id as likely
        seeds = np.arange(self.N)
        unmasked = self.draw(logits, 1.0, 0, seeds, 0)
        assert set(unmasked.tolist()) == set(range(8))
        for top_k in (8, 9, 1 << 20, -1):
            assert self.draw(logits, 1.0, top_k, seeds, 0).tolist() \
                == unmasked.tolist()
        # Ties with the k-th value are kept, as the oracle keeps them.
        assert set(self.draw(logits, 1.0, 2, seeds, 0).tolist()) \
            == set(range(8))
        one = self.draw(np.arange(8, dtype=np.float32)[None].repeat(
            self.N, 0), 1.0, 1, seeds, 0)
        assert set(one.tolist()) == {7}

    @pytest.mark.parametrize("k", [1, 2, 37, 500, 999, 1000])
    def test_a_rows_threshold_is_its_sorted_kth_value(self, k):
        from raytpu.inference.sampling import _kth_largest, _ordered_bits
        x = np.random.default_rng(k).normal(size=(5, 1000)).astype(np.float32)
        x[0, :10], x[0, 10:20] = 0.0, -0.0          # one value to a float
        x[1, 5], x[2, 7] = np.inf, -np.inf
        x[3] = np.round(x[3])                        # ties at every rank
        x[4] *= 1e30
        order = _ordered_bits(jnp.asarray(x))
        kth = jax.jit(_kth_largest)(order, jnp.full(5, k, jnp.int32))
        kept = np.asarray(order >= kth[:, None])
        want = np.sort(x, axis=-1)[:, -k]
        assert (kept == (x >= want[:, None])).all()
        assert (kept.sum(axis=-1) >= k).all()

    def test_rows_of_one_batch_are_independent(self):
        rng = np.random.default_rng(3)
        logits = rng.normal(size=(6, 32)).astype(np.float32)
        temperature = [0.0, 0.8, 1.3, 0.8, 0.0, 2.0]
        top_k = [4, 4, 0, 9, 0, 40]
        seed = [1, 1, 2, 1, 5, 0xFFFFFFFF]
        position = [0, 7, 7, 7, 2, 100]
        together = self.draw(logits, temperature, top_k, seed, position)
        for i in range(6):
            alone = self.draw(logits[i:i + 1], temperature[i], top_k[i],
                              seed[i], position[i])
            assert alone.tolist() == [together[i]]
        assert together[0] == np.argmax(logits[0])
        assert together[4] == np.argmax(logits[4])
        # Reordered, and beside other rows, a row draws the same.
        order = [3, 0, 5, 1]
        pick = lambda xs: [xs[i] for i in order]
        assert self.draw(logits[order], pick(temperature), pick(top_k),
                         pick(seed), pick(position)).tolist() \
            == together[order].tolist()

    def test_the_draw_is_keyed_by_seed_and_position(self):
        logits = np.zeros((1, 64), np.float32)
        base = self.draw(logits, 1.0, 0, 9, 4)
        assert self.draw(logits, 1.0, 0, 9, 4).tolist() == base.tolist()
        seeds = self.draw(np.zeros((32, 64), np.float32), 1.0, 0,
                          np.arange(32), 4)
        positions = self.draw(np.zeros((32, 64), np.float32), 1.0, 0, 9,
                              np.arange(32))
        assert len(set(seeds.tolist())) > 8
        assert len(set(positions.tolist())) > 8
        assert positions[4] == base[0] == seeds[9]

    def test_first_token_and_later_ones_come_from_one_stream(
            self, llama_model):
        _, params = llama_model
        eng = InferenceEngine(LCFG, params, page_size=8, max_num_seqs=4,
                              max_model_len=64)
        calls = keep_sampled(eng)
        prompt = [5, 6, 7, 8]
        sampling = SamplingParams(max_new_tokens=6, temperature=0.9,
                                  top_k=20, seed=(1 << 32) + 77)
        (out,) = eng.generate([prompt], sampling)
        assert len(calls) == 6
        for i, (logits, temperature, top_k, seed, position, ids) \
                in enumerate(calls):
            # The row's own position: the prompt's last, then one on.
            assert position.tolist() == [len(prompt) - 1 + i]
            assert seed.tolist() == [77] and top_k.tolist() == [20]
            assert ids.tolist() == [out[i]] == self.draw(
                logits, 0.9, 20, 77, len(prompt) - 1 + i).tolist()
        # A step counts the rows of the sampler it dispatched: the last
        # step fetches the last token and dispatches none.
        assert [s["sampled_stochastic"]
                for s in eng.step_log()["steps"]] == [1] * 6 + [0]

    def test_a_preempted_request_draws_what_it_draws_unpreempted(
            self, llama_model):
        _, params = llama_model
        pa, pb = list(range(1, 8)), list(range(20, 25))
        requests = (("a", pa, SamplingParams(
            max_new_tokens=8, temperature=0.8, top_k=12, seed=3)),
            ("b", pb, SamplingParams(
                max_new_tokens=8, temperature=1.1, seed=4)))

        def run(**kw):
            eng = InferenceEngine(LCFG, params, page_size=4, max_num_seqs=2,
                                  max_model_len=24, **kw)
            for request in requests:
                eng.add_request(*request)
            outs = {"a": [], "b": []}
            while eng.has_unfinished():
                for o in eng.step():
                    outs[o.request_id].append(o.token_id)
            return eng, outs

        # 5 usable pages: the two cannot both stay resident.
        tight, preempted = run(num_pages=6)
        roomy, plain = run()
        assert tight.stats()["num_preemptions"] >= 1
        assert roomy.stats()["num_preemptions"] == 0
        assert preempted == plain
        assert [len(v) for v in plain.values()] == [8, 8]
        # And neither stream is the greedy one.
        greedy = InferenceEngine(LCFG, params, page_size=4, max_num_seqs=2,
                                 max_model_len=24).generate(
            [pa, pb], SamplingParams(max_new_tokens=8))
        assert plain["a"] != greedy[0] and plain["b"] != greedy[1]

    def test_under_a_tp_mesh_a_row_draws_what_one_device_draws(
            self, llama_model):
        _, params = llama_model
        eng = InferenceEngine(LCFG, params, page_size=8, max_num_seqs=4,
                              max_model_len=64, tp=2)
        calls = keep_sampled(eng)
        outs = eng.generate([[5, 6, 7, 8], [9, 10]], SamplingParams(
            max_new_tokens=4, temperature=0.9, top_k=20, seed=6))
        assert len(calls) == 2 + 3 and [len(o) for o in outs] == [4, 4]
        for logits, temperature, top_k, seed, position, ids in calls:
            assert ids.tolist() == self.draw(
                logits, temperature, top_k, seed, position).tolist()

    def test_mixed_batch_counts_its_stochastic_rows(self, llama_model):
        _, params = llama_model
        eng = InferenceEngine(LCFG, params, page_size=8, max_num_seqs=4,
                              max_model_len=64)
        eng.add_request("g", [1, 2, 3], SamplingParams(max_new_tokens=4))
        eng.add_request("s", [4, 5, 6], SamplingParams(
            max_new_tokens=4, temperature=1.0, seed=1))
        eng.add_request("t", [7, 8], SamplingParams(
            max_new_tokens=4, temperature=0.5, top_k=3, seed=2))
        calls = keep_sampled(eng)
        while eng.has_unfinished():
            eng.step()
        steps = eng.step_log()["steps"]
        # A step of three prefills' first tokens (two of them drawn), then
        # three decodes of a bucket of four with two stochastic rows each,
        # and the step that fetches the last one's tokens.
        assert [s["decodes"] for s in steps] == [0, 3, 3, 3, 0]
        assert [s["ahead"] for s in steps] == [0, 0, 1, 1, 0]
        assert [s["sampled_stochastic"] for s in steps] == [2, 2, 2, 2, 0]
        for logits, temperature, *_, ids in calls:
            greedy = temperature <= 0
            assert ids[greedy].tolist() \
                == np.argmax(logits, axis=-1)[greedy].tolist()

    def test_request_rows_are_put_once_a_batch_not_once_a_step(
            self, llama_model):
        _, params = llama_model
        eng = InferenceEngine(LCFG, params, page_size=8, max_num_seqs=4,
                              max_model_len=64)
        made, rows = [], eng._sampling_rows

        def counted(seqs, bucket):
            made.append([s.request_id for s in seqs])
            return rows(seqs, bucket)

        eng._sampling_rows = counted
        eng.add_request("a", [1, 2, 3], SamplingParams(max_new_tokens=9))
        eng.add_request("b", [4, 5], SamplingParams(max_new_tokens=4))
        while eng.has_unfinished():
            eng.step()
        steps = eng.step_log()["steps"]
        assert len([s for s in steps if s["decodes"]]) == 8
        # A prefill's own row each, then once a membership of the batch.
        assert made == [["a"], ["b"], ["a", "b"], ["a"]]
        # The same ids under another request's parameters are another
        # batch: a sequence is known by what it is, not by its name.
        hot = SamplingParams(max_new_tokens=3, temperature=1.0, seed=8)
        cold = SamplingParams(max_new_tokens=3)
        first = eng.generate([[1, 2, 3]], cold)
        again = InferenceEngine(LCFG, params, page_size=8, max_num_seqs=4,
                                max_model_len=64)
        assert again.generate([[1, 2, 3]], hot) \
            == eng.generate([[1, 2, 3]], hot)
        assert eng.generate([[1, 2, 3]], cold) == first


class TestDecodeLaunch:
    """A decode step hands the device what changed since the last one
    (ISSUE 49): every call of the decode program is held to the loop that
    built its inputs before (``kept_tables.watch_decode``), and the step
    record says what was put and what was passed again."""

    def make(self, params, **kw):
        from kept_tables import watch_decode

        kw = {"page_size": 8, "max_num_seqs": 4, "max_model_len": 64, **kw}
        eng = InferenceEngine(LCFG, params, **kw)
        return eng, watch_decode(eng)

    def test_a_table_is_put_again_only_when_a_row_of_it_has_changed(
            self, llama_model):
        model, params = llama_model
        eng, calls = self.make(params)
        out = eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=15))
        assert out[0] == reference_greedy(model, params, [1, 2, 3], 15)
        # Fourteen decodes, at positions 3 to 16: a new batch, then a new
        # page at 8 and at 16. Tokens, positions, dests and context
        # lengths go over every step, the table beside them when it moved.
        reused = [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 1, 1, 0]
        assert calls == [(r, 5 - r) for r in reused]
        steps = [s for s in eng.step_log()["steps"] if s["decodes"]]
        # A step dispatched ahead puts no tokens: they are the ids in
        # flight, where they lie (``watch_decode`` counts them as one).
        assert [s["ahead"] for s in steps] == [0] + [1] * 13
        assert [(s["tables_reused"], s["host_puts"] + s["ahead"])
                for s in steps] == calls
        assert [s["table_width"] for s in steps] == [1] * 5 + [2] * 8 + [4]
        stats = eng.stats()
        assert (stats["table_puts"], stats["table_reuses"]) == (3, 11)
        # A step with no decode says so.
        idle = [s for s in eng.step_log()["steps"] if not s["decodes"]]
        assert idle and all((s["tables_reused"], s["host_puts"]) == (0, 0)
                            for s in idle)

    def test_a_turnover_puts_the_table_again(self, llama_model):
        _, params = llama_model
        eng, calls = self.make(params, page_size=16)
        eng.add_request("a", [1, 2, 3], SamplingParams(max_new_tokens=9))
        eng.add_request("b", [4, 5], SamplingParams(max_new_tokens=4))
        batches = []
        while eng.has_unfinished():
            eng.step()
            batches.append(sorted(s.request_id
                                  for s in eng.scheduler.running))
        # No page's edge is crossed: only the membership moves, once when
        # the two decode together and once when "b" has left.
        assert [r for r, _ in calls] == [0, 1, 1, 0, 1, 1, 1, 1]
        assert ["a"] in batches and ["a", "b"] in batches

    @pytest.mark.parametrize("scenario", ["staggered", "preempted",
                                          "shared_prefix"])
    def test_the_program_is_handed_what_the_loop_built(
            self, llama_model, scenario):
        model, params = llama_model
        prompts = [list(range(1, 8)), list(range(20, 25)),
                   list(range(30, 41))]
        new, options = 8, {"page_size": 4, "max_model_len": 32}
        if scenario == "preempted":
            # 5 usable pages: two growing sequences cannot both stay, and
            # the one that comes back takes whatever row is free.
            prompts, options = prompts[:2], {
                **options, "num_pages": 6, "max_num_seqs": 2,
                "max_model_len": 24}
        if scenario == "shared_prefix":
            prompts = [list(range(1, 14)), list(range(1, 14)) + [50, 51]]
        eng, calls = self.make(params, **options)
        sampling = SamplingParams(max_new_tokens=new)
        if scenario == "preempted":
            outs = eng.generate(prompts, sampling)
            assert eng.stats()["num_preemptions"] >= 1
        else:
            # One after another has started: the later graft the first's
            # prompt pages where they share them.
            outs = {f"r{i}": [] for i in range(len(prompts))}
            for i, prompt in enumerate(prompts):
                eng.add_request(f"r{i}", prompt, sampling)
                for _ in range(2):
                    for o in eng.step():
                        outs[o.request_id].append(o.token_id)
            while eng.has_unfinished():
                for o in eng.step():
                    outs[o.request_id].append(o.token_id)
            outs = list(outs.values())
        if scenario == "shared_prefix":
            assert eng.prefix_cache.stats()["hit_tokens"] > 0
        for prompt, out in zip(prompts, outs):
            assert out == reference_greedy(model, params, prompt, new)
        assert {r for r, _ in calls} == {0, 1}
        assert all(puts == 5 - r for r, puts in calls)
        assert eng.cache.free_pages() == eng.cache.total_pages


class _OneAtATime(InferenceEngine):
    """The engine with nothing dispatched ahead: the decode in flight is
    fetched before the next is built, from the host's tokens then. What
    a step in flight is held to."""

    def _run_decode(self, seqs, out):
        n = self._drain(out) if self._flight is not None else 0
        seqs = [s for s in seqs if s.state == "running"]  # a stop token
        return n + (super()._run_decode(seqs, out) if seqs else 0)


def _ahead_families():
    from raytpu.models.mixtral import Lfm2Moe, Lfm2MoeConfig, Mellum

    mellum, lfm2 = (dataclasses.replace(c.tiny(), paged_attn="reference",
                                        **_F32)
                    for c in (MellumConfig, Lfm2MoeConfig))
    return {
        # A dense family, two kinds of pool, and layers that keep a state.
        "dense": (LCFG, lambda: llama_init(Llama(LCFG), LCFG, seed=0,
                                           batch=1)),
        "two_kinds": (mellum, lambda: mixtral_init(Mellum(mellum), mellum,
                                                   seed=1)),
        "state": (lfm2, lambda: mixtral_init(Lfm2Moe(lfm2), lfm2, seed=1,
                                             batch=1)),
    }


AHEAD = _ahead_families()


@pytest.fixture(scope="module", params=sorted(AHEAD))
def ahead_family(request):
    cfg, init = AHEAD[request.param]
    return cfg, init()


class TestDecodeAhead:
    """One decode in flight (ISSUE 52): a step dispatches its decode
    before the ids of the step before have come back, and what it gives
    out is what decoding one step at a time gives, whatever ends a
    sequence meanwhile."""

    # One decode bucket, as a serving cell pins it: the ids in flight are
    # handed over on the device whatever joins or leaves.
    OPTIONS = dict(page_size=4, max_num_seqs=4, max_model_len=64,
                   decode_buckets=[4])

    @staticmethod
    def sampling(seed, **kw):
        # Drawn, so that a tiny seeded model's rows are many tokens.
        return SamplingParams(temperature=1.0, seed=seed, **kw)

    @staticmethod
    def prompts(cfg, *lengths):
        rng = np.random.default_rng(3)
        return [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
                for n in lengths]

    def alone(self, family, prompt, sampling, **options):
        """The request decoded alone and with nothing ahead: its tokens
        and why it ended."""
        cfg, params = family
        eng = _OneAtATime(cfg, params, **{**self.OPTIONS, **options})
        seq = eng.add_request("alone", prompt, sampling)
        while eng.has_unfinished():
            eng.step()
        assert eng.stats()["decodes_ahead"] == 0
        return list(seq.generated), seq.finish_reason

    @staticmethod
    def watch(eng):
        """``(batch dispatched, batch in flight before)`` a call of
        ``_run_decode``, and every call of ``scheduler.finish``."""
        calls, finished = [], []
        run, finish = eng._run_decode, eng.scheduler.finish

        def noted(seqs, out):
            flight = eng._flight
            calls.append(([s.request_id for s in seqs], flight and [
                s.request_id for s in flight.seqs]))
            return run(seqs, out)

        def ended(seq, reason):
            finished.append((seq.request_id, reason))
            return finish(seq, reason)

        eng._run_decode, eng.scheduler.finish = noted, ended
        return calls, finished

    @staticmethod
    def run(eng, outs, steps=None):
        n = 0
        while eng.has_unfinished() and (steps is None or n < steps):
            for o in eng.step():
                outs.setdefault(o.request_id, []).append(o.token_id)
            n += 1

    @staticmethod
    def holds(eng, calls):
        """The records say what happened: a decode went out ahead where
        one was in flight, whatever its batch, and nowhere else; and
        nothing is left behind."""
        decoded = [s for s in eng.step_log()["steps"] if s["decodes"]]
        assert len(decoded) == len(calls)
        for step, (batch, flight) in zip(decoded, calls):
            assert step["ahead"] == (flight is not None), (batch, flight)
            assert not (step["ahead"] and step["preempted"])
        # The hand-over of a bucket was entered with its first decode.
        assert eng.stats()["carry_compiles"] == {
            str(s["bucket"]): 1 for s in decoded}
        stats = eng.stats()
        assert stats["decodes_ahead"] == sum(s["ahead"] for s in decoded)
        assert stats["decodes_ahead"] + stats["decodes_drained"] \
            == len(decoded)
        assert stats["ahead_rows_dropped"] == sum(
            s["ahead_rows_dropped"] for s in eng.step_log()["steps"])
        assert eng._flight is None and not eng.scheduler.running
        assert eng.cache.free_pages() == eng.cache.total_pages
        assert eng.cache.seats_in_use() == 0
        TestDecodeAhead.ordinals_hold(eng.step_log()["steps"], calls)
        return stats

    @staticmethod
    def ordinals_hold(steps, calls):
        """A record says which decode each of its numbers is of (ISSUE
        57): ``dispatched`` counts the decodes dispatched, from 1; every
        decode is fetched once, by a later record; and ``carried`` is 1
        exactly where the batch in flight was another than the step's."""
        decoded = [s for s in steps if s["decodes"]]
        assert [s["dispatched"] for s in decoded] \
            == list(range(1, len(decoded) + 1))
        assert all(s["dispatched"] == 0 for s in steps if not s["decodes"])
        fetched = [s["fetched"] for s in steps if s["fetched"]]
        assert fetched == list(range(1, len(decoded) + 1))
        at = {s["dispatched"]: i for i, s in enumerate(steps)
              if s["dispatched"]}
        for i, s in enumerate(steps):
            if s["fetched"]:
                assert at[s["fetched"]] < i  # an earlier record's decode
            if s["ahead"]:  # behind the decode in flight, fetched here
                assert s["fetched"] == s["dispatched"] - 1
            assert 0 <= s["wait_cpu_s"] <= s["cpu_s"] \
                <= s["end"] - s["start"] if "wait_cpu_s" in s \
                else 0 <= s["cpu_s"] <= s["end"] - s["start"]
        for step, (batch, flight) in zip(decoded, calls):
            assert step["carried"] == (flight is not None
                                       and flight != batch), (batch, flight)
        assert all(s["carried"] == 0 for s in steps if not s["decodes"])

    def test_a_stop_token_drops_the_row_in_flight(self, ahead_family):
        cfg, params = ahead_family
        short, long = self.prompts(cfg, 6, 9)
        free, _ = self.alone(ahead_family, short,
                             self.sampling(5, max_new_tokens=12))
        # A token it has not given before, a few steps in.
        at = next(i for i in range(3, 11) if free[i] not in free[:i])
        stops = self.sampling(5, max_new_tokens=12,
                              stop_token_ids=(free[at],))
        other = self.sampling(6, max_new_tokens=12)
        eng = InferenceEngine(cfg, params, **self.OPTIONS)
        calls, finished = self.watch(eng)
        eng.add_request("stops", short, stops)
        eng.add_request("other", long, other)
        outs = {}
        self.run(eng, outs)
        assert outs["stops"] == free[:at + 1]
        assert outs["other"] == self.alone(ahead_family, long, other)[0]
        # Ended once, its pages freed then; the row that was in flight
        # behind its last token emitted nothing.
        assert sorted(finished) == [("other", "length"), ("stops", "stop")]
        stats = self.holds(eng, calls)
        assert stats["ahead_rows_dropped"] == 1
        both = [i for i, (batch, _) in enumerate(calls)
                if batch == ["stops", "other"]]
        assert len(both) == at + 1  # one row more than it was given tokens
        # Ahead on every step but the first, also where the batch moved:
        # the other's token came from its row in flight, on the device.
        decoded = [s for s in eng.step_log()["steps"] if s["decodes"]]
        assert [s["ahead"] for s in decoded] == [0] + [1] * (len(calls) - 1)
        assert calls[both[-1] + 1] == (["other"], ["stops", "other"])

    def test_an_abort_between_two_steps(self, ahead_family):
        cfg, params = ahead_family
        kept, gone, late = self.prompts(cfg, 7, 5, 8)
        samplings = [self.sampling(s, max_new_tokens=10) for s in (1, 2, 3)]
        eng = InferenceEngine(cfg, params, **{**self.OPTIONS,
                                              "max_num_seqs": 2})
        calls, finished = self.watch(eng)
        eng.add_request("kept", kept, samplings[0])
        eng.add_request("gone", gone, samplings[1])
        outs = {}
        self.run(eng, outs, steps=4)
        assert eng._flight is not None and len(outs["gone"]) == 3
        seat = eng.cache.seat("gone") if eng.cache.total_seats else None
        assert eng.abort("gone")
        # Its pages and its seat go to the next request at once, while
        # the row it had in flight is still to run.
        eng.add_request("late", late, samplings[2])
        self.run(eng, outs, steps=1)
        if seat is not None:
            assert eng.cache.seat("late") == seat
        self.run(eng, outs)
        alone = [self.alone(ahead_family, p, s, max_num_seqs=2)[0]
                 for p, s in zip((kept, gone, late), samplings)]
        assert outs["kept"] == alone[0] and outs["late"] == alone[2]
        assert outs["gone"] == alone[1][:3]
        assert finished.count(("gone", "aborted")) == 1
        stats = self.holds(eng, calls)
        assert stats["ahead_rows_dropped"] == 1
        decoded = [s for s in eng.step_log()["steps"] if s["decodes"]]
        assert [s["ahead"] for s in decoded] == [0] + [1] * (len(calls) - 1)
        assert (["kept"], ["kept", "gone"]) in calls

    def test_one_joins_and_one_leaves_in_neighbouring_steps(
            self, ahead_family):
        cfg, params = ahead_family
        prompts = self.prompts(cfg, 5, 7, 6)
        samplings = [self.sampling(11, max_new_tokens=14),
                     self.sampling(12, max_new_tokens=5),
                     self.sampling(13, max_new_tokens=6)]
        eng = InferenceEngine(cfg, params, **self.OPTIONS)
        calls, _ = self.watch(eng)
        outs = {}
        eng.add_request("stays", prompts[0], samplings[0])
        eng.add_request("leaves", prompts[1], samplings[1])
        self.run(eng, outs, steps=3)
        # Prefilled in the step that fetches ``leaves``' last token but
        # one: it joins as the other leaves by length.
        eng.add_request("joins", prompts[2], samplings[2])
        self.run(eng, outs)
        for name, prompt, sampling in zip(("stays", "leaves", "joins"),
                                          prompts, samplings):
            assert outs[name] == self.alone(ahead_family, prompt,
                                            sampling)[0], name
        stats = self.holds(eng, calls)
        assert stats["ahead_rows_dropped"] == 0
        batches = [batch for batch, _ in calls]
        assert ["stays", "leaves"] in batches \
            and ["stays", "joins"] in batches and ["stays"] in batches
        # A sequence that leaves by length is left out a step early: it
        # is given no row beyond its last token.
        assert sum("leaves" in b for b in batches) == 4
        assert sum("joins" in b for b in batches) == 5

    def test_a_preemption_drains_first(self, ahead_family):
        cfg, params = ahead_family
        prompts = self.prompts(cfg, 7, 5)
        samplings = [self.sampling(21, max_new_tokens=10),
                     self.sampling(22, max_new_tokens=10)]
        # Five usable pages of four: two growing sequences cannot both
        # stay.
        options = dict(num_pages=6, max_num_seqs=2, max_model_len=24)
        eng = InferenceEngine(cfg, params, **{**self.OPTIONS, **options})
        calls, _ = self.watch(eng)
        for i, (prompt, sampling) in enumerate(zip(prompts, samplings)):
            eng.add_request(f"r{i}", prompt, sampling)
        resumed, outs = [], {}
        while eng.has_unfinished():
            known = {s.request_id: s.num_tokens
                     for s in eng.scheduler.waiting if s.generated}
            for o in eng.step():
                outs.setdefault(o.request_id, []).append(o.token_id)
            record = eng.step_log()["steps"][-1]
            if record["preempted"]:
                # Drained before the scheduler preempted: nothing was in
                # flight for the victim, and the step's own decode was
                # built from the host's tokens.
                assert record["ahead"] == 0
                assert all(s.in_flight == 0 and s.cached_len == 0
                           for s in eng.scheduler.waiting)
            resumed += [(p, known[p["request_id"]])
                        for p in record.get("prefills", ())
                        if p["request_id"] in known]
        assert eng.stats()["num_preemptions"] >= 1 and resumed
        # The resumed sequence re-prefills every token whose KV was
        # written: all it has but the newest, which its next decode
        # writes (from ``start`` where the prefix cache still held its
        # prompt's pages).
        assert all(p.get("start", 0) + p["tokens"] == tokens - 1
                   for p, tokens in resumed)
        for i, (prompt, sampling) in enumerate(zip(prompts, samplings)):
            assert outs[f"r{i}"] == self.alone(
                ahead_family, prompt, sampling, **options)[0]
        stats = self.holds(eng, calls)
        assert stats["ahead_rows_dropped"] == 0 and stats["decodes_ahead"]

    def test_max_model_len_ends_a_sequence(self, ahead_family):
        cfg, params = ahead_family
        prompts = self.prompts(cfg, 9, 5)
        sampling = self.sampling(31, max_new_tokens=40)
        # Buckets of 1, 2, 4 here: when the batch falls from two to one
        # the ids in flight are another bucket's, and are fetched first.
        options = dict(max_model_len=16, decode_buckets=None)
        eng = InferenceEngine(cfg, params, **{**self.OPTIONS, **options})
        calls, finished = self.watch(eng)
        eng.add_request("long", prompts[0], sampling)
        eng.add_request("short", prompts[1], sampling)
        outs = {}
        self.run(eng, outs)
        assert [len(outs[k]) for k in ("long", "short")] == [7, 11]
        for name, prompt in zip(("long", "short"), prompts):
            assert (outs[name], "length") == self.alone(
                ahead_family, prompt, sampling, **options), name
        assert sorted(finished) == [("long", "length"), ("short", "length")]
        stats = self.holds(eng, calls)
        assert stats["ahead_rows_dropped"] == 0
        # A row a token after the prefill's, and none past the model's
        # length; the last token comes from a step that dispatches none.
        batches = [batch for batch, _ in calls]
        assert sum("long" in b for b in batches) == 6
        assert sum("short" in b for b in batches) == 10
        last = eng.step_log()["steps"][-1]
        assert last["decodes"] == 0 and last["ahead"] == 0
        decoded = [s for s in eng.step_log()["steps"] if s["decodes"]]
        assert [s["bucket"] for s in decoded] == [2] * 6 + [1] * 4
        assert [s["ahead"] for s in decoded] == [0] + [1] * 5 + [0] + [1] * 3
        assert calls[6] == (["short"], None)

    def test_a_record_names_the_decode_it_dispatched_and_the_one_it_fetched(
            self, ahead_family):
        """A joiner, a leaver and a drain (the batch falls to another
        bucket): the ordinals pair every number of a record with its
        decode, whatever the batch did."""
        cfg, params = ahead_family
        prompts = self.prompts(cfg, 5, 7, 6)
        samplings = [self.sampling(41, max_new_tokens=12),
                     self.sampling(42, max_new_tokens=4),
                     self.sampling(43, max_new_tokens=5)]
        eng = InferenceEngine(cfg, params, **{**self.OPTIONS,
                                              "decode_buckets": None})
        calls, _ = self.watch(eng)
        outs = {}
        eng.add_request("stays", prompts[0], samplings[0])
        eng.add_request("leaves", prompts[1], samplings[1])
        self.run(eng, outs, steps=3)
        eng.add_request("joins", prompts[2], samplings[2])
        self.run(eng, outs)
        self.holds(eng, calls)
        steps = eng.step_log()["steps"]
        decoded = [s for s in steps if s["decodes"]]
        # The batch moved inside a bucket (carried), fell to another
        # (drained: fetched in a step that dispatches from the host's
        # tokens), and the last token came from a step that dispatched
        # nothing.
        assert {s["carried"] for s in decoded} == {0, 1}
        drained = [s for s in decoded[1:] if not s["ahead"]]
        assert drained and all(s["fetched"] == s["dispatched"] - 1
                               and not s["carried"] for s in drained)
        assert (steps[-1]["dispatched"], steps[-1]["fetched"]) \
            == (0, len(decoded))

    def test_a_drafting_engine_fetches_the_decode_it_dispatched(self):
        from raytpu.models.mixtral import ExaoneMoe, ExaoneMoeConfig

        cfg = dataclasses.replace(ExaoneMoeConfig.tiny(),
                                  experts_held=(2, 4),
                                  paged_attn="reference", choice_bias=0.05,
                                  **_F32)
        params = mixtral_init(ExaoneMoe(cfg), cfg, seed=1, batch=1)
        eng = InferenceEngine(cfg, params, page_size=4, max_num_seqs=4,
                              max_model_len=96)
        assert eng._drafting is not None
        for i, prompt in enumerate(self.prompts(cfg, 9, 6)):
            eng.add_request(f"r{i}", prompt,
                            self.sampling(50 + i, max_new_tokens=7 + i))
        self.run(eng, {})
        steps = eng.step_log()["steps"]
        decoded = [s for s in steps if s["decodes"]]
        assert decoded and len(decoded) + 1 >= len(steps) - 1
        assert [(s["dispatched"], s["fetched"]) for s in decoded] \
            == [(n, n) for n in range(1, len(decoded) + 1)]
        assert all((s["dispatched"], s["fetched"], s["carried"],
                    s["ahead"]) == (0, 0, 0, 0)
                   for s in steps if not s["decodes"])
        assert all(s["carried"] == 0 and 0 <= s["wait_cpu_s"] <= s["cpu_s"]
                   for s in decoded)

    def test_tokens_go_to_the_program_in_one_form(self, llama_model):
        """The host's tokens of a drained step are put first, so the
        decode program's cache holds one entry a bucket and width
        whether its tokens came from the host or from the step in
        flight."""
        _, params = llama_model
        eng = InferenceEngine(LCFG, params, **{**self.OPTIONS,
                                               "page_size": 16})
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
        assert eng.stats()["decodes_ahead"] == 0
        entries = eng._decode_fn._cache_size()
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=4))
        assert eng.stats()["decodes_ahead"] == 2
        assert eng._decode_fn._cache_size() == entries
        assert sum(eng.stats()["decode_compiles"].values()) == 1


class TestStepLog:
    """One record per step, its phases live spans (ISSUE 24)."""

    def make_engine(self, params, **kw):
        kw.setdefault("page_size", 8)
        kw.setdefault("max_num_seqs", 4)
        kw.setdefault("max_model_len", 64)
        return InferenceEngine(LCFG, params, **kw)

    @staticmethod
    def phases(step, name):
        return [p for p in step["phases"] if p[0] == name]

    def test_phases_are_ordered_nest_in_the_step_and_tile_the_decode(
            self, llama_model):
        _, params = llama_model
        eng = self.make_engine(params)
        eng.generate([[1, 2, 3], [4, 5, 6, 7]],
                     SamplingParams(max_new_tokens=4))
        eng.generate([[1, 2, 3], [4, 5, 6, 7]],
                     SamplingParams(max_new_tokens=4))  # warm: no compile
        steps = eng.step_log()["steps"]
        first = steps[0]
        assert [p[0] for p in first["phases"]] == [
            "infer.schedule", "infer.prefill", "infer.prefill"]
        assert first["admitted"] == 2 and first["decodes"] == 0
        assert [(p["tokens"], p["bucket"]) for p in first["prefills"]] \
            == [(3, 16), (4, 16)]
        decoded = [s for s in steps if s["decodes"]]
        assert decoded and all(s["compiled"] == 0 for s in decoded[-3:])
        # A batch's first decode has none in flight to fetch, and its
        # last tokens are fetched by a step that dispatches nothing.
        assert [s["ahead"] for s in decoded[-3:]] == [0, 1, 1]
        assert steps[-1]["decodes"] == 0 and [
            p[0] for p in steps[-1]["phases"]] == [
            "infer.schedule", "infer.decode", "infer.decode.wait",
            "infer.decode.sample"]
        for step in decoded[-3:]:
            assert [p[0] for p in step["phases"]] == [
                "infer.schedule", "infer.decode", "infer.decode.launch",
                "infer.decode.wait", "infer.decode.sample"]
            last = step["start"]
            for name, t0, t1 in step["phases"]:
                assert step["start"] <= t0 <= t1 <= step["end"], name
                assert t0 >= last, name  # in order of their start
                last = t0
            (dec,) = self.phases(step, "infer.decode")
            parts = [self.phases(step, f"infer.decode.{k}")[0]
                     for k in ("launch", "wait", "sample")]
            assert dec[1] <= parts[0][1] and parts[-1][2] <= dec[2]
            for a, b in zip(parts, parts[1:]):
                assert a[2] <= b[1]
            covered = sum(t1 - t0 for _, t0, t1 in parts)
            assert 0 <= (dec[2] - dec[1]) - covered < 1e-3
            assert step["decodes"] == 2 and step["bucket"] == 2
            assert step["table_width"] == 1 and step["live_pages"] == 2
        for a, b in zip(steps, steps[1:]):
            assert a["end"] <= b["start"]

    def test_decode_span_contains_the_host_fetch(self, llama_model):
        from raytpu.util import tracing

        _, params = llama_model
        eng = self.make_engine(params)
        tracing.clear_spans()
        tracing.enable_tracing()
        try:
            eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
            spans = tracing.get_spans()
        finally:
            tracing.disable_tracing()
            tracing.clear_spans()
        by_id = {s["span_id"]: s for s in spans}
        waits = [s for s in spans if s["name"] == "infer.decode.wait"]
        # Two decodes: the first fetches nothing, the second the first's
        # ids, and a step that dispatches none the second's.
        assert len(waits) == 3
        fetching = [s for s in eng.step_log()["steps"]
                    if self.phases(s, "infer.decode.wait")]
        assert [(s["dispatched"], s["fetched"]) for s in fetching] \
            == [(1, 0), (2, 1), (0, 2)]
        for wait in waits:
            dec = by_id[wait["parent_span_id"]]
            assert dec["name"] == "infer.decode"
            assert dec["duration_s"] >= wait["duration_s"] > 0
            assert dec["t0"] <= wait["t0"]
            # What comes back is the batch's token ids, not its logits.
            assert dec["attributes"].get("bucket", 1) == 1
            assert by_id[dec["parent_span_id"]]["name"] == "infer.step"
        step = [s for s in spans if s["name"] == "infer.step"][-1]
        assert {"decodes", "bucket", "table_width", "live_pages", "compiled",
                "preempted", "admitted"} <= set(step["attributes"])

    def test_step_log_since_filters_and_reports_the_oldest_start(
            self, llama_model):
        _, params = llama_model
        eng = self.make_engine(params)
        empty = eng.step_log()
        assert (empty["oldest_start"], empty["steps"]) == (None, [])
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=5))
        log = eng.step_log()
        # The prefill, four decodes, and the last one's fetch.
        assert len(log["steps"]) == 6
        assert log["oldest_start"] == log["steps"][0]["start"]
        cut = log["steps"][2]["end"]
        later = eng.step_log(since=cut)
        assert [s["start"] for s in later["steps"]] \
            == [s["start"] for s in log["steps"][3:]]
        assert later["oldest_start"] == log["oldest_start"]

    def test_ring_and_decode_batch_hist_stay_bounded(self, llama_model):
        from raytpu.util import tracing

        _, params = llama_model
        eng = self.make_engine(params)
        assert eng.recorder._ring.maxlen == 4096
        # The same ring at a size a test can overrun.
        eng.recorder = tracing.StepRecorder(maxlen=16)
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=27))
        log = eng.step_log()
        assert len(eng.recorder) == len(log["steps"]) == 16  # of 16 + 12
        assert log["oldest_start"] == log["steps"][0]["start"]
        # The last step fetches the last token and dispatches nothing.
        assert eng.stats()["decode_batch_hist"] == [1] * 15
        assert eng.stats()["decode_tokens"] == 26  # the totals are not cut

    def test_compiled_counts_the_first_step_of_a_bucket_only(
            self, llama_model):
        _, params = llama_model
        eng = self.make_engine(params)
        eng.add_request("a", [1, 2, 3], SamplingParams(max_new_tokens=12))
        for _ in range(3):
            eng.step()
        prefill, first, second = eng.step_log()["steps"]
        # A program and the sampler over its logits' shape; with a
        # bucket's first decode the hand-over of its ids in flight.
        assert prefill["compiled"] == 2 and first["compiled"] == 3
        assert second["compiled"] == 0
        # A second sequence joins: a new batch bucket, compiled once.
        eng.add_request("b", [4, 5, 6], SamplingParams(max_new_tokens=4))
        for _ in range(3):
            eng.step()
        admitted, joined, after = eng.step_log()["steps"][-3:]
        # Its prefill's program is warm; it decodes from the next step.
        assert admitted["decodes"] == 1 and admitted["compiled"] == 0
        # Ids in flight at one bucket are fetched before a decode at
        # another: no program hands them over.
        assert joined["decodes"] == 2 and joined["compiled"] == 3
        assert (admitted["ahead"], joined["ahead"], after["ahead"]) \
            == (1, 0, 1)
        assert eng.stats()["carry_compiles"] == {"1": 1, "2": 1}
        assert after["decodes"] == 2 and after["compiled"] == 0
        assert sum(s["compiled"] for s in eng.step_log()["steps"]) == sum(
            sum(eng.stats()[k].values()) for k in (
                "prefill_compiles", "chunk_prefill_compiles",
                "decode_compiles", "sample_compiles", "carry_compiles"))

    def test_waited_s_covers_the_time_behind_a_full_batch(self, llama_model):
        import time

        _, params = llama_model
        eng = self.make_engine(params, max_num_seqs=1)
        eng.add_request("first", [1, 2, 3], SamplingParams(max_new_tokens=4))
        eng.add_request("behind", [4, 5, 6], SamplingParams(max_new_tokens=2))
        queued = time.perf_counter()
        time.sleep(0.05)
        sat = None
        while eng.has_unfinished():
            eng.step()
            step = eng.step_log()["steps"][-1]
            for p in step.get("prefills", ()):
                if p["request_id"] == "behind":
                    sat = step["phases"][1][1] - queued  # its prefill's t0
                    waited = p["waited_s"]
        assert sat is not None and sat >= 0.05
        assert waited >= sat
        first = eng.step_log()["steps"][0]["prefills"][0]
        assert first["request_id"] == "first"
        assert 0 <= first["waited_s"] < waited

    def test_preempted_is_counted_in_the_step_that_preempts(
            self, llama_model):
        _, params = llama_model
        # 4 usable pages of 4 tokens: two sequences outgrow the pool.
        eng = self.make_engine(params, page_size=4, num_pages=5,
                               max_num_seqs=2, max_model_len=16)
        eng.generate([[1, 2, 3], [4, 5, 6]],
                     SamplingParams(max_new_tokens=8))
        steps = eng.step_log()["steps"]
        assert sum(s["preempted"] for s in steps) \
            == eng.stats()["num_preemptions"] > 0
        resumed = [p for s in steps for p in s.get("prefills", ())
                   if "waited_s" not in p]
        assert resumed  # a resume prefill has no arrival to wait from

    def test_profile_region_shows_the_phases_on_the_profilers_clock(
            self, llama_model, tmp_path):
        """``tracing.profile()``: the xplane's host lines hold the
        engine's phases as events, beside whatever the device ran."""
        import glob

        from raytpu.util import tracing

        try:
            from jax.profiler import ProfileData
        except ImportError:
            pytest.skip("this jax cannot read an xplane (no ProfileData)")
        _, params = llama_model
        eng = self.make_engine(params)
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))  # warm
        with tracing.profile(str(tmp_path), host_tracer_level=1):
            eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        names = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("infer."):
                            names.setdefault(ev.name, []).append(
                                (ev.start_ns, ev.duration_ns))
        # The prefill, two decodes (the first had none in flight to
        # emit), and the second one's fetch.
        assert len(names["infer.step"]) == 4
        assert len(names["infer.decode.sample"]) == 3
        assert {"infer.schedule", "infer.prefill", "infer.decode",
                "infer.decode.launch", "infer.decode.wait"} <= set(names)
        # On one clock: every sample event lies inside a step event.
        for start, dur in names["infer.decode.sample"]:
            assert any(s <= start and start + dur <= s + d
                       for s, d in names["infer.step"])


class TestEngineGPT2:
    def test_batched_greedy_matches_reference(self, gpt2_model):
        model, params = gpt2_model
        eng = InferenceEngine(GCFG, params, page_size=8, max_num_seqs=4,
                              max_model_len=64)
        pa, pb = list(range(1, 10)), [11, 12]
        outs = eng.generate([pa, pb], SamplingParams(max_new_tokens=6))
        assert outs[0] == reference_greedy(model, params, pa, 6)
        assert outs[1] == reference_greedy(model, params, pb, 6)
        assert max(eng.stats()["decode_batch_hist"]) >= 2

    def _stranger(self, **namespace):
        """GCFG's values in a config class of this test's own, no kin of
        ``GPT2Config`` and unknown to ``raytpu.inference``."""
        cls = dataclasses.make_dataclass(
            "StrangerConfig",
            [(f.name, f.type) for f in dataclasses.fields(GPT2Config)],
            frozen=True, namespace=namespace)
        cfg = cls(**{f.name: getattr(GCFG, f.name)
                     for f in dataclasses.fields(GPT2Config)})
        assert not isinstance(cfg, GPT2Config)
        return cfg

    def test_engine_serves_a_config_by_its_description_alone(
            self, gpt2_model):
        # The description is built here from GPT-2's entry points; each
        # says when it is traced, so what the engine ran is known.
        model, params = gpt2_model
        traced = []

        def said(name):
            fwd = getattr(gpt2_mod, name)
            return lambda *a: traced.append(name) or fwd(*a)

        cfg = self._stranger(serving=property(lambda c: gpt2_mod.Serving(
            said("gpt2_prefill"), said("gpt2_step"),
            gpt2_mod.serving_params,
            kv_heads=c.n_head, head_dim=c.n_embd // c.n_head)))
        eng = InferenceEngine(cfg, params, page_size=8, max_num_seqs=4,
                              max_model_len=64, prefill_chunk=8)
        pa, pb = list(range(1, 14)), [11, 12]  # pa: two chunks
        outs = eng.generate([pa, pb], SamplingParams(max_new_tokens=6))
        assert outs[0] == reference_greedy(model, params, pa, 6)
        assert outs[1] == reference_greedy(model, params, pb, 6)
        assert {"gpt2_prefill", "gpt2_step"} == set(traced)
        # Once a bucket; the sampler over their logits is the engine's,
        # and the hand-over of the ids in flight where the batch moved.
        assert len(traced) == eng._programs_traced() - sum(
            sum(eng.stats()[k].values())
            for k in ("sample_compiles", "carry_compiles"))
        assert eng.stats()["expert_tokens"] is None

    def test_a_config_that_cannot_say_how_it_is_served_is_refused(
            self, gpt2_model):
        _, params = gpt2_model
        with pytest.raises(TypeError) as err:
            InferenceEngine(self._stranger(), params, page_size=8)
        said = str(err.value)
        assert "StrangerConfig does not say how it is served" in said
        assert "`serving`" in said
        # What is missing, not a list of the families the tree has.
        assert not re.search("GPT2|Llama|Mixtral|Olmoe", said)


# ---------------------------------------------------------------------------
# The working copy: float32 parameters under bf16 compute are cast once,
# when the engine is built, by the family's serving_params; the programs
# take that tree and convert no weight.
# ---------------------------------------------------------------------------

BF16 = {
    "gpt2": (dataclasses.replace(GPT2Config.tiny(), attn_impl="reference",
                                 paged_attn="reference", remat=False),
             GPT2, gpt2_init, gpt2_mod),
    "llama": (dataclasses.replace(LlamaConfig.tiny(), attn_impl="reference",
                                  paged_attn="reference", remat=False),
              Llama, llama_init, llama_mod),
}


@pytest.fixture(scope="module", params=sorted(BF16))
def bf16_family(request):
    """(module, bf16-compute config, float32 parameters) of a family."""
    cfg, model_cls, init, mod = BF16[request.param]
    assert cfg.dtype == jnp.bfloat16
    params = init(model_cls(cfg), cfg, seed=0, batch=1)
    assert {a.dtype for a in jax.tree_util.tree_leaves(params)} \
        == {jnp.dtype(jnp.float32)}
    return mod, cfg, params


def _weight_shapes(params):
    """Shapes of the matmul kernels and embeddings, stacked and as one
    layer's slice. (A stacked bias has the shape of a stacked norm scale,
    so shape cannot tell those two apart; their dtypes are checked by
    name.)"""
    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if path[-1].key in ("kernel", "embedding"):
            shapes.add(leaf.shape)
            if path[0].key in ("h", "layers"):  # stacked over the layers
                shapes.add(leaf.shape[1:])
    return shapes


def _f32_to_bf16_converts(text):
    """Shapes of every float32 -> bf16 ``convert`` of a lowered program."""
    found = re.findall(
        r"stablehlo\.convert [^\n]*\(tensor<([0-9x]+)xf32>\) -> "
        r"tensor<\1xbf16>", text)
    return {tuple(int(n) for n in dims.split("x")) for dims in found}


class TestServingParams:
    def _programs(self, mod, cfg):
        """Each forward under jit with inputs like the engine's: a filled
        pool of 5 pages of 8 and two 4-token rows at positions 9..12."""
        prefix = "gpt2" if mod is gpt2_mod else "llama"
        kv = getattr(cfg, "n_kv_head", cfg.n_head)
        rng = np.random.default_rng(0)
        pools = [[jnp.asarray(rng.standard_normal(
            (5, 8, kv * (cfg.n_embd // cfg.n_head))), cfg.dtype)
            for _ in range(cfg.n_layer)] for _ in range(2)]
        tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 16)),
                             jnp.int32)
        positions = jnp.arange(9, 13, dtype=jnp.int32)
        dests = 16 + positions  # page 2, after page 1's eight slots
        table = jnp.asarray([[1, 2]], jnp.int32)
        return {
            "prefill": lambda p: getattr(mod, f"{prefix}_prefill")(
                cfg, p, tokens, 8 + jnp.arange(16), *pools),
            "chunk": lambda p: getattr(mod, f"{prefix}_step")(
                cfg, p, tokens[:, :4], positions[None], dests[None], table,
                *pools),
            "decode": lambda p: getattr(mod, f"{prefix}_step")(
                cfg, p, tokens[0, :4, None], positions[:, None],
                dests[:, None], jnp.tile(table, (4, 1)), *pools),
        }

    @pytest.mark.parametrize("program", ["prefill", "chunk", "decode"])
    def test_working_copy_gives_the_same_bits(self, bf16_family, program):
        mod, cfg, params = bf16_family
        fwd = jax.jit(self._programs(mod, cfg)[program])
        working = mod.serving_params(cfg, params)
        by_dtype = {str(a.dtype) for a in jax.tree_util.tree_leaves(working)}
        assert by_dtype == {"bfloat16", "float32"}
        want = jax.tree_util.tree_leaves(fwd(params))
        got = jax.tree_util.tree_leaves(fwd(working))
        assert len(got) == len(want) == 1 + 2 * cfg.n_layer
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            # The same bits, not close ones: the operands are the same.
            assert np.array_equal(np.asarray(g.astype(jnp.float32)),
                                  np.asarray(w.astype(jnp.float32)))
        assert np.isfinite(np.asarray(got[0])).all() and np.asarray(
            got[0]).std() > 0

    def test_norm_leaves_are_not_cast_and_the_rest_are(self, bf16_family):
        mod, cfg, params = bf16_family
        flat, _ = jax.tree_util.tree_flatten_with_path(
            mod.serving_params(cfg, params))
        for path, leaf in flat:
            keys = [k.key for k in path]
            is_norm = any("norm" in k or k.startswith("ln_") for k in keys)
            assert leaf.dtype == (jnp.float32 if is_norm else jnp.bfloat16), \
                keys
        assert any(l.dtype == jnp.float32 for _, l in flat)

    def test_a_tree_in_the_compute_type_is_passed_through(self, bf16_family):
        mod, cfg, params = bf16_family
        # float32 compute (every other test of this file), and a bf16
        # tree under bf16 compute: the same arrays come back, no copy.
        f32_cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        working = mod.serving_params(cfg, params)
        for c, tree in ((f32_cfg, params), (cfg, working)):
            again = mod.serving_params(c, tree)
            for a, b in zip(jax.tree_util.tree_leaves(again),
                            jax.tree_util.tree_leaves(tree)):
                assert a is b

    def test_abstract_leaves_change_dtype_only(self, bf16_family):
        # perfbench's AOT compile builds an engine on shapes alone.
        mod, cfg, params = bf16_family
        shapes = jax.eval_shape(lambda: params)
        working = mod.serving_params(cfg, params)
        for a, b in zip(
                jax.tree_util.tree_leaves(mod.serving_params(cfg, shapes)),
                jax.tree_util.tree_leaves(working)):
            assert isinstance(a, jax.ShapeDtypeStruct)
            assert (a.shape, a.dtype) == (b.shape, b.dtype)

    def _lower(self, eng, params):
        i32 = jnp.int32
        b, t = 4, 16
        prefill = eng._prefill_fn.lower(
            params, eng.cache.k, eng.cache.v, jnp.zeros((1, t), i32),
            jnp.zeros((t,), i32)).as_text()
        decode = eng._decode_fn.lower(
            params, eng.cache.k, eng.cache.v, jnp.zeros((b,), i32),
            jnp.zeros((b,), i32), jnp.zeros((b,), i32),
            jnp.zeros((b, 2), i32), jnp.ones((b,), i32)).as_text()
        return prefill, decode

    def test_engine_programs_take_bf16_weights_and_convert_none(
            self, bf16_family):
        mod, cfg, params = bf16_family
        eng = InferenceEngine(cfg, params, page_size=8, max_num_seqs=4,
                              max_model_len=64)
        weights = _weight_shapes(params)
        for text in self._lower(eng, eng._params):
            assert not _f32_to_bf16_converts(text) & weights
            # The program's parameters: no float32 one of a weight's shape.
            main = text[text.index("@main("):]
            main = main[:main.index(") -> ")]
            f32_args = {tuple(int(n) for n in dims.split("x")) for dims in
                        re.findall(r"tensor<([0-9x]+)xf32>", main)}
            assert not f32_args & weights
            assert "xbf16>" in main
        # The same jitted functions on the tree as given do convert
        # them, so the check above can fail.
        for text in self._lower(eng, params):
            assert _f32_to_bf16_converts(text) & weights
        want: dict = {}
        for leaf in jax.tree_util.tree_leaves(eng._params):
            want[str(leaf.dtype)] = want.get(str(leaf.dtype), 0) + leaf.nbytes
        got = eng.stats()["param_bytes"]
        assert got == want and set(got) == {"bfloat16", "float32"}
        total = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
        assert got["float32"] < 0.01 * total
        assert got["bfloat16"] == (total - got["float32"]) // 2
        out = eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=3))
        assert len(out[0]) == 3

    def test_tp2_shards_the_working_copy(self, bf16_family):
        mod, cfg, params = bf16_family
        eng = InferenceEngine(cfg, params, page_size=8, max_num_seqs=2,
                              max_model_len=32, tp=2)
        split = 0
        for leaf in jax.tree_util.tree_leaves(eng._params):
            assert len(leaf.sharding.device_set) == 2
            if "tp" in leaf.sharding.spec:
                # What the tp rules split is a matmul weight: cast first.
                assert leaf.dtype == jnp.bfloat16
                split += 1
        assert split >= 4
        assert eng.stats()["param_bytes"] == InferenceEngine(
            cfg, params, page_size=8, max_num_seqs=2,
            max_model_len=32).stats()["param_bytes"]


# ---------------------------------------------------------------------------
# The pools: one [pages, page_size, kv_heads * head_dim] array a layer,
# given donated to the three programs and written where they lie.
# ---------------------------------------------------------------------------

POOL_ENGINE = dict(page_size=4, num_pages=19, max_num_seqs=4,
                   max_model_len=32, prefill_chunk=8)


def _pool_engine(family, impl="reference", **kw):
    cfg, model_cls, init, _ = BF16[family]
    cfg = dataclasses.replace(cfg, paged_attn=impl)
    params = init(model_cls(cfg), cfg, seed=0, batch=1)
    return InferenceEngine(cfg, params, **dict(POOL_ENGINE, **kw))


class TestPoolsWrittenInPlace:
    @pytest.mark.parametrize("impl", ["reference", "interpret"])
    @pytest.mark.parametrize("family", sorted(BF16))
    def test_programs_take_every_pool_donated_and_relay_none(
            self, family, impl):
        """In each lowered program all 2 x layers pool arguments, and no
        other, may be written in place, and no ``reshape`` or ``transpose``
        touches a tensor of a pool's shape."""
        from kv_pool_4d import pool_facts

        eng = _pool_engine(family, impl)
        i32, ks, vs = jnp.int32, eng.cache.k, eng.cache.v
        assert ks[0].shape == (19, 4, eng._config.n_embd
                               // eng._config.n_head * eng.cache.num_kv_heads)
        programs = {
            "prefill": eng._prefill_fn.lower(
                eng._params, ks, vs, jnp.zeros((1, 16), i32),
                jnp.zeros((16,), i32)),
            "chunk": eng._chunk_fn.lower(
                eng._params, ks, vs, jnp.zeros((1, 8), i32),
                jnp.zeros((8,), i32), jnp.zeros((8,), i32),
                jnp.zeros((1, 2), i32)),
            "decode": eng._decode_fn.lower(
                eng._params, ks, vs, jnp.zeros((4,), i32),
                jnp.zeros((4,), i32), jnp.zeros((4,), i32),
                jnp.zeros((4, 2), i32), jnp.ones((4,), i32)),
        }
        for name, lowered in programs.items():
            facts = pool_facts(eng, lowered.as_text())
            pools = 2 * eng.cache.num_layers
            assert facts["pool_args"] == pools, (name, facts)
            assert facts["donated"] == facts["donated_pools"] == pools, \
                (name, facts)
            assert not facts["relaid"], (name, facts["relaid"])
        # Nothing ran: lowering consumes no pool.
        assert not any(a.is_deleted() for a in ks + vs)

    def test_the_check_above_sees_a_relaid_and_an_undonated_pool(self):
        from kv_pool_4d import pool_facts

        eng = _pool_engine("gpt2")
        pool = eng.cache.k[0]

        def relay(ks):
            return [k.reshape(19 * 4, -1).reshape(k.shape) + 1 for k in ks]

        facts = pool_facts(eng, jax.jit(relay).lower([pool, pool]).as_text())
        assert facts["pool_args"] == 2 and facts["donated"] == 0
        assert len(facts["relaid"]) == 4  # into the flat view and back, twice

    @pytest.mark.parametrize("family", sorted(BF16))
    def test_a_step_consumes_the_pools_it_was_given(self, family):
        """Whole prefill, chunked prefill and decode: the arrays that were
        ``cache.k`` / ``cache.v`` before a step are deleted after it, and
        the lists hold live arrays of the same shape."""
        eng = _pool_engine(family)
        eng.add_request("whole", [1, 2, 3, 4, 5],
                        SamplingParams(max_new_tokens=3))
        eng.add_request("chunked", list(range(1, 20)),
                        SamplingParams(max_new_tokens=3))
        ran = set()
        while eng.has_unfinished():
            before = eng.cache.k + eng.cache.v
            eng.step()
            record = eng.step_log()["steps"][-1]
            ran |= {name for name, _, _ in record["phases"]}
            if not record["decodes"] and not record.get("prefills"):
                continue  # the last tokens' fetch: no program ran
            assert all(a.is_deleted() for a in before), record["phases"]
            now = eng.cache.k + eng.cache.v
            assert len(now) == 2 * eng.cache.num_layers
            assert not any(a.is_deleted() for a in now)
            assert {a.shape for a in now} == {before[0].shape}
        assert {"infer.prefill", "infer.prefill_chunk", "infer.decode"} <= ran

    def test_stats_count_the_pools_bytes_once(self):
        eng = _pool_engine("llama")
        c = eng._config
        want = 2 * c.n_layer * 19 * 4 * c.n_kv_head * c.head_dim * 2  # bf16
        assert eng.stats()["kv_pool_bytes"] == want
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
        stats = eng.stats()  # of an engine whose first pools are gone
        assert stats["kv_pool_bytes"] == want
        assert stats["devices"] == [f"cpu:{jax.devices()[0].id}"]

    @pytest.mark.parametrize("family", sorted(BF16))
    def test_tp2_keeps_the_pools_split_on_their_last_dimension(self, family):
        from jax.sharding import PartitionSpec

        eng = _pool_engine(family, tp=2)
        plain = _pool_engine(family)
        prompts = [[1, 2, 3, 4, 5], list(range(1, 20))]
        assert eng.generate(prompts, SamplingParams(max_new_tokens=4)) \
            == plain.generate(prompts, SamplingParams(max_new_tokens=4))
        width = eng.cache.num_kv_heads * eng.cache.head_dim
        for pool in eng.cache.k + eng.cache.v:
            assert pool.sharding.spec == PartitionSpec(None, None, "tp")
            assert {s.data.shape for s in pool.addressable_shards} \
                == {(19, 4, width // 2)}
        assert eng.stats()["kv_pool_bytes"] == plain.stats()["kv_pool_bytes"]
        # Whole heads to a shard: the same pages hold the same rows (to
        # bf16's rounding: two shards sum a projection in another order).
        for a, b in zip(eng.cache.k + eng.cache.v,
                        plain.cache.k + plain.cache.v):
            np.testing.assert_allclose(
                np.asarray(a[1:].astype(jnp.float32)),
                np.asarray(b[1:].astype(jnp.float32)), atol=0.06, rtol=0.02)

    def test_kv_handoff_ships_the_4d_pools_bytes(self):
        """The wire is ``[layers, K|V, pages, page_size, kv_heads,
        head_dim]`` contiguous, as when the pools were 4-D; what is read
        comes from the export's own copy (a step may consume the pools
        meanwhile), and the sink's pools end with the same rows."""
        from raytpu.inference import disagg

        src = _pool_engine("llama", enable_prefix_cache=True)
        dst = _pool_engine("llama", enable_prefix_cache=True)
        prompt = list(range(1, 15))  # three full pages of 4 and a tail
        src.generate([prompt], SamplingParams(max_new_tokens=1))
        source = disagg.KVHandoffSource(src)
        meta = source.begin(prompt)
        cache = src.cache
        pages = src.prefix_cache.match(prompt, max_pages=3)
        assert meta["num_pages"] == len(pages) == 3
        assert (meta["kv_heads"], meta["head_dim"]) == (
            cache.num_kv_heads, cache.head_dim)
        want = np.stack([
            np.stack([np.asarray(pool[li])[pages].reshape(
                3, 4, cache.num_kv_heads, cache.head_dim)
                for pool in (cache.k, cache.v)])
            for li in range(cache.num_layers)]).tobytes()
        assert meta["total_bytes"] == len(want)
        # Steps between begin and the reads consume the pools read above.
        src.generate([[7, 8, 9]], SamplingParams(max_new_tokens=2))
        got = b"".join(source.read(meta["handoff_id"], off,
                                   min(100, len(want) - off))
                       for off in range(0, len(want), 100))
        assert got == want
        sink = disagg.KVHandoffSink(dst)
        assert sink.begin(meta, prompt)
        sink.write(0, got)
        assert sink.seal() == 3 and source.end(meta["handoff_id"])
        landed = dst.prefix_cache.match(prompt, max_pages=3)
        for li in range(cache.num_layers):
            for a, b in ((src.cache.k, dst.cache.k), (src.cache.v, dst.cache.v)):
                assert np.asarray(a[li])[pages].tobytes() \
                    == np.asarray(b[li])[landed].tobytes()


# ---------------------------------------------------------------------------
# Compile-once lint: jax.jit may appear ONLY inside _build_* constructors
# (and never inside a loop) anywhere in raytpu/inference — the
# per-iteration step() must call prebuilt functions, not re-jit.
# ---------------------------------------------------------------------------

class TestInferenceJitLint:
    """Thin wrapper over RTP004 (raytpu/analysis/rules/jit_in_builders.py)
    — the ad-hoc ``_jit_calls_outside_builders`` scan migrated into the
    lint framework; this keeps the invariant visible from the inference
    suite and proves the rule still bites."""

    def test_jit_only_in_build_constructors(self):
        from raytpu.analysis.core import run_lint
        from raytpu.analysis.rules.jit_in_builders import (
            jit_calls_outside_builders,
        )

        result = run_lint(select=["RTP004"], use_baseline=False)
        assert not result.findings, (
            "jax.jit outside a _build_* constructor (or inside a loop) in "
            "raytpu/inference — the per-iteration path must only CALL "
            "prebuilt compiled functions:\n  "
            + "\n  ".join(str(f) for f in result.findings))
        # The invariant is only meaningful if jit sites exist at all.
        pkg = pathlib.Path(__file__).resolve().parent.parent / \
            "raytpu" / "inference"
        total = []
        for path in sorted(pkg.glob("*.py")):
            t, _ = jit_calls_outside_builders(ast.parse(path.read_text()))
            total.extend(t)
        assert len(total) >= 1, "expected the program builder's jit site"

    def test_engine_names_no_model_family(self):
        """The seam: the engine imports no family's module and asks no
        config for its type; it goes by ``config.serving`` alone."""
        path = pathlib.Path(__file__).resolve().parent.parent / \
            "raytpu" / "inference" / "engine.py"
        imported, called, builders = [], [], []
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.FunctionDef) \
                    and node.name.startswith("_build_"):
                builders.append(node.name)
            elif isinstance(node, ast.ImportFrom):
                imported.append(node.module or "")
            elif isinstance(node, ast.Import):
                imported.extend(a.name for a in node.names)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name):
                called.append((node.func.id, [
                    a.value for a in node.args
                    if isinstance(a, ast.Constant)]))
        assert not [m for m in imported if m.startswith("raytpu.models")]
        # (Imports inside a method are seen too: this is one.)
        assert "raytpu.ops.paged_attention" in imported
        assert not [c for c in called if c[0] == "isinstance"]
        assert sorted(c[1] for c in called if c[0] == "getattr") \
            == [["paged_attn", None], ["serving", None]]
        # The three programs' one builder, the sampler's and the
        # hand-over's of the ids in flight, and for a model that drafts
        # for itself the five programs' (its prompts' two through one
        # builder inside it).
        assert builders == ["_build_program", "_build_sampler",
                            "_build_carry", "_build_drafting",
                            "_build_prompt"]

    def test_lint_catches_planted_violation(self):
        from raytpu.analysis.core import run_rule_on_source
        from raytpu.analysis.rules.jit_in_builders import JitInBuilders

        planted = (
            "import jax\n"
            "def step(self):\n"
            "    fn = jax.jit(lambda x: x)\n"
            "def _build_decode_fn(self):\n"
            "    return jax.jit(lambda x: x)\n"
            "def _build_loopy(self):\n"
            "    for _ in range(2):\n"
            "        jax.jit(lambda x: x)\n")
        findings = run_rule_on_source(
            JitInBuilders(), planted,
            rel="raytpu/inference/_planted.py")
        assert len(findings) == 2  # step() and the in-loop builder call
