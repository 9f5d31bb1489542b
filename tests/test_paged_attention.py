"""Paged flash-decode attention (raytpu/ops/paged_attention.py):
kernel-vs-reference numerics across ragged contexts / GQA ratios /
page sizes, implementation resolution (env toggle + config override,
warnings on bad values), engine integration (greedy generation
token-identical with the kernel on vs off — including prefix-cache
hits and preemption-resume), the compile-once-per-bucket discipline
with trimmed block tables, and the pages-gathered accounting behind
the reference-gather trim."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import InferenceEngine, SamplingParams
from raytpu.models.gpt2 import GPT2Config
from raytpu.models.gpt2 import init_params as gpt2_init
from raytpu.models.llama import Llama, LlamaConfig
from raytpu.models.llama import init_params as llama_init
from raytpu.ops.paged_attention import (
    gather_kv_pages,
    paged_attention,
    paged_attention_reference,
    resolve_paged_impl,
    scatter_kv_slots,
)

LCFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                           attn_impl="reference", remat=False)
GCFG = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32,
                           attn_impl="reference", remat=False)


@pytest.fixture(scope="module")
def llama_params():
    return llama_init(Llama(LCFG), LCFG, seed=0, batch=1)


@pytest.fixture(scope="module")
def gpt2_params():
    from raytpu.models.gpt2 import GPT2

    return gpt2_init(GPT2(GCFG), GCFG, seed=0, batch=1)


def _setup(rng, b, t, heads, kv, d, page_size, pages_per_seq, dtype,
           ctx=None):
    """Random pool + block tables + positions for ``b`` sequences whose
    query tokens end at ragged context lengths. The pools are shaped as
    the cache holds them: a token's ``kv`` heads side by side in a row."""
    num_pages = b * pages_per_seq + 1
    q = jnp.asarray(rng.standard_normal((b, t, heads, d)), dtype)
    k = jnp.asarray(rng.standard_normal(
        (num_pages, page_size, kv * d)), dtype)
    v = jnp.asarray(rng.standard_normal(
        (num_pages, page_size, kv * d)), dtype)
    # Distinct live pages per sequence (page 0 stays scratch).
    bt = np.arange(1, num_pages).reshape(b, pages_per_seq)
    if ctx is None:
        ctx = rng.integers(t, pages_per_seq * page_size, size=(b,))
    pos = np.maximum(ctx[:, None] - (t - 1) + np.arange(t)[None], 0)
    return (q, k, v, jnp.asarray(bt, jnp.int32),
            jnp.asarray(pos, jnp.int32))


class TestKernelNumerics:
    @pytest.mark.parametrize("heads,kv", [(4, 4), (8, 2), (4, 1)])
    @pytest.mark.parametrize("page_size", [4, 8, 16])
    def test_decode_matches_reference_ragged(self, heads, kv, page_size):
        rng = np.random.default_rng(heads * 100 + page_size)
        args = _setup(rng, b=4, t=1, heads=heads, kv=kv, d=16,
                      page_size=page_size, pages_per_seq=6,
                      dtype=jnp.float32)
        ref = paged_attention_reference(*args, sm_scale=16 ** -0.5)
        out = paged_attention(*args, force="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_chunk_shape_matches_reference(self):
        # Chunked prefill: B=1, many query tokens at consecutive
        # positions, attending cached slots <= their own position.
        rng = np.random.default_rng(7)
        args = _setup(rng, b=1, t=24, heads=6, kv=3, d=16, page_size=8,
                      pages_per_seq=8, dtype=jnp.float32)
        ref = paged_attention_reference(*args, sm_scale=16 ** -0.5)
        out = paged_attention(*args, force="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_bf16_pages_fp32_accumulators(self):
        # Acceptance bar: interpret-mode kernel within 2e-2 of the fp32
        # reference when pages and activations are bf16.
        rng = np.random.default_rng(11)
        q, k, v, bt, pos = _setup(rng, b=4, t=1, heads=8, kv=2, d=32,
                                  page_size=16, pages_per_seq=8,
                                  dtype=jnp.bfloat16)
        ref = paged_attention_reference(q, k, v, bt, pos,
                                        sm_scale=32 ** -0.5)
        out = paged_attention(q, k, v, bt, pos, force="interpret")
        err = np.max(np.abs(np.asarray(ref, np.float32)
                            - np.asarray(out, np.float32)))
        assert err <= 2e-2, f"bf16 kernel error {err} exceeds 2e-2"

    def test_single_token_context(self):
        # Context of exactly one token (first decode after a 1-token
        # prompt): the softmax must normalize over that slot alone.
        rng = np.random.default_rng(3)
        args = _setup(rng, b=2, t=1, heads=4, kv=2, d=8, page_size=4,
                      pages_per_seq=3, dtype=jnp.float32,
                      ctx=np.array([1, 1]))
        ref = paged_attention_reference(*args, sm_scale=8 ** -0.5)
        out = paged_attention(*args, force="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_gather_helper_layout(self):
        rng = np.random.default_rng(5)
        k = jnp.asarray(rng.standard_normal((9, 4, 2 * 8)), jnp.float32)
        bt = jnp.asarray([[3, 1], [2, 2]], jnp.int32)
        out = gather_kv_pages(k, bt, 8)
        assert out.shape == (2, 8, 2, 8)  # heads split after the gather
        np.testing.assert_array_equal(np.asarray(out[0, :4]),
                                      np.asarray(k[3]).reshape(4, 2, 8))
        np.testing.assert_array_equal(np.asarray(out[1, 4:]),
                                      np.asarray(k[2]).reshape(4, 2, 8))

    @pytest.mark.parametrize("impl", ["reference", "interpret"])
    @pytest.mark.parametrize("heads,kv,d,t", [(4, 4, 16, 1), (8, 2, 16, 1),
                                              (6, 3, 16, 24), (4, 4, 128, 1)])
    def test_flat_pool_gives_the_4d_pools_bits(self, heads, kv, d, t, impl):
        """The same attention, bit for bit, as the parent computed from
        ``[pages, page_size, kv, d]`` pools (tests/kv_pool_4d.py)."""
        from kv_pool_4d import attention_4d, same_bits

        rng = np.random.default_rng(kv * d + t)
        q, k, v, bt, pos = _setup(rng, b=1 if t > 1 else 3, t=t, heads=heads,
                                  kv=kv, d=d, page_size=8, pages_per_seq=6,
                                  dtype=jnp.float32)
        shape4 = k.shape[:2] + (kv, d)
        want = attention_4d(q, k.reshape(shape4), v.reshape(shape4), bt, pos,
                            force=impl)
        assert same_bits(paged_attention(q, k, v, bt, pos, force=impl), want)

    def test_a_row_that_holds_no_whole_heads_is_refused(self):
        rng = np.random.default_rng(0)
        q, k, v, bt, pos = _setup(rng, b=2, t=1, heads=4, kv=2, d=16,
                                  page_size=4, pages_per_seq=2,
                                  dtype=jnp.float32)
        with pytest.raises(ValueError, match="pool row of 24 features"):
            paged_attention(q, k[..., :24], v[..., :24], bt, pos,
                            force="interpret")


class TestHeadsPerBlock:
    """How many kv heads share a block is chosen from the shape: all that
    keep the block's query rows inside one pass of the matrix unit, of
    the counts Mosaic can block (whole 128-lane tiles, or every head)."""

    @pytest.mark.parametrize("kv,d,rows_per_head,want", [
        (25, 64, 1, 25),     # GPT-2 XL decode: no smaller count fills tiles
        (16, 128, 1, 16),    # OLMoE decode: one step a page, not sixteen
        (16, 128, 256, 1),   # its prompt chunks: the fewest heads
        (12, 64, 1, 12),     # GPT-2 124M decode
        (12, 64, 64, 2),     # and a 64-token chunk: 2 x 64 rows fit
        (8, 128, 4, 8),      # GQA 32/8 decode: 8 x 4 rows
        (8, 128, 64, 2),     # GQA chunk of 16 tokens x 4 query heads
        (2, 32, 2, 2),       # no count fills a tile: every head
    ])
    def test_count_follows_the_rows(self, kv, d, rows_per_head, want):
        from raytpu.ops.paged_attention import _kv_heads_per_block

        assert _kv_heads_per_block(kv, d, rows_per_head) == want

    @pytest.mark.parametrize("page_size,rows,lanes,itemsize,want", [
        (16, 32, 1600, 2, 16),    # GPT-2 XL decode: 256 slots, 3.4 MB
        (16, 16, 2048, 2, 16),    # OLMoE decode: 4 MiB of buffers, the most
        (16, 16, 4096, 2, 8),     # rows twice as wide: a lane tile of slots
        (16, 16, 768, 2, 16),     # GPT-2 124M: never more than 256 slots
        (16, 512, 128, 2, 16),    # a chunk of 256 tokens, two heads of 64
        (16, 2048, 256, 2, 8),    # GQA chunk: the scores hold it to 128
        (4, 8, 32, 4, 64),        # the tests' float32 pages of 4 ...
        (8, 8, 256, 4, 32),       # ... and of 8
        (128, 8, 1024, 2, 2),     # a page that is a lane tile itself
        (16, 16, 16384, 2, 8),    # no budget goes under a lane tile
    ])
    def test_pages_a_block_follow_the_shapes(self, page_size, rows, lanes,
                                             itemsize, want):
        from raytpu.ops.paged_attention import _pages_per_block

        assert _pages_per_block(page_size, rows, lanes, itemsize) == want

    @pytest.mark.parametrize("t", [1, 40])
    def test_grouped_and_single_head_blocks_agree_with_reference(self, t):
        # kv 4 x d 128: a decode block takes all four heads, a 40-token
        # chunk one head (4 x 40 rows are more than a pass).
        rng = np.random.default_rng(11)
        args = _setup(rng, b=1 if t > 1 else 3, t=t, heads=4, kv=4, d=128,
                      page_size=8, pages_per_seq=8, dtype=jnp.float32)
        ref = paged_attention_reference(*args, sm_scale=128 ** -0.5)
        out = paged_attention(*args, force="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def _block_setup(rng, positions, t, heads, kv, d, page_size, width, dtype):
    """A pool, tables ``[len(positions), width]`` and query positions for
    sequences whose last query token stands at ``positions``: live pages
    distinct, every dead column naming the scratch page 0."""
    b = len(positions)
    last = np.asarray(positions)
    live = last // page_size + 1
    num_pages = int(live.sum()) + 3
    q = jnp.asarray(rng.standard_normal((b, t, heads, d)), dtype)
    k, v = (np.asarray(rng.standard_normal((num_pages, page_size, kv * d)),
                       np.float32) for _ in range(2))
    bt = np.zeros((b, width), np.int32)
    owned = np.split(rng.permutation(np.arange(1, int(live.sum()) + 1)),
                     np.cumsum(live)[:-1])
    for i, pages in enumerate(owned):
        bt[i, :len(pages)] = pages
    pos = np.maximum(last[:, None] - (t - 1) + np.arange(t)[None], 0)
    return (q, k, v, jnp.asarray(bt), jnp.asarray(pos, jnp.int32),
            np.concatenate(owned))


# The shapes the cells run, in small: (heads, kv heads, head_dim).
_XL_LIKE = (5, 5, 64)      # 320 lanes in one group, not whole lane tiles
_OLMOE_LIKE = (2, 2, 128)  # kv heads of 128, one query head each
_GQA_128 = (8, 2, 128)     # the same under GQA


class TestBlockPath:
    """The kernel takes a sequence's pages a block at a time
    (``_pages_per_block``): contexts that end at every kind of place in
    a block, tables far wider than the sequences, the cells' shapes in
    small, against the reference."""

    # Each case: heads/kv/d, t, page size, pool dtype, and the positions
    # of the batch's last query tokens as a function of a block's slots.
    CASES = {
        "inside_a_blocks_first_page": (
            _XL_LIKE, 1, 16, jnp.bfloat16, lambda n, ps: [n + 1, 2 * n + 5]),
        "on_a_page_boundary": (
            _XL_LIKE, 1, 16, jnp.bfloat16,
            lambda n, ps: [n + ps - 1, n + ps, 3 * ps - 1]),
        "on_a_block_boundary": (
            _XL_LIKE, 1, 16, jnp.bfloat16, lambda n, ps: [n - 1, n, 2 * n - 1]),
        "one_token_of_context": (
            _OLMOE_LIKE, 1, 16, jnp.bfloat16, lambda n, ps: [0, 0, n]),
        "table_blocks_wider_than_the_longest": (
            _OLMOE_LIKE, 1, 16, jnp.bfloat16, lambda n, ps: [ps + 2, 5, n // 2],
            5),
        "rows_of_very_different_lengths": (
            _GQA_128, 1, 16, jnp.bfloat16,
            lambda n, ps: [0, 3 * n + 7, ps, 2 * n - 1, n + ps + 1, 9]),
        "xl_like_float32_pages_of_4": (
            _XL_LIKE, 1, 4, jnp.float32, lambda n, ps: [n - 1, n, 2 * n + 2, 1]),
        "gqa_float32_pages_of_8": (
            _GQA_128, 1, 8, jnp.float32, lambda n, ps: [2 * n, n + 3, 0]),
        "olmoe_like_float32_pages_of_8": (
            _OLMOE_LIKE, 1, 8, jnp.float32, lambda n, ps: [n, 2 * n + 9]),
        "chunk_inside_the_second_block": (
            _XL_LIKE, 24, 16, jnp.bfloat16, lambda n, ps: [n + 30]),
        "chunk_across_a_block_boundary": (
            _GQA_128, 24, 8, jnp.float32, lambda n, ps: [n + 10]),
        "chunk_of_a_prompts_first_tokens": (
            _OLMOE_LIKE, 40, 4, jnp.float32, lambda n, ps: [39]),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_matches_reference(self, case):
        from raytpu.ops.paged_attention import _pages_per_block

        (heads, kv, d), t, page_size, dtype, where, *spare = self.CASES[case]
        # The most slots a block of these shapes can take: whatever rule
        # sizes it, every case's positions straddle the blocks it makes.
        slots = page_size * _pages_per_block(
            page_size, 8, kv * d, jnp.dtype(dtype).itemsize)
        positions = where(slots, page_size)
        blocks = max(positions) // slots + 1 + (spare[0] if spare else 0)
        rng = np.random.default_rng(len(case))
        q, k, v, bt, pos, _ = _block_setup(
            rng, positions, t, heads, kv, d, page_size,
            blocks * slots // page_size, dtype)
        k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
        ref = paged_attention_reference(q, k, v, bt, pos, sm_scale=d ** -0.5)
        out = paged_attention(q, k, v, bt, pos, force="interpret")
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    def test_pages_nobody_owns_cannot_poison_the_result(self):
        """The scratch page and every page no sequence owns hold NaN: a
        dead column's page is never fetched, and a slot that was not
        fetched is masked and its V row zero (0 x a stale NaN is NaN)."""
        rng = np.random.default_rng(5)
        positions = [0, 37, 150, 129]
        q, k, v, bt, pos, owned = _block_setup(
            rng, positions, 1, 4, 2, 16, 4, 80, jnp.float32)
        ref = paged_attention_reference(
            q, jnp.asarray(k), jnp.asarray(v), bt, pos, sm_scale=0.25)
        nobody = np.setdiff1d(np.arange(k.shape[0]), owned)
        assert 0 in nobody and len(nobody) == 3
        k[nobody], v[nobody] = np.nan, np.nan
        out = np.asarray(paged_attention(
            q, jnp.asarray(k), jnp.asarray(v), bt, pos, force="interpret"))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, np.asarray(ref), atol=1e-5, rtol=1e-5)


class TestScatterKVSlots:
    @pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n", [1, 5])
    def test_equals_the_write_through_the_flat_view(self, n, pool_dtype):
        """Slot ``page * page_size + offset`` is row ``offset`` of page
        ``page``: the same pool as writing ``[N, kv, d]`` rows through the
        ``[pages * size, kv, d]`` view of a 4-D pool (how the parent's
        prefill wrote), rows cast to the pool's dtype, every other slot
        untouched."""
        rng = np.random.default_rng(n)
        pool = jnp.asarray(rng.standard_normal((7, 4, 2 * 8)), pool_dtype)
        rows = jnp.asarray(rng.standard_normal((n, 2 * 8)), jnp.float32)
        dests = jnp.asarray(rng.choice(np.arange(4, 28), n, replace=False),
                            jnp.int32)
        want = pool.reshape(28, 2, 8).at[dests].set(
            rows.reshape(n, 2, 8).astype(pool_dtype)).reshape(pool.shape)
        got = jax.jit(scatter_kv_slots)(pool, dests, rows)
        assert got.dtype == pool.dtype and got.shape == pool.shape
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        for i, dest in enumerate(np.asarray(dests)):
            np.testing.assert_array_equal(
                np.asarray(got[dest // 4, dest % 4], np.float32),
                np.asarray(rows[i].astype(pool_dtype), np.float32))

    def test_padding_rows_land_in_the_scratch_page_only(self):
        pool = jnp.ones((3, 4, 2), jnp.float32)
        rows = jnp.full((3, 2), 7.0)
        got = scatter_kv_slots(pool, jnp.asarray([0, 0, 9], jnp.int32), rows)
        np.testing.assert_array_equal(np.asarray(got[1]), np.ones((4, 2)))
        assert float(got[2, 1, 0]) == 7.0 and float(got[0, 0, 0]) == 7.0
        assert float(np.asarray(got).sum()) == 24 + 2 * 2 * 6.0

    def test_a_donated_pool_is_written_where_it_lies(self):
        """Given donated, the program's result is the buffer that came in
        (marked so in the lowered program) and the array passed is gone."""
        write = jax.jit(scatter_kv_slots, donate_argnums=0)
        pool = jnp.zeros((5, 4, 6), jnp.float32)
        dests, rows = jnp.asarray([9], jnp.int32), jnp.ones((1, 6))
        text = write.lower(pool, dests, rows).as_text()
        assert "tf.aliasing_output = 0" in text or "jax.buffer_donor" in text
        got = write(pool, dests, rows)
        assert pool.is_deleted() and float(got[2, 1].sum()) == 6.0


def _engine(cfg, params, dtype, impl):
    cfg = dataclasses.replace(cfg, dtype=dtype, paged_attn=impl)
    return InferenceEngine(cfg, params, page_size=4, num_pages=19,
                           max_num_seqs=4, max_model_len=32,
                           enable_prefix_cache=False)


class TestFlatPoolsAgainstThe4DPools:
    """The engine's three programs on ``[pages, page_size, kv * d]`` pools,
    given donated, against the family's walks on the parent's 4-D pools
    (tests/kv_pool_4d.py): logits and every pool, bit for bit."""

    @pytest.mark.parametrize("impl", ["reference", "interpret"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_gpt2_programs(self, gpt2_params, dtype, impl):
        from kv_pool_4d import check_engine_programs

        eng = _engine(GCFG, gpt2_params, dtype, impl)
        done = check_engine_programs(eng, list(range(3, 14)),
                                     list(range(20, 36)))
        assert done == ["prefill", "chunk@0", "chunk@8", "decode#0",
                        "decode#1"]

    @pytest.mark.parametrize("impl", ["reference", "interpret"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_llama_programs(self, llama_params, dtype, impl):
        from kv_pool_4d import check_engine_programs

        eng = _engine(LCFG, llama_params, dtype, impl)  # GQA: 2 kv heads
        assert eng.cache.k[0].shape == (19, 4, 2 * LCFG.head_dim)
        assert len(check_engine_programs(
            eng, list(range(3, 14)), list(range(20, 36)))) == 5


class TestImplResolution:
    @pytest.mark.parametrize("selector, impl", [
        (None, "reference"), ("auto", "reference"), ("kernel", "tpu"),
        ("tpu", "tpu"), ("interpret", "interpret"),
        ("reference", "reference")])
    def test_selector_off_a_tpu(self, selector, impl):
        # The config's field and the platform, nothing else: auto is the
        # reference here, a pinned value itself; none of them warns.
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert resolve_paged_impl(selector) == impl

    def test_bad_config_value_warns(self):
        with pytest.warns(RuntimeWarning, match="paged_attn"):
            assert resolve_paged_impl("not-an-impl") == "reference"


def _kernel_cfg(cfg):
    return dataclasses.replace(cfg, paged_attn="interpret")


def _ref_cfg(cfg):
    return dataclasses.replace(cfg, paged_attn="reference")


def _stats(eng):
    """``eng.stats()`` and, of each decode step still on record, the block
    table's columns it was handed (what the reference path gathers)."""
    return eng.stats() | {
        "table_widths": eng.recorder.values("table_width")}


class TestEngineTokenIdentity:
    """Greedy generation must be token-identical with the kernel on vs
    off, across batch buckets, prefix-cache hits, and preemption."""

    PROMPTS = [list(range(1, 9)), list(range(3, 25)), [7, 8],
               list(range(40, 50))]

    def _generate(self, cfg, params, prompts, **eng_kw):
        eng = InferenceEngine(cfg, params, **eng_kw)
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=8))
        return outs, _stats(eng)

    def _staggered(self, cfg, params, prompts, **eng_kw):
        """Staggered arrivals: the decode batch grows/shrinks, walking
        multiple batch buckets in one run."""
        eng = InferenceEngine(cfg, params, **eng_kw)
        pending = list(enumerate(prompts))
        results = {i: [] for i in range(len(prompts))}
        it = 0
        while pending or eng.has_unfinished():
            if pending and it % 3 == 0:
                i, p = pending.pop(0)
                eng.add_request(f"r{i}", p,
                                SamplingParams(max_new_tokens=8))
            for o in eng.step():
                results[int(o.request_id[1:])].append(o.token_id)
            it += 1
        return [results[i] for i in range(len(prompts))], _stats(eng)

    def test_llama_kernel_matches_reference_across_buckets(
            self, llama_params):
        kw = dict(page_size=8, max_num_seqs=4, max_model_len=64)
        ref, sref = self._staggered(_ref_cfg(LCFG), llama_params,
                                    self.PROMPTS, **kw)
        ker, sker = self._staggered(_kernel_cfg(LCFG), llama_params,
                                    self.PROMPTS, **kw)
        assert ref == ker
        # The batch walked multiple decode buckets in both runs.
        assert len(sker["decode_compiles"]) >= 2
        assert sref["paged_attn_impl"] == "reference"
        assert sker["paged_attn_impl"] == "interpret"
        # Both were handed the same tables, trimmed under the 8 columns
        # a sequence may have.
        assert sref["table_widths"] == sker["table_widths"]
        assert 0 < max(sker["table_widths"]) < 8

    def test_gpt2_kernel_matches_reference(self, gpt2_params):
        kw = dict(page_size=8, max_num_seqs=4, max_model_len=64)
        ref, sref = self._generate(_ref_cfg(GCFG), gpt2_params,
                                   self.PROMPTS, **kw)
        ker, sker = self._generate(_kernel_cfg(GCFG), gpt2_params,
                                   self.PROMPTS, **kw)
        assert ref == ker
        assert sker["paged_attn_impl"] == "interpret"
        assert sref["table_widths"] == sker["table_widths"]

    def test_prefix_cache_hit_identical(self, llama_params):
        # Shared 16-token system prefix: the second/third request hit
        # the prefix cache and prefill only their tails via the paged
        # chunk path — which must also run the kernel.
        system = list(range(1, 17))
        prompts = [system + [30 + i] for i in range(3)]
        kw = dict(page_size=8, max_num_seqs=4, max_model_len=64,
                  enable_prefix_cache=True)

        def collect(cfg):
            eng = InferenceEngine(cfg, llama_params, **kw)
            results = {}
            for i, p in enumerate(prompts):  # sequential: hits warm
                eng.add_request(f"p{i}", p,
                                SamplingParams(max_new_tokens=6))
                toks = []
                while eng.has_unfinished():
                    for o in eng.step():
                        toks.append(o.token_id)
                results[i] = toks
            return results, _stats(eng)

        ref, sref = collect(_ref_cfg(LCFG))
        ker, sker = collect(_kernel_cfg(LCFG))
        assert ref == ker
        assert sref["prefix_cache"]["hit_tokens"] > 0
        assert sker["prefix_cache"]["hit_tokens"] > 0
        # The prefix-hit tails ran the chunk path in both impls.
        assert sref["chunk_prefill_compiles"]
        assert sker["chunk_prefill_compiles"]
        assert sref["table_widths"] == sker["table_widths"]

    def test_preemption_resume_identical(self, llama_params):
        # 5 usable pages of 4 tokens force preempt-to-recompute; the
        # resumed prefill + decode must be token-identical too.
        prompts = [list(range(1, 8)), list(range(20, 25))]
        kw = dict(page_size=4, num_pages=6, max_num_seqs=2,
                  max_model_len=24)
        ref, sref = self._generate(_ref_cfg(LCFG), llama_params,
                                   prompts, **kw)
        ker, sker = self._generate(_kernel_cfg(LCFG), llama_params,
                                   prompts, **kw)
        assert sref["num_preemptions"] >= 1
        assert sker["num_preemptions"] >= 1
        assert ref == ker


class TestCompileOnceAndTrim:
    def test_decode_compiles_once_per_batch_x_pages_bucket(
            self, llama_params):
        eng = InferenceEngine(_kernel_cfg(LCFG), llama_params,
                              page_size=8, max_num_seqs=4,
                              max_model_len=64)
        # Staggered arrivals churn batch composition AND context
        # growth walks the page-width buckets.
        pending = [(f"r{i}", list(range(1, 4 + 3 * i))) for i in range(4)]
        it = 0
        while pending or eng.has_unfinished():
            if pending and it % 2 == 0:
                rid, p = pending.pop(0)
                eng.add_request(rid, p, SamplingParams(max_new_tokens=10))
            eng.step()
            it += 1
        stats = eng.stats()
        assert stats["decode_compiles"]
        assert all(v == 1 for v in stats["decode_compiles"].values()), (
            f"recompile within a (batch x pages) bucket: "
            f"{stats['decode_compiles']}")
        # Keys are "BxP" combos; every width is a pow2 page bucket.
        for key in stats["decode_compiles"]:
            b, p = key.split("x")
            assert int(p) & (int(p) - 1) == 0

    def test_reference_gather_is_trimmed(self, llama_params):
        # Short prompts under a large max_model_len: the trimmed gather
        # must touch far fewer block-table columns than the padded
        # P_max width would.
        eng = InferenceEngine(_ref_cfg(LCFG), llama_params, page_size=4,
                              max_num_seqs=2, max_model_len=96)
        assert eng.max_pages_per_seq == 24
        eng.generate([[1, 2, 3], [5, 6, 7, 8]],
                     SamplingParams(max_new_tokens=6))
        widths = eng.recorder.values("table_width")
        untrimmed = len(widths) * 2 * eng.max_pages_per_seq
        gathered = sum(rows * width for rows, width in zip(
            eng.recorder.values("bucket"), widths, strict=True))
        assert 0 < gathered < untrimmed / 2, (
            f"{gathered} columns gathered; untrimmed would be "
            f"~{untrimmed}")

    def test_trim_never_drops_live_pages(self, llama_params):
        # A sequence that grows past a page-bucket boundary mid-decode
        # still sees its whole context (output == untrimmed reference
        # via the engine-level identity tests); here just assert the
        # bucket walk actually happened.
        eng = InferenceEngine(_ref_cfg(LCFG), llama_params, page_size=4,
                              max_num_seqs=1, max_model_len=64)
        eng.generate([list(range(1, 8))],
                     SamplingParams(max_new_tokens=12))
        widths = {int(k.split("x")[1])
                  for k in eng.stats()["decode_compiles"]}
        assert len(widths) >= 2  # crossed at least one width bucket
