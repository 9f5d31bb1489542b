"""Mellum2: window layers among full ones behind two kinds of KV pool, a
rotary embedding a kind (YaRN on the full layers), a head size that is
not hidden / heads. All at a tiny size on the CPU (``MellumConfig.tiny``:
two periods of S S S F, window 8, YaRN over an original length of 32, so
that a context of a few dozen positions slides every window and reaches
both of YaRN's regimes), page size 4.

The model is held to the benchmark's plain float32 reference
(``perfbench/families/mellum.py``, written from the layer equations and
not from the program): in float32 they choose the same experts and agree
to rounding, 1e-4 of the largest reference logit.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import InferenceEngine, PagedKVCache, PrefixCache
from raytpu.inference.sampling import SamplingParams
from raytpu.models import llama as llama_mod
from raytpu.models.llama import FULL, WINDOW, Rope, rope_tables
from raytpu.models.mixtral import (Mellum, MellumConfig, OlmoeConfig,
                                   init_params)
from raytpu.ops.flash_attention import flash_attention
from raytpu.ops.paged_attention import paged_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = dict(dtype=jnp.float32, attn_impl="reference",
           paged_attn="reference", remat=False)
TINY = dataclasses.replace(MellumConfig.tiny(), **F32)
ENGINE = dict(page_size=4, max_num_seqs=4, max_model_len=128)
IMPLS = ["reference", "interpret"]


@pytest.fixture(scope="module")
def family():
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families",
                       "mellum")


@pytest.fixture(scope="module")
def params():
    return init_params(Mellum(TINY), TINY, seed=1)


def file_config(c: MellumConfig):
    """The configuration file the family's reference reads, for ``c``."""
    r = c.full_rope
    return {
        "family": "mellum", "vocab_size": c.vocab_size,
        "max_position_embeddings": c.block_size,
        "num_hidden_layers": c.n_layer, "num_attention_heads": c.n_head,
        "num_key_value_heads": c.n_kv_head, "hidden_size": c.n_embd,
        "head_dim": c.head_dim, "moe_intermediate_size": c.n_inter,
        "num_experts": c.n_expert,
        "num_experts_per_tok": c.n_expert_per_tok,
        "norm_topk_prob": c.norm_topk_prob, "rms_norm_eps": c.norm_eps,
        "layer_types": list(c.layer_types), "sliding_window": c.window,
        "rope_parameters": {
            FULL: {"rope_type": "yarn", "rope_theta": r.theta,
                   "factor": r.yarn_factor,
                   "original_max_position_embeddings":
                   r.original_max_position, "beta_fast": r.beta_fast,
                   "beta_slow": r.beta_slow,
                   "attention_factor": 0.1 * math.log(r.yarn_factor) + 1},
            WINDOW: {"rope_type": "default", "rope_theta": c.rope_theta}},
        "compute_dtype": "float32", "param_dtype": "float32"}


def rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float32)
                        - np.asarray(want, np.float32)).max()
                 / np.abs(np.asarray(want)).max())


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, TINY.vocab_size, size=n)]
            for n in lengths]


# ---- the config ----------------------------------------------------------------


class TestConfig:
    def test_head_dim_is_a_field_with_the_old_default(self):
        assert llama_mod.LlamaConfig.tiny().head_dim == 128 // 4
        assert OlmoeConfig().head_dim == 2048 // 16
        c = MellumConfig()
        assert (c.head_dim, c.n_embd // c.n_head) == (128, 72)
        assert dataclasses.replace(c, n_layer=8).head_dim == 128

    def test_published_pattern_windows_and_ropes(self):
        c = MellumConfig()
        assert c.layer_types == ((WINDOW,) * 3 + (FULL,)) * 7
        assert [c.layer_kind(i) for i in (0, 3, 27)] == [WINDOW, FULL, FULL]
        assert c.rope_of(WINDOW) == 500000.0
        assert c.rope_of(FULL).yarn_factor == 16.0
        assert c.serving.layer_windows == ((1024,) * 3 + (None,)) * 7
        assert llama_mod.LlamaConfig.tiny().serving.layer_windows == ()
        assert dataclasses.replace(c, n_layer=8).layer_types \
            == ((WINDOW,) * 3 + (FULL,)) * 2

    def test_a_bad_pattern_is_refused(self):
        with pytest.raises(ValueError, match="layer_types"):
            llama_mod.LlamaConfig(n_layer=2, layer_types=(FULL,))
        with pytest.raises(ValueError, match="window"):
            llama_mod.LlamaConfig(n_layer=1, layer_types=(WINDOW,))

    def test_param_tree_has_the_published_projections(self, params):
        attn = params["layers_0"]["attn"]
        c = TINY
        assert attn["q_proj"]["kernel"].shape == (64, 8 * 16)
        assert attn["k_proj"]["kernel"].shape == (64, 2 * 16)
        assert attn["o_proj"]["kernel"].shape == (8 * 16, 64)
        assert c.n_embd // c.n_head != c.head_dim
        assert "layers" not in params and "layers_7" in params


# ---- rope -------------------------------------------------------------------------


class TestRope:
    def test_yarn_at_the_published_numbers(self):
        """ISSUE 32's formula, written out again."""
        rope = MellumConfig().full_rope
        d, theta, orig, factor = 128, 500000.0, 8192, 16.0

        def dim(n):
            return d * math.log(orig / (2 * math.pi * n)) \
                / (2 * math.log(theta))

        low, high = math.floor(dim(32)), math.ceil(dim(1))
        assert (low, high) == (18, 35)
        want = []
        for i in range(64):
            plain = theta ** (-2 * i / d)
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            want.append(plain / factor * ramp + plain * (1 - ramp))
        got = llama_mod.yarn_frequencies(d, rope)
        np.testing.assert_allclose(got, want, rtol=1e-12)
        pos = jnp.asarray([0, 1, 1000, 40000])
        cos, sin = rope_tables(d, pos, rope)
        angles = np.asarray(pos, np.float64)[:, None] * np.asarray(want)
        scale = 1.2772588722239782
        assert scale == pytest.approx(0.1 * math.log(16) + 1)
        # float32 angles of 40,000 radians carry 4e-3 of rounding.
        np.testing.assert_allclose(cos[:3], np.cos(angles[:3]) * scale,
                                   atol=2e-4)
        np.testing.assert_allclose(sin[:3], np.sin(angles[:3]) * scale,
                                   atol=2e-4)
        np.testing.assert_allclose(cos[3, 30:], np.cos(angles[3, 30:])
                                   * scale, atol=2e-4)

    def test_plain_rope_is_what_it_was(self):
        pos = jnp.arange(7)
        old = rope_tables(16, pos, 10000.0)
        new = rope_tables(16, pos, Rope(theta=10000.0))
        assert all(np.array_equal(a, b) for a, b in zip(old, new))

    def test_attention_factor_defaults_to_the_formula(self):
        given = Rope(theta=1e4, yarn_factor=4.0, original_max_position=32,
                     attention_factor=0.1 * math.log(4.0) + 1)
        left_out = dataclasses.replace(given, attention_factor=None)
        a, b = (rope_tables(16, jnp.arange(50), r) for r in (given,
                                                             left_out))
        np.testing.assert_allclose(a[0], b[0], rtol=1e-6)


# ---- windowed attention against a dense masked softmax ------------------------


def dense(q, k, v, window):
    """[B, H, T, D] each; position p sees p - window < j <= p."""
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
    seen = (j <= i) & ((j > i - window) if window else True)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


WINDOWS = [None, 5, 16, 48, 64, 200]  # none; smaller than, a block of,
#                                       inside, equal to, larger than T


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_flash_attention(window, impl, monkeypatch):
    import importlib

    fa = importlib.import_module("raytpu.ops.flash_attention")
    monkeypatch.setattr(fa, "DEFAULT_BLOCK_Q", 16)
    monkeypatch.setattr(fa, "DEFAULT_BLOCK_K", 16)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 4, 64, 16)) for kk in keys)
    want = dense(q, k, v, window)
    got = flash_attention(q, k, v, window=window, force=impl)
    assert float(jnp.abs(got - want).max()) < 2e-6

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    grads = jax.grad(loss(lambda *a: flash_attention(
        *a, window=window, force=impl)), argnums=(0, 1, 2))(q, k, v)
    wanted = jax.grad(loss(lambda *a: dense(*a, window)),
                      argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, wanted):
        assert float(jnp.abs(g - w).max()) < 2e-5


def test_a_window_is_a_causal_layers():
    q = jnp.zeros((1, 1, 8, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=4)


@pytest.fixture(scope="module")
def paged_case():
    """Two sequences of 40 positions on shuffled pages of 4; 2 kv heads
    under 4 query heads of 16."""
    ps, kvh, h, d, t = 4, 2, 4, 16, 40
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    k = jax.random.normal(keys[0], (2, t, kvh, d))
    v = jax.random.normal(keys[1], (2, t, kvh, d))
    q = jax.random.normal(keys[2], (2, t, h, d))
    npg = t // ps
    pool_k = np.zeros((1 + 2 * npg, ps, kvh * d), np.float32)
    pool_v = np.zeros_like(pool_k)
    tables = np.zeros((2, npg + 2), np.int32)
    perm = np.random.default_rng(0).permutation(2 * npg) + 1
    for b in range(2):
        for p in range(npg):
            page = int(perm[b * npg + p])
            tables[b, p] = page
            pool_k[page] = np.asarray(k[b, p * ps:(p + 1) * ps]).reshape(
                ps, -1)
            pool_v[page] = np.asarray(v[b, p * ps:(p + 1) * ps]).reshape(
                ps, -1)
    return dict(q=q, ps=ps, tables=tables, pool_k=jnp.asarray(pool_k),
                pool_v=jnp.asarray(pool_v),
                k=jnp.repeat(k, 2, axis=2).transpose(0, 2, 1, 3),
                v=jnp.repeat(v, 2, axis=2).transpose(0, 2, 1, 3))


def slid(tables, first_pos, window, ps):
    """The table as the cache hands it over: scratch in the columns the
    window has slid past."""
    out = tables.copy()
    for b, p in enumerate(first_pos):
        if window:
            out[b, :max(0, p - window + 1) // ps] = 0
    return out


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("window", [None, 3, 8, 13, 40, 64])
def test_windowed_paged_attention(paged_case, window, impl, monkeypatch):
    c = paged_case
    want = dense(c["q"].transpose(0, 2, 1, 3), c["k"], c["v"],
                 window).transpose(0, 2, 1, 3)
    # A decode step: one query a sequence, at positions 39 and 22.
    pos = np.array([[39], [22]], np.int32)
    got = paged_attention(
        c["q"][np.arange(2), pos[:, 0]][:, None], c["pool_k"], c["pool_v"],
        slid(c["tables"], pos[:, 0], window, c["ps"]), pos, force=impl,
        window=window)
    assert float(jnp.abs(got[:, 0] - want[np.arange(2), pos[:, 0]]).max()) \
        < 2e-6
    # A prompt's chunk: 16 rows of sequence 0 from position 20, in two
    # query blocks.
    # (The module itself: raytpu.ops exports a function of its name.)
    monkeypatch.setattr(sys.modules[paged_attention.__module__],
                        "BLOCK_Q", 8)
    rows = np.arange(20, 36, dtype=np.int32)[None]
    got = paged_attention(
        c["q"][:1, 20:36], c["pool_k"], c["pool_v"],
        slid(c["tables"][:1], [20], window, c["ps"]), rows, force=impl,
        window=window)
    assert float(jnp.abs(got - want[:1, 20:36]).max()) < 2e-6


# ---- the cache manager by kind --------------------------------------------------


def two_kinds(seqs=2, burst=6, pages=200):
    return PagedKVCache(
        4, pages, 4, 1, 8, layer_windows=(8, None, 8, None),
        window_pages=PagedKVCache.window_pool_pages(8, 4, seqs, burst),
        window_burst=burst)


class TestCacheByKind:
    def test_pools_by_kind(self):
        c = two_kinds()
        # A seat is ceil(8 / 4) + 1 = 3 pages, a burst of 6 tokens 2
        # more: 2 x 3 + 2 + scratch.
        assert (c.window_seq_pages, c.window_burst_pages) == (3, 2)
        assert [a.shape[0] for a in c.k] == [9, 200, 9, 200]
        assert c.layer_kinds == (1, 0, 1, 0) and c.kinds == (0, 1)
        assert c.max_window_seqs == 2

    def test_window_table_stays_bounded_and_pages_return(self):
        c = two_kinds()
        assert c.allocate("a", 13)
        pos = 0
        while pos < 13:  # the prompt in chunks of 6
            take = min(6, 13 - pos)
            c.slide("a", pos, pos + take)
            assert len(c.window_table("a")[1]) \
                <= c.window_seq_pages + c.window_burst_pages
            dests = c.chunk_dests("a", pos, take, 8, kind=1)
            assert all(d >= 4 for d in dests[:take])  # none to scratch
            pos += take
            c.slide("a", pos, pos)
            assert len(c.window_table("a")[1]) <= c.window_seq_pages
        released = 0
        for p in range(13, 13 + 10 * 8):  # ten windows of decoding
            assert c.extend("a", p + 1)
            released += c.slide("a", p, p + 1)
            first, pages = c.window_table("a")
            assert len(pages) <= c.window_seq_pages
            assert first == max(0, p - 7) // 4
            assert first + len(pages) == p // 4 + 1
            assert c.slot("a", p, kind=1) == pages[-1] * 4 + p % 4
            assert c.pages_read(p, 1) == len(pages)
            assert c.pages_read(p) == p // 4 + 1 == c.num_seq_pages("a")
        assert released == 20 and c.window_pages_owned() == 3
        row = c.table_array(["a"], 30, kind=1)[0]
        assert list(np.nonzero(row)[0]) == [21, 22, 23]
        assert c.table_array(["a"], 30)[0, :24].all()

    def test_both_kinds_must_have_room(self):
        c = two_kinds(seqs=2)
        assert c.allocate("a", 5) and c.allocate("b", 5)
        assert not c.allocate("c", 5)       # no third window seat
        assert "c" not in c._tables and c.free_pages() == 199 - 4
        c.free("a")
        assert c.allocate("c", 5)
        small = two_kinds(seqs=2, pages=4)
        assert small.allocate("a", 9)
        assert not small.allocate("b", 9)   # the full pools are spent
        assert "b" not in small._wtables

    def test_free_is_idempotent_and_returns_both_kinds(self):
        c = two_kinds()
        c.allocate("a", 20)
        c.slide("a", 20, 21)
        assert c.window_pages_owned() == 3 and c.utilization() > 0
        c.free("a")
        c.free("a")
        assert c.window_pages_owned() == 0 and c.used_pages() == 0
        assert c.utilization() == 0.0 and len(c._wfree) == 8
        with pytest.raises(KeyError):
            c.slide("a", 0, 1)

    def test_utilization_is_over_both_kinds(self):
        c = two_kinds()
        c.allocate("a", 40)  # 10 full pages
        c.slide("a", 40, 41)
        assert c.utilization() == pytest.approx((10 + 3) / (199 + 8))

    def test_a_whole_prompts_rows_left_of_the_window_go_to_scratch(self):
        c = two_kinds()
        c.allocate("a", 21)
        c.slide("a", 21, 21)
        first, pages = c.window_table("a")
        assert (first, len(pages)) == (3, 3)  # positions 12..23
        dests = c.chunk_dests("a", 0, 21, 24, kind=1)
        assert all(d < 4 for d in dests[:12]) and all(d < 4
                                                      for d in dests[21:])
        assert [int(d) // 4 for d in dests[12:21]] \
            == [pages[0]] * 4 + [pages[1]] * 4 + [pages[2]]
        full = c.chunk_dests("a", 0, 21, 24)
        assert all(d >= 4 for d in full[:21])

    def test_one_kind_is_what_it_was(self):
        c = PagedKVCache(3, 10, 4, 2, 8)
        assert c.kinds == (0,) and c.window is None
        assert [a.shape for a in c.k] == [(10, 4, 16)] * 3
        assert c.allocate("a", 9) and c.slide("a", 0, 9) == 0
        assert c.block_table("a") == [1, 2, 3]
        assert list(c.chunk_dests("a", 2, 5, 8)) \
            == [6, 7, 8, 9, 10, 5 % 4, 6 % 4, 7 % 4]
        assert c.utilization() == pytest.approx(3 / 9)
        with pytest.raises(IndexError):
            c.chunk_dests("a", 8, 5, 8)

    def test_no_sharing_over_window_layers(self):
        c = two_kinds()
        with pytest.raises(ValueError, match="window"):
            PrefixCache(c)
        with pytest.raises(ValueError, match="not shared"):
            c.allocate_shared("a", 9, [1, 2])
        with pytest.raises(ValueError, match="window_pages"):
            PagedKVCache(2, 10, 4, 1, 8, layer_windows=(8, None))
        with pytest.raises(ValueError, match="one window"):
            PagedKVCache(2, 10, 4, 1, 8, layer_windows=(8, 16),
                         window_pages=20)


# ---- the model, served, against the reference -----------------------------------


def served_logits(cfg, params, prompt, new, **engine):
    """Every logit row the engine's programs produce for one request:
    the prompt's last row, then one a decoded position."""
    eng = InferenceEngine(cfg, params, **dict(ENGINE, **engine))
    rows = []

    def keep(fn, pick):
        def kept(*a):
            res = fn(*a)
            rows.extend(pick(np.asarray(res[0])))
            return res
        return kept

    take = {"n": 0}
    eng._prefill_fn = keep(eng._prefill_fn, lambda lg: [lg[len(prompt) - 1]])
    chunk = eng._chunk_fn

    def chunk_kept(*a):
        res = chunk(*a)
        take["n"] += 1
        if eng.scheduler.running[0].cached_len + a[3].shape[1] \
                >= len(prompt) and not eng.scheduler.running[0].generated:
            last = (len(prompt) - 1) % eng.prefill_chunk
            rows.append(np.asarray(res[0])[0, last])
        return res

    eng._chunk_fn = chunk_kept
    eng._decode_fn = keep(eng._decode_fn, lambda lg: [lg[0]])
    out = eng.generate([prompt], SamplingParams(max_new_tokens=new))[0]
    return eng, out, np.stack(rows[:new])


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk", [None, 8])
def test_served_logits_are_the_references(family, params, impl, chunk):
    """A prompt of 29 tokens (whole, or in chunks of 8) and 20 decoded
    positions through both kinds of pool: 49 positions, six windows, past
    YaRN's original 32."""
    cfg = dataclasses.replace(TINY, attn_impl=impl, paged_attn=impl)
    prompt = prompts(29)[0]
    eng, out, got = served_logits(cfg, params, prompt, 20,
                                  prefill_chunk=chunk)
    want = np.asarray(family.logits(
        file_config(TINY), params, jnp.asarray([prompt + out[:-1]])))[0]
    assert rel_err(got, want[len(prompt) - 1:]) < 1e-4
    stats = eng.stats()
    assert bool(stats["chunk_prefill_compiles"]) == (chunk is not None)
    log = eng.step_log()["steps"]
    assert sum(s["window_pages_released"] for s in log) >= 5
    assert max(s["live_pages_window"] for s in log) <= 3
    assert max(s["live_pages_full"] for s in log) == 47 // 4 + 1
    assert eng.cache.window_pages_owned() == 0


def test_controls_fail_where_the_program_passes(family, params):
    """What the cell's check must catch, at the tiny size: the window
    ignored, plain rope on the full layers, the router not renormalised."""
    prompt = prompts(40)[0]
    tokens = jnp.asarray([prompt])
    want = np.asarray(family.logits(file_config(TINY), params, tokens))[0]
    model = lambda c: np.asarray(Mellum(c).apply(  # noqa: E731
        {"params": params}, tokens))[0]
    assert rel_err(model(TINY), want) < 1e-4
    for wrong in (dict(layer_types=(FULL,) * 8),
                  dict(full_rope=None),
                  dict(norm_topk_prob=False)):
        assert rel_err(model(dataclasses.replace(TINY, **wrong)), want) \
            > 1e-2, wrong


def test_batched_decode_is_solo_decode(params):
    eng = InferenceEngine(TINY, params, **ENGINE)
    batch = prompts(5, 21, 37)
    together = eng.generate(batch, SamplingParams(max_new_tokens=12))
    for prompt, out in zip(batch, together):
        solo = InferenceEngine(TINY, params, **ENGINE).generate(
            [prompt], SamplingParams(max_new_tokens=12))[0]
        assert solo == out


@pytest.mark.parametrize("chunk", [None, 8])
def test_a_step_of_two_kinds_is_handed_what_the_loop_built(params, chunk):
    """Every call of the decode program against the loop that built its
    inputs before the tables were kept (``kept_tables``): both kinds'
    tables and dests, the rows a window table has slid past among them,
    with sequences that come and go, and greedy tokens a sequence what it
    decodes alone. A kind's table goes over again when one of its rows
    has changed, and both with the batch."""
    from kept_tables import watch_decode

    eng = InferenceEngine(TINY, params, prefill_chunk=chunk, **ENGINE)
    calls = watch_decode(eng)
    batch, new = prompts(5, 21, 37, 11), (30, 12, 9, 17)
    outs, batches = {f"r{i}": [] for i in range(4)}, []
    for i, (prompt, n) in enumerate(zip(batch, new)):
        eng.add_request(f"r{i}", prompt, SamplingParams(max_new_tokens=n))
    while eng.has_unfinished():
        # (A sequence whose last token is in flight takes no row.)
        decoding = [s.request_id for s in eng.scheduler.running
                    if s.cached_len >= s.prefill_len
                    and not eng.scheduler._last_in_flight(s)]
        for o in eng.step():
            outs[o.request_id].append(o.token_id)
        batches.append(decoding)
    for prompt, n, out in zip(batch, new, outs.values()):
        assert InferenceEngine(TINY, params, **ENGINE).generate(
            [prompt], SamplingParams(max_new_tokens=n))[0] == out
    # Tokens, positions, context lengths and a kind's dests each step,
    # and the tables that moved: a page of 4 fills every fourth step a
    # sequence, and the window table gives one back as often.
    assert all(puts == 5 + 2 - reused for reused, puts in calls)
    assert {reused for reused, _ in calls} == {0, 1, 2}
    decoded = [b for b in batches if b]
    assert len(decoded) == len(calls)
    for before, now, (reused, _) in zip(decoded, decoded[1:], calls[1:]):
        assert reused == 0 or before == now
    stats = eng.stats()
    assert stats["table_puts"] + stats["table_reuses"] == 2 * len(calls)
    assert stats["table_reuses"] == sum(reused for reused, _ in calls)
    assert eng.cache.window_pages_owned() == 0


def test_engine_sizes_and_reports_pools_by_kind(params):
    eng = InferenceEngine(TINY, params, prefill_chunk=8, **ENGINE)
    c = eng.cache
    # Window 8 on pages of 4: a seat of 3, a burst of ceil(15 / 4) + 1
    # - 3 = 2; four sequence slots.
    assert c.num_window_pages == 4 * 3 + 2 + 1
    by_kind = eng.stats()["kv_pool_bytes_by_kind"]
    row = 2 * 16 * 4  # kv heads x head_dim x float32
    assert by_kind == {"full": 2 * 2 * c.num_pages * 4 * row,
                       "window": 2 * 6 * 15 * 4 * row}
    assert sum(by_kind.values()) == eng.stats()["kv_pool_bytes"]
    assert eng.prefix_cache is None
    with pytest.raises(ValueError, match="prefix cache"):
        InferenceEngine(TINY, params, enable_prefix_cache=True, **ENGINE)
    with pytest.raises(ValueError, match="one device"):
        InferenceEngine(TINY, params, tp=2, **ENGINE)
    with pytest.raises(ValueError, match="chunk_buckets"):
        InferenceEngine(TINY, params, prefill_chunk=16, chunk_buckets=[8],
                        **ENGINE)


def test_a_one_kind_engine_is_what_it_was():
    cfg = dataclasses.replace(llama_mod.LlamaConfig.tiny(), **F32)
    eng = InferenceEngine(cfg, llama_mod.init_params(
        llama_mod.Llama(cfg), cfg), page_size=4, max_num_seqs=2,
        max_model_len=32)
    assert eng.prefix_cache is not None and eng.cache.kinds == (0,)
    assert eng.stats()["kv_pool_bytes_by_kind"]["window"] == 0
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_new_tokens=3))
    log = eng.step_log()["steps"]
    assert all(s["live_pages_window"] == 0 == s["window_pages_released"]
               and s["live_pages_full"] == s["live_pages"] for s in log)


def test_a_window_model_takes_no_disaggregated_role():
    from raytpu.inference.serving import LLMDeployment

    with pytest.raises(ValueError, match="window layers"):
        LLMDeployment._target(model="mellum", role="prefill")


# ---- training forward -------------------------------------------------------------


def test_loss_and_gradients_against_the_reference(family, params):
    from raytpu.models.mixtral import mixtral_loss_fn

    cfg = dataclasses.replace(TINY, router_aux_coef=0.0)
    tokens = jnp.asarray(prompts(48, 48, seed=3))
    want, wanted = jax.value_and_grad(
        lambda p: family.loss(file_config(TINY), p, tokens))(params)
    for impl in IMPLS:  # a windowed backward takes the masked reference
        c = dataclasses.replace(cfg, attn_impl=impl)
        got, grads = jax.value_and_grad(
            lambda p: mixtral_loss_fn(Mellum(c), p, tokens))(params)
        assert float(got) == pytest.approx(float(want), rel=1e-5)
        worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda g, w: float(jnp.abs(g - w).max()
                               / (jnp.abs(w).max() + 1e-12)),
            grads, wanted)))
        assert worst < 2e-3, (impl, worst)


# ---- the chip script, rehearsed -------------------------------------------------


@pytest.mark.parametrize("phase,extra", [
    ("check", []), ("long", ["--tokens", "50", "80", "--controls"]),
    ("long", ["--tokens", "50", "55", "60", "--together"])])
def test_chip_mellum_rehearsal(phase, extra, capsys):
    """``chip_mellum.py`` at the benchmark's tiny configuration: the
    program inside 1e-4 of the reference through the whole-prompt program
    (``check``) and through chunks and both kinds of pool at two context
    lengths (``long``; ``--together``: three prompts of four chunks in
    one engine, decoding in one batch), and the three controls that bite
    in float32 far outside it (the fourth rounds bf16 matrices, of which
    a float32 tree has none)."""
    import json

    import chip_mellum

    tests = os.path.join(ROOT, "perfbench", "tests", "mellum")
    rc = chip_mellum.main([
        phase, "--cpu", "--seeds", "5",
        "--config", os.path.join(tests, "configs", "tiny-mellum.json"),
        "--mix", os.path.join(tests, "traffic", "tiny-long-decode.json")]
        + extra)
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["worst_rel_err"] < 1e-5
    if "--together" in extra:
        assert rc == 0 and result["ok"] is True
        (r,) = result["results"]
        assert r["prompt_tokens"] == [50, 55, 60]
        assert r["live_pages_window_max"] >= 3 * 2  # one batch of three
        assert all(k.startswith("4x")
                   for k in r["programs"]["decode_compiles"])
        return
    assert rc == 1 and result["ok"] is False  # float8 cannot fail here
    judged = result["results"][-1]
    assert judged["forced"]["max"] < 1e-5
    assert min(judged[c]["median"] for c in chip_mellum.CONTROLS[:3]) > 0.02
    assert judged["float8"]["max"] < 1e-5
    assert [judged["caught_by"][c] for c in chip_mellum.CONTROLS[:3]] \
        == ["max"] * 3
    assert judged["caught_by"]["float8"] is None
    if phase == "long":
        assert [r["prompt_tokens"] for r in result["results"]] \
            == [[80], [50]]
        assert all(r["programs"]["chunk_prefill_compiles"]
                   and not r["programs"]["prefill_compiles"]
                   for r in result["results"])
        assert all(r["live_pages_window_max"] == 3
                   for r in result["results"])
