"""LFM2-24B-A2B: gated short convolutions in three layers of four, which
keep a state a sequence at its seat and no keys or values, beside
full-attention layers' paged pools, two leading dense layers and a
sigmoid-routed expert layer. All at a tiny size on the CPU
(``Lfm2MoeConfig.tiny``: conv conv.dense | full conv conv conv; of 8
experts a token takes 2), page size 4, float32.

The served engine is held to the benchmark's plain float32 reference
(``perfbench/families/lfm2_moe.py``, written from the layer equations and
not from the program: no cache, no state carried, the convolution as
shifted adds): in float32 they choose the same experts and agree to
rounding, 1e-4 of the largest reference logit, at every position of a
prompt, through chunks of any length, and at every decoded row.
"""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from raytpu.inference import InferenceEngine, PagedKVCache
from raytpu.inference.prefix_cache import PrefixCache
from raytpu.inference.sampling import SamplingParams
from raytpu.inference.scheduler import Scheduler, Sequence
from raytpu.models.mixtral import Lfm2Moe, Lfm2MoeConfig, init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dataclasses.replace(
    Lfm2MoeConfig.tiny(), dtype=jnp.float32, attn_impl="reference",
    paged_attn="reference", remat=False, choice_bias=0.05)
ENGINE = dict(page_size=4, max_num_seqs=4, max_model_len=96)
# Float32 rounding between two orders of the same sums, over the largest
# reference logit.
ROUNDING = 1e-4


@pytest.fixture(scope="module")
def family():
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families",
                       "lfm2_moe")


@pytest.fixture(scope="module")
def params():
    return init_params(Lfm2Moe(TINY), TINY, seed=1, batch=1)


def file_config(c: Lfm2MoeConfig):
    """The configuration file the family's reference reads, for ``c``."""
    return {
        "family": "lfm2_moe", "vocab_size": c.vocab_size,
        "max_position_embeddings": c.block_size,
        "num_hidden_layers": c.n_layer, "num_attention_heads": c.n_head,
        "num_key_value_heads": c.n_kv_head, "hidden_size": c.n_embd,
        "intermediate_size": c.dense_inter,
        "moe_intermediate_size": c.n_inter, "num_experts": c.n_expert,
        "num_experts_per_tok": c.n_expert_per_tok,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scale, "use_expert_bias": True,
        "num_dense_layers": c.first_dense,
        "layer_types": list(c.layer_types), "conv_L_cache": c.conv_taps,
        "conv_bias": False, "norm_eps": c.norm_eps,
        "rope_parameters": {"rope_theta": c.rope_theta,
                            "rope_type": "default"},
        "assumed": {"expert_bias_std": c.choice_bias,
                    "norm_topk_sum_eps": c.topk_sum_eps,
                    "tie_word_embeddings": c.tie_embeddings},
        "compute_dtype": "float32", "param_dtype": "float32"}


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, TINY.vocab_size, size=n)]
            for n in lengths]


class Recording(InferenceEngine):
    """An engine that keeps the logits of every row its programs computed
    for a sequence, by (request, position): a whole prompt's, a chunk's
    and a decode's alike."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.rows = {}
        for name in ("_prefill_fn", "_chunk_fn", "_decode_fn"):
            setattr(self, name, self._keeping(getattr(self, name)))

    def _keeping(self, fn):
        def kept(*a):
            res = fn(*a)
            self._last = res[0]
            return res

        return kept

    def _run_prefill(self, seq, out):
        before = seq.cached_len
        n = super()._run_prefill(seq, out)
        logits = np.asarray(self._last)
        logits = logits.reshape(-1, logits.shape[-1])
        for j in range(seq.cached_len - before):
            self.rows[seq.request_id, before + j] = logits[j]
        return n

    def _run_decode(self, seqs, out):
        before = [s.cached_len for s in seqs]
        n = super()._run_decode(seqs, out)
        logits = np.asarray(self._last)
        for i, (seq, at) in enumerate(zip(seqs, before)):
            self.rows[seq.request_id, at] = logits[i]
        return n


def serve(params, requests, new_tokens=6, engine=None, **options):
    """Run ``requests`` (prompts) greedily to their end in one engine.
    Returns the tokens a request and the engine."""
    eng = engine or Recording(TINY, params, **{**ENGINE, **options})
    first = len(eng.rows)
    ids = [f"r{first}-{i}" for i in range(len(requests))]
    for rid, prompt in zip(ids, requests):
        eng.add_request(rid, prompt, SamplingParams(
            max_new_tokens=new_tokens))
    tokens = {rid: [] for rid in ids}
    while eng.has_unfinished():
        for o in eng.step():
            tokens[o.request_id].append(o.token_id)
    return [tokens[rid] for rid in ids], ids, eng


def moved(family, params, eng, rid, prompt, generated):
    """Of every row the engine computed for ``rid``, its largest
    difference from the reference's row of that position, teacher-forced
    over the tokens served, over the largest reference logit."""
    tokens = prompt + generated[:-1]
    want = np.asarray(family.logits(file_config(TINY), params,
                                    jnp.asarray([tokens])))[0]
    got = np.stack([eng.rows[rid, p] for p in range(len(tokens))])
    return float(np.abs(got - want).max() / np.abs(want).max())


# ---- the config and what the engine builds of it ------------------------------


def test_published_values_and_the_cache_it_builds(params):
    c = Lfm2MoeConfig()
    assert (c.n_layer, c.n_embd, c.n_head, c.n_kv_head, c.head_dim) \
        == (40, 2048, 32, 8, 64)
    assert (c.n_expert, c.n_expert_per_tok, c.n_inter, c.dense_inter,
            c.n_shared, c.first_dense, c.conv_taps, c.tie_embeddings) \
        == (64, 4, 1536, 11776, 0, 2, 3, True)
    assert c.layer_types.count("conv") == 30 \
        and c.layer_types[:6] == ("conv", "conv", "full_attention",
                                  "conv", "conv", "conv")
    served = c.serving
    assert served.layer_states[:3] == ((2, 2048), (2, 2048), None)
    assert served.layer_windows == (None,) * 10
    assert served.expert_counts == (38, 64)
    # The tiny model: one attention layer's pool, five state arrays.
    eng = InferenceEngine(TINY, params, **ENGINE)
    cache = eng.cache
    assert len(cache.k) == len(cache.v) == 1 and len(cache.state) == 5
    assert all(a.shape == (4 + 1, 2, 64) for a in cache.state)
    assert cache.token_bytes == 2 * 2 * 8 * 4
    assert cache.state_bytes == 5 * 2 * 64 * 4
    assert eng.prefix_cache is None
    stats = eng.stats()
    assert (stats["state_seats"], stats["state_seats_total"],
            stats["state_bytes"]) == (0, 4, 5 * cache.state_bytes)
    assert "lm_head" not in params and set(params["layers_0"]["conv"]) \
        == {"in_proj", "kernel", "out_proj"}


def test_uncut_parameter_count_is_the_models_name(family):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    whole, a_token = family.published_param_counts(cfg)
    assert abs(whole / 23.98e9 - 1) < 0.01
    assert abs(a_token / 2.33e9 - 1) < 0.01


# ---- the engine against the reference -------------------------------------------


@pytest.mark.parametrize("chunk", [None, 1, 2, 3, 7],
                         ids=["whole-padded", "chunks-1", "chunks-2",
                              "chunks-3", "chunks-7"])
def test_prompt_rows_and_decoded_rows_agree_with_the_reference(
        family, params, chunk):
    """One prompt of 23 tokens whole (a bucket of 32: the state is that
    of the last live row) or through chunks of 1, 2, 3 and 7 rows (the
    last of 1, 1, 2 and 2 live rows: a chunk shorter than the state
    carries the old state's newest row on; a padded last chunk leaves
    the last live row's), then 6 decoded rows."""
    (prompt,) = prompts(23)
    (out,), (rid,), eng = serve(params, [prompt], prefill_chunk=chunk)
    programs = eng.stats()
    assert bool(programs["chunk_prefill_compiles"]) == (chunk is not None)
    assert bool(programs["prefill_compiles"]) == (chunk is None)
    assert moved(family, params, eng, rid, prompt, out) < ROUNDING


def test_a_batch_gives_each_sequence_what_it_gets_alone(family, params):
    batch = prompts(5, 23, 40, seed=3)
    together, ids, eng = serve(params, batch, prefill_chunk=16)
    for prompt, out, rid in zip(batch, together, ids):
        (alone,), _, _ = serve(params, [prompt], prefill_chunk=16)
        assert out == alone
        assert moved(family, params, eng, rid, prompt, out) < ROUNDING
    steps = eng.step_log()["steps"]
    assert max(s["state_seats"] for s in steps) == 3
    assert max(s["state_bytes"] for s in steps) == 3 * eng.cache.state_bytes
    assert steps[-1]["state_seats"] == 0 == eng.cache.seats_in_use()


@pytest.mark.parametrize("chunk", [None, 4], ids=["whole", "chunks"])
def test_a_seat_reused_after_finish_starts_from_zeros(family, params, chunk):
    first, second = prompts(9, 11, seed=5)
    _, _, eng = serve(params, [first], max_num_seqs=1, prefill_chunk=chunk)
    assert np.abs(np.asarray(eng.cache.state[0][1])).max() > 0
    (out,), (rid,), _ = serve(params, [second], engine=eng)
    assert moved(family, params, eng, rid, second, out) < ROUNDING


def test_preempted_and_resumed_gives_the_tokens_of_an_unpreempted_run(
        family, params):
    batch = prompts(10, 12, 9, seed=7)
    calm, _, _ = serve(params, batch, new_tokens=20)
    # 12 pages of 4 hold the three prompts and not their 20 tokens more.
    tight, ids, eng = serve(params, batch, new_tokens=20, num_pages=13)
    assert eng.stats()["num_preemptions"] > 0
    assert tight == calm
    for prompt, out, rid in zip(batch, tight, ids):
        assert moved(family, params, eng, rid, prompt, out) < ROUNDING


@pytest.mark.parametrize("pages", [None, 13])
def test_a_step_with_seats_is_handed_what_the_loop_built(params, pages):
    """Every call of the decode program against the loop that built its
    inputs before the tables were kept (``kept_tables``): the seats go
    over with the small rows in one put, the one kind's table when a row
    of it has moved; on 13 pages a sequence is preempted and comes back."""
    from kept_tables import watch_decode

    batch = prompts(10, 12, 9, seed=7)
    calm, _, _ = serve(params, batch, new_tokens=20)
    eng = Recording(TINY, params, **{
        **ENGINE, **({"num_pages": pages} if pages else {})})
    calls = watch_decode(eng)
    tight, _, _ = serve(params, batch, new_tokens=20, engine=eng)
    assert tight == calm
    assert (eng.stats()["num_preemptions"] > 0) == bool(pages)
    # Seats, tokens, positions, dests, context lengths; the table.
    assert {reused for reused, _ in calls} == {0, 1}
    assert all(puts == 5 + 1 - reused for reused, puts in calls)


def test_admission_stops_at_the_last_seat_with_pages_to_spare():
    cache = PagedKVCache(1, 64, 4, 2, 16, state_shapes=[[((2, 64), None)]],
                         seats=2)
    sched = Scheduler(cache, max_num_seqs=4, max_model_len=96)
    for i in range(3):
        sched.add(Sequence(f"s{i}", [1, 2, 3]))
    plan = sched.schedule()
    assert [s.request_id for s in plan.prefills] == ["s0", "s1"]
    assert len(sched.waiting) == 1 and cache.free_pages() > 50
    assert sorted(cache.seat(f"s{i}") for i in range(2)) == [1, 2]
    assert not cache.allocate("other", 3)
    # A seat goes back with the pages, and the one waiting takes it.
    seat = cache.seat("s0")
    sched.finish(plan.prefills[0], "stop")
    assert [s.request_id for s in sched.schedule().prefills[-1:]] == ["s2"]
    assert cache.seat("s2") == seat and cache.seats_in_use() == 2


def test_a_drafting_engine_seats_its_module_by_the_same_map():
    from raytpu.models.mixtral import ExaoneMoe, ExaoneMoeConfig

    cfg = dataclasses.replace(
        ExaoneMoeConfig.tiny(), dtype=jnp.float32, attn_impl="reference",
        paged_attn="reference", remat=False)
    eng = InferenceEngine(cfg, init_params(ExaoneMoe(cfg), cfg, seed=1,
                                           batch=1), **ENGINE)
    assert not hasattr(eng, "_slot_of") and not eng.cache.state
    assert eng.cache.total_seats == ENGINE["max_num_seqs"]
    eng.add_request("a", prompts(9)[0], SamplingParams(max_new_tokens=3))
    eng.step()
    assert eng.cache.seat("a") >= 1 and eng.stats()["state_seats"] == 1
    while eng.has_unfinished():
        eng.step()
    assert eng.cache.seats_in_use() == 0


# ---- what refuses such a model, by name -----------------------------------------


def test_prefix_cache_hand_off_and_a_sharded_engine_refuse(params):
    with pytest.raises(ValueError, match="keep a state"):
        InferenceEngine(TINY, params, enable_prefix_cache=True, **ENGINE)
    with pytest.raises(ValueError, match="keep a state"):
        PrefixCache(PagedKVCache(
            1, 8, 4, 2, 16, state_shapes=[[((2, 64), None)]], seats=2))
    with pytest.raises(ValueError, match="keep a state"):
        InferenceEngine(TINY, params, tp=2, **ENGINE)
    from raytpu.inference.serving import LLMDeployment

    deployment = LLMDeployment._target
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="keep a state"):
            deployment(model="lfm2_moe", engine_options=ENGINE, role=role)
