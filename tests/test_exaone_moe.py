"""K-EXAONE-236B-A23B: window layers (roped) among full ones that see no
positions, a norm over each head of q and of k, eight (here four) query
heads a kv head, a sigmoid-routed expert layer of which a share is held,
and a prediction module through which the served model drafts for itself:
a decode step verifies two positions a sequence and yields one token or
two. All at a tiny size on the CPU (``ExaoneMoeConfig.tiny``: the dense
layer, one period S S F S after it and the module; window 8, shorter than
every context here; experts 2 to 5 of 8 held), page size 4, float32.

The model is held to the benchmark's plain float32 reference
(``perfbench/families/exaone_moe.py``, written from the layer equations
and not from the program): in float32 they choose the same experts and
agree to rounding, 1e-4 of the largest reference logit. The engine's rows
are read by (request, position) through the benchmark's own contract
(``perfbench.probe.kept_rows``).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import probe
from raytpu.inference import InferenceEngine, PagedKVCache
from raytpu.inference import sampling
from raytpu.inference.sampling import SamplingParams
from raytpu.inference.scheduler import Scheduler, Sequence
from raytpu.models.mixtral import (ExaoneMoe, ExaoneMoeConfig, MoEFFN,
                                   init_params)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = dict(dtype=jnp.float32, attn_impl="reference",
           paged_attn="reference", remat=False, choice_bias=0.05)
TINY = dataclasses.replace(ExaoneMoeConfig.tiny(), experts_held=(2, 4),
                           **F32)
ENGINE = dict(page_size=4, max_num_seqs=4, max_model_len=96)
# Float32 rounding between two orders of the same sums, over the largest
# reference logit: the program's rows stand at 1e-6 to 1e-5.
ROUNDING = 1e-4


@pytest.fixture(scope="module")
def family():
    from perfbench.byname import load_module

    return load_module([os.path.join(ROOT, "perfbench")], "families",
                       "exaone_moe")


@pytest.fixture(scope="module")
def params():
    return init_params(ExaoneMoe(TINY), TINY, seed=1, batch=1)


def file_config(c: ExaoneMoeConfig, held=None):
    """The configuration file the family's reference reads, for ``c``."""
    first, count = held or c.experts_held or (0, c.n_expert)
    return {
        "family": "exaone_moe", "vocab_size": c.vocab_size,
        "max_position_embeddings": c.block_size,
        "num_hidden_layers": c.n_layer, "num_attention_heads": c.n_head,
        "num_key_value_heads": c.n_kv_head, "hidden_size": c.n_embd,
        "head_dim": c.head_dim, "intermediate_size": c.dense_inter,
        "moe_intermediate_size": c.n_inter, "num_experts": count,
        "published_num_experts": c.n_expert, "experts_held": [first, count],
        "num_shared_experts": c.n_shared,
        "num_experts_per_tok": c.n_expert_per_tok,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scale, "scoring_func": "sigmoid",
        "n_group": 1, "topk_group": 1,
        "first_k_dense_replace": c.first_dense,
        "layer_types": list(c.layer_types),
        "mlp_layer_types": ["dense" if i < c.first_dense else "sparse"
                            for i in range(c.n_layer)],
        "sliding_window": c.window,
        "sliding_windows": [c.window if k == "sliding_attention" else 0
                            for k in c.layer_types],
        "rope_parameters": {"rope_theta": c.rope_theta,
                            "rope_type": "default"},
        "num_nextn_predict_layers": c.mtp_layers,
        "mtp_layer_types": ["full_attention"],
        "rms_norm_eps": c.norm_eps, "hidden_act": "silu",
        "tie_word_embeddings": False,
        "assumed": {"e_score_correction_bias_std": c.choice_bias},
        "compute_dtype": "float32", "param_dtype": "float32"}


def rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float32)
                        - np.asarray(want, np.float32)).max()
                 / np.abs(np.asarray(want)).max())


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, TINY.vocab_size, size=n)]
            for n in lengths]


def serve(cfg, params, requests, *, engine=None, decoded_only=False,
          capture=True, **options):
    """Run ``requests`` (``(prompt, SamplingParams)``) to their end in one
    engine under the probe's capture. Returns the tokens a request, the
    kept rows by (request, position), the module's logits as each step
    left them ``{request: {position of the module's row: logits}}``, and
    the engine. ``decoded_only``: not the prompt's last row (a chunk's
    program is not captured); ``capture=False``: no rows (a preempted
    sequence's second prefill computes its rows a second time)."""
    eng = engine or probe.ProbedEngine(cfg, params, **{**ENGINE, **options})
    captured = eng.capture_logits() if capture else None
    seqs = {f"r{i}": eng.add_request(f"r{i}", p, s)
            for i, (p, s) in enumerate(requests)}
    tokens = {rid: [] for rid in seqs}
    drafts = {rid: {} for rid in seqs}
    while eng.has_unfinished():
        for o in eng.step():
            tokens[o.request_id].append(o.token_id)
        if eng._drafting is not None:
            state = np.asarray(eng._draft_state[1])
            for rid, slot in eng.cache._seats.items():
                seq = seqs[rid]
                if seq.cached_len >= seq.prefill_len:  # its prompt is in
                    drafts[rid][seq.cached_len - 1] = state[slot]
    if not capture:
        return tokens, None, drafts, eng
    eng.stop_capture()
    rows = probe.kept_rows(captured, {
        rid: range(len(s.prompt) - 1 + decoded_only, s.num_tokens - 1)
        for rid, s in seqs.items()})
    return tokens, rows, drafts, eng


# ---- the config and the parameter tree ------------------------------------------


class TestConfig:
    def test_published_values(self):
        c = ExaoneMoeConfig()
        assert (c.n_layer, c.n_embd, c.n_head, c.n_kv_head, c.head_dim) \
            == (48, 6144, 64, 8, 128)
        assert (c.n_expert, c.n_expert_per_tok, c.n_inter, c.dense_inter,
                c.n_shared, c.first_dense) == (128, 8, 2048, 18432, 1, 1)
        assert (c.window, c.vocab_size, c.rope_theta, c.routed_scale,
                c.scoring, c.mtp_layers) \
            == (128, 153600, 1e6, 2.5, "sigmoid", 1)
        assert c.layer_types[:8] == ("sliding_attention",) * 3 + (
            "full_attention",) + ("sliding_attention",) * 3 + (
            "full_attention",)
        assert c.layer_types.count("full_attention") == 12

    def test_only_window_layers_are_roped(self):
        assert TINY.rope_of("full_attention") is None
        assert TINY.rope_of("sliding_attention") == TINY.rope_theta
        assert dataclasses.replace(TINY, rope_kinds=None).rope_of(
            "full_attention") == TINY.rope_theta

    def test_the_program_config_of_a_file_is_the_config(self, family):
        got = family.program_config(file_config(TINY), F32)
        assert got == dataclasses.replace(TINY, remat=False)

    def test_param_tree(self, params):
        attn = params["layers_3"]["attn"]
        assert attn["q_norm"]["scale"].shape == (16,)   # one head's values
        assert attn["k_norm"]["scale"].shape == (16,)
        assert "mlp" in params["layers_0"] and "moe" in params["layers_1"]
        assert params["layers_1"]["moe"]["wg"].shape == (4, 64, 32)
        m = params["mtp"]
        assert sorted(m) == ["block", "eh_proj", "enorm", "final_norm",
                             "hnorm"]
        assert m["eh_proj"]["kernel"].shape == (128, 64)
        assert sorted(m["block"]) == ["attn", "input_norm", "moe",
                                      "post_attn_norm"]

    def test_one_module_at_most(self):
        with pytest.raises(ValueError, match="one prediction module"):
            dataclasses.replace(TINY, mtp_layers=2)

    def test_counts_are_the_trees(self, family, params):
        leaves = sum(a.size for a in jax.tree_util.tree_leaves(params))
        assert family.param_count(file_config(TINY)) == leaves
        assert family.moe_shape(file_config(TINY))[:3] == (5, 4, 2)
        assert family.layers_by_kind(file_config(TINY)) == (2, 4)
        assert family.kv_shape(file_config(TINY))[0] == 6


# ---- the model against the reference --------------------------------------------


def test_training_forward_is_the_references(family, params):
    tokens = jnp.asarray(prompts(40, 40, seed=2))
    want = family.logits(file_config(TINY), params, tokens)
    got = ExaoneMoe(TINY).apply({"params": params}, tokens)
    assert rel_err(got, want) < ROUNDING


def test_controls_fail_where_the_program_passes(family, params):
    """What the cell's check must catch, at the tiny size and in float32:
    each departure from the equations moves the logits far outside the
    1e-4 the right program stands inside."""
    tokens = jnp.asarray(prompts(40))
    want = np.asarray(family.logits(file_config(TINY), params, tokens))[0]
    for wrong in (dict(rope_kinds=None), dict(qk_head_norm=False),
                  dict(routed_scale=1.0), dict(window=64),
                  dict(norm_topk_prob=False)):
        c = dataclasses.replace(TINY, **wrong)
        p = params
        if "qk_head_norm" in wrong:  # a tree without the norms' scales
            p = init_params(ExaoneMoe(c), c, seed=1, batch=1)
        got = np.asarray(ExaoneMoe(c).apply({"params": p}, tokens))[0]
        assert rel_err(got, want) > 1e-3, wrong


class TestRouting:
    @pytest.fixture(scope="class")
    def x(self):
        return jax.random.normal(jax.random.PRNGKey(3), (40, 64))

    def test_program_layer_is_the_references(self, family, params, x):
        moe = params["layers_1"]["moe"]
        got, counts = MoEFFN(TINY).apply({"params": moe}, x)
        with jax.default_matmul_precision("highest"):
            want = family._experts(file_config(TINY), moe, x)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_the_eight_shares_add_up_to_the_whole_layer(self, family, x):
        """Eight chips hold one expert each (``experts_held`` (i, 1), all
        8 settings). Their routed parts, with the shared expert counted
        once, are the uncut layer; a pair whose expert is elsewhere costs
        no row."""
        whole_cfg = dataclasses.replace(TINY, experts_held=None)
        moe = init_params(ExaoneMoe(whole_cfg), whole_cfg, seed=4,
                          batch=1)["layers_2"]["moe"]
        with jax.default_matmul_precision("highest"):
            whole = family._experts(file_config(whole_cfg), moe, x)
            shared = family._swiglu(moe["shared"], x)
        total, pairs = jnp.zeros_like(whole), 0
        for chip in range(8):
            c = dataclasses.replace(TINY, experts_held=(chip, 1), n_shared=0)
            share = {k: v for k, v in moe.items() if k != "shared"}
            share.update({w: moe[w][chip:chip + 1]
                          for w in ("wg", "wi", "wo")})
            part, counts = MoEFFN(c).apply({"params": share}, x)
            assert counts.shape == (1,)
            total, pairs = total + part, pairs + int(counts.sum())
        assert pairs == 40 * 2
        np.testing.assert_allclose(total + shared, whole, atol=5e-5)


# ---- served: a step of two positions ---------------------------------------------


def test_served_rows_are_the_references(family, params):
    """Prefill, then decode through the three kinds of pool with drafting
    on: every row the engine kept, by (request, position), against the
    reference's one forward pass over the tokens the stream received; and
    the module's logits each step left against ``draft_logits``."""
    greedy = SamplingParams(max_new_tokens=14)
    drawn = SamplingParams(max_new_tokens=14, temperature=1.0, seed=9)
    requests = list(zip(prompts(13, 21, 30), (greedy, drawn, drawn)))
    tokens, rows, drafts, eng = serve(TINY, params, requests)
    cfg = file_config(TINY)
    steps = eng.step_log()["steps"]
    assert sum(s["emitted"] for s in steps) == 3 * 13  # the prefill's: 3
    for i, (prompt, _) in enumerate(requests):
        rid = f"r{i}"
        assert len(tokens[rid]) == 14
        seq = jnp.asarray([prompt + tokens[rid]])
        want = np.asarray(family.logits(cfg, params, seq))[0]
        for pos, row in rows[rid].items():
            assert rel_err(row, want[pos]) < ROUNDING, (rid, pos)
        wanted = np.asarray(family.draft_logits(cfg, params, seq))[0]
        assert len(drafts[rid]) >= 7
        for pos, row in drafts[rid].items():
            assert rel_err(row, wanted[pos]) < ROUNDING, (rid, pos)


def test_chunked_prompts_draft_too(family, params):
    """A prompt in chunks of 8 runs the module chunk by chunk: the first
    decode step has its draft, and the rows are the reference's."""
    tokens, rows, drafts, eng = serve(
        TINY, params, [(prompts(27)[0], SamplingParams(max_new_tokens=6))],
        prefill_chunk=8, decoded_only=True)
    assert eng.stats()["chunk_prefill_compiles"]
    seq = jnp.asarray([prompts(27)[0] + tokens["r0"]])
    cfg = file_config(TINY)
    want = np.asarray(family.logits(cfg, params, seq))[0]
    wanted = np.asarray(family.draft_logits(cfg, params, seq))[0]
    # (A chunk's program is not captured: the decoded rows.)
    for pos in range(27, 27 + 5):
        assert rel_err(rows["r0"][pos], want[pos]) < ROUNDING, pos
    assert 26 in drafts["r0"]
    for pos, row in drafts["r0"].items():
        assert rel_err(row, wanted[pos]) < ROUNDING, pos


def test_greedy_drafting_is_no_drafting_token_for_token(params):
    """Sequences that finish at different steps, in one batch."""
    greedy = [(p, SamplingParams(max_new_tokens=n))
              for p, n in zip(prompts(11, 19, 30, 7), (9, 14, 6, 22))]
    on, rows_on, _, eng = serve(TINY, params, greedy)
    off, rows_off, _, plain = serve(TINY, params, greedy, drafting=False)
    assert on == off
    assert eng.stats()["drafted_tokens"] > 20
    assert plain.stats()["drafted_tokens"] is None
    assert len(plain.cache.k) == 5 and len(eng.cache.k) == 6
    # A rejected draft's rows, in the model's pools and the window
    # tables, are written again: no later row differs from the run that
    # never drafted.
    for rid, by_pos in rows_off.items():
        for pos, row in by_pos.items():
            assert rel_err(rows_on[rid][pos], row) < ROUNDING, (rid, pos)


def test_a_drafting_engine_keeps_no_step_in_flight(params):
    """How far a sequence advances in a verify step is the device's
    answer, so nothing is dispatched ahead of its fetch; the same model
    served without its module runs ahead like any other."""
    requests = [(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts(11, 19), (9, 6))]
    _, _, _, eng = serve(TINY, params, requests, capture=False)
    log = eng.step_log()["steps"]
    assert any(s["decodes"] for s in log) and eng._flight is None
    assert all(s["ahead"] == 0 and s["ahead_rows_dropped"] == 0
               for s in log)
    stats = eng.stats()
    assert (stats["decodes_ahead"], stats["decodes_drained"],
            stats["ahead_rows_dropped"]) == (0, 0, 0)
    _, _, _, plain = serve(TINY, params, requests, capture=False,
                           drafting=False)
    assert plain.stats()["decodes_ahead"] > 0
    assert {s["ahead"] for s in plain.step_log()["steps"]} == {0, 1}


def test_a_request_draws_the_same_alone_and_in_a_full_batch(params):
    hot = [(p, SamplingParams(max_new_tokens=12, temperature=1.0,
                              seed=40 + i))
           for i, p in enumerate(prompts(9, 17, 26, 12))]
    together, _, _, eng = serve(TINY, params, hot, capture=False)
    log = eng.step_log()["steps"]
    assert sum(s["sampled_stochastic"] for s in log) > 0
    for i in (1, 3):
        alone, _, _, _ = serve(TINY, params, [hot[i]], capture=False)
        assert alone["r0"] == together[f"r{i}"]


@pytest.mark.parametrize("pages", [None, 16])
def test_a_verify_step_is_handed_what_the_loop_built(params, pages):
    """Every call of the verification program against the loop that built
    its inputs before the tables were kept (``kept_tables``): two
    positions' dests a kind, both kinds' tables, the module's slots; with
    sequences that end at different steps and, on 16 pages, one that is
    preempted and comes back in whatever row is free. Greedy tokens are
    those of the run that never drafts."""
    from kept_tables import watch_decode

    requests = [(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts(18, 17, 7), (20, 20, 11))]
    options = {**ENGINE, **({"num_pages": pages} if pages else {})}
    eng = InferenceEngine(TINY, params, **options)
    calls = watch_decode(eng)
    on, _, _, _ = serve(TINY, params, requests, engine=eng, capture=False)
    off, _, _, _ = serve(TINY, params, requests, capture=False,
                         drafting=False)
    assert on == off
    assert (eng.stats()["num_preemptions"] > 0) == bool(pages)
    # Slots, tokens, positions and a kind's dests each step, the tables
    # that moved, and the sampler's three rows with a new batch.
    assert {0, 2} <= {reused for reused, _ in calls} <= {0, 1, 2}
    for reused, puts in calls:
        assert puts - (5 + 2 - reused) in ((0, 3) if reused == 0 else (0,))
    stats = eng.stats()
    assert stats["table_puts"] + stats["table_reuses"] == 2 * len(calls)


def always_kept(eng):
    """Have ``eng``'s verification keep every draft."""
    accept = eng._accept_fn

    def kept(*a):
        ids, n = accept(*a)
        return ids.at[:, 1].set(jnp.maximum(ids[:, 1], 0)), \
            jnp.full_like(n, 2)

    eng._accept_fn = kept


def test_a_sequence_that_ends_on_its_first_kept_token_emits_one(params):
    eng = InferenceEngine(TINY, params, **ENGINE)
    always_kept(eng)
    out = eng.generate(prompts(10, 10), SamplingParams(max_new_tokens=4))
    # The prompt's token, two of a step, and one of the last step's two.
    assert [len(o) for o in out] == [4, 4]
    emitted = [s["emitted"] for s in eng.step_log()["steps"] if s["decodes"]]
    assert emitted == [4, 2]
    assert eng.stats()["draft_accepted"] == 4


def test_steps_of_one_and_of_two_tokens_advance_by_as_many(params):
    eng = probe.ProbedEngine(TINY, params, **ENGINE)
    always_kept(eng)
    tokens, rows, _, _ = serve(
        TINY, params, [(prompts(10)[0], SamplingParams(max_new_tokens=7))],
        engine=eng)
    assert len(tokens["r0"]) == 7 and sorted(rows["r0"]) == list(range(9, 16))


class TestBudget:
    def test_the_schedule_secures_the_drafts_slot(self):
        """A sequence whose next token still fits its last page and whose
        draft does not takes a page, or preempts for one."""
        cache = PagedKVCache(1, 5, 4, 1, 8)  # four usable pages
        sched = Scheduler(cache, max_num_seqs=2, max_model_len=64,
                          step_positions=2)
        a, b = (Sequence(request_id=r, prompt=list(range(1, 4)))
                for r in "ab")
        for seq in (a, b):
            sched.add(seq)
        assert len(sched.schedule().prefills) == 2
        for seq in (a, b):  # the prompt is in, its first token out
            seq.cached_len = 3
            seq.generated.append(1)
        plan = sched.schedule()  # position 3 and position 4, a page more
        assert cache.num_seq_pages("a") == 2 and plan.decodes == [a, b]
        a.cached_len = b.cached_len = 7  # 7 and 8: a third page each,
        plan = sched.schedule()          # and the pool has none
        assert plan.decodes == [a] and plan.preempted == [b]
        one = Scheduler(PagedKVCache(1, 4, 4, 1, 8), max_model_len=64)
        c = Sequence(request_id="c", prompt=list(range(1, 4)))
        one.add(c)
        one.schedule()
        c.cached_len, c.generated = 3, [1]
        one.schedule()
        assert one.cache.num_seq_pages("c") == 1  # one position: it fits

    def test_a_prompt_needs_room_for_its_draft(self, params):
        eng = InferenceEngine(TINY, params, page_size=4, num_pages=4,
                              max_num_seqs=1, max_model_len=64)
        eng.add_request("fits", list(range(1, 11)))      # 10 + 2: 3 pages
        with pytest.raises(ValueError, match="KV-page capacity"):
            eng.add_request("not", list(range(1, 12)))   # 11 + 2: 4
        plain = InferenceEngine(TINY, params, page_size=4, num_pages=4,
                                max_num_seqs=1, max_model_len=64,
                                drafting=False)
        plain.add_request("fits", list(range(1, 12)))    # 11 + 1: 3

    def test_the_last_positions_of_max_model_len_are_decoded(self, params):
        """A sequence runs to ``max_model_len`` tokens: the draft's slot of
        its last step is the length's last position."""
        eng = InferenceEngine(TINY, params, page_size=4, max_num_seqs=1,
                              max_model_len=24)
        out = eng.generate(prompts(9), SamplingParams(max_new_tokens=40))
        assert len(out[0]) == 24 - 9
        off = InferenceEngine(TINY, params, page_size=4, max_num_seqs=1,
                              max_model_len=24, drafting=False)
        assert off.generate(prompts(9),
                            SamplingParams(max_new_tokens=40)) == out

    def test_a_preempted_sequence_comes_back_with_a_draft(self, params):
        """Pages for one and a half of two sequences: the younger is
        preempted, prefilled again with what it had generated (nothing is
        sampled anew, the module runs over all of it) and ends as it does
        with room."""
        requests = [(p, SamplingParams(max_new_tokens=20))
                    for p in prompts(18, 17)]
        roomy, _, _, _ = serve(TINY, params, requests, capture=False)
        tight, _, _, eng = serve(TINY, params, requests, capture=False,
                                 num_pages=16)
        assert eng.stats()["num_preemptions"] > 0
        assert tight == roomy


# ---- speculative sampling --------------------------------------------------------


class TestSpeculative:
    V, N = 12, 20000

    def draws(self, temperature=1.0, top_k=0):
        rng = np.random.default_rng(0)
        p = (1.5 * rng.normal(size=(2, self.V))).astype(np.float32)
        q = (1.5 * rng.normal(size=self.V)).astype(np.float32)
        n = self.N
        rows = (jnp.full(n, temperature), jnp.full(n, top_k, jnp.int32),
                jnp.arange(n, dtype=jnp.uint32))
        at = jnp.full(n, 7, jnp.int32)
        q_all = jnp.broadcast_to(q, (n, self.V))
        draft = jax.jit(sampling.draft_token)(q_all, *rows, at)
        ids, kept = jax.jit(sampling.speculative)(
            jnp.broadcast_to(p, (n, 2, self.V)), q_all, draft, *rows, at)
        return p, q, np.asarray(draft), np.asarray(ids), np.asarray(kept)

    @staticmethod
    def softmax(x):
        e = np.exp(x - x.max())
        return e / e.sum()

    def chi2(self, ids, probs):
        seen = probs > 0
        want = len(ids) * probs[seen]
        got = np.bincount(ids, minlength=self.V)
        assert got[~seen].sum() == 0
        return float(((got[seen] - want) ** 2 / want).sum())

    def test_the_first_token_is_the_verifiers_whatever_the_draft(self):
        """20,000 keyed draws over 12 tokens against a fixed ``p`` and an
        unrelated ``q``: chi-squared with 11 degrees of freedom lies under
        31.3 with probability 0.999, and a sampler that emitted the draft
        (``q``) or the residual alone reads in the thousands."""
        p, q, draft, ids, kept = self.draws()
        assert self.chi2(ids[:, 0], self.softmax(p[0])) < 31.3
        assert self.chi2(draft, self.softmax(q)) < 31.3
        assert self.chi2(ids[:, 0], self.softmax(q)) > 1000
        # Kept with probability sum(min(p, q)); three standard errors.
        rate = np.minimum(self.softmax(p[0]), self.softmax(q)).sum()
        assert abs((kept == 2).mean() - rate) < 3 * 0.5 / self.N ** 0.5
        # A kept draft is followed by a draw from the second row.
        two = kept == 2
        assert (ids[two, 0] == draft[two]).all()
        assert (ids[~two, 1] == -1).all()
        assert self.chi2(ids[two, 1], self.softmax(p[1])) < 31.3

    def test_under_top_k_the_masked_tokens_never_come(self):
        p, q, draft, ids, kept = self.draws(temperature=0.7, top_k=4)
        shaped = np.where(p[0] >= np.sort(p[0])[-4], p[0] / 0.7, -np.inf)
        assert self.chi2(ids[:, 0], self.softmax(shaped)) < 16.3  # 3 dof

    def test_a_greedy_row_keeps_the_draft_iff_it_is_the_argmax(self):
        logits = jnp.asarray([[[0., 3, 1], [2, 0, 1]]] * 2)
        q = jnp.asarray([[0., 5, 1], [9., 0, 1]])
        zeros = (jnp.zeros(2), jnp.zeros(2, jnp.int32),
                 jnp.zeros(2, jnp.uint32))
        at = jnp.zeros(2, jnp.int32)
        draft = sampling.draft_token(q, *zeros, at)
        assert draft.tolist() == [1, 0]
        ids, kept = sampling.speculative(logits, q, draft, *zeros, at)
        assert ids.tolist() == [[1, 0], [1, -1]] and kept.tolist() == [2, 1]


# ---- what stays refused ----------------------------------------------------------


def test_refusals(params):
    with pytest.raises(ValueError, match="one device"):
        InferenceEngine(TINY, params, tp=2, **ENGINE)
    with pytest.raises(ValueError, match="without the prefix cache"):
        InferenceEngine(TINY, params, enable_prefix_cache=True, **ENGINE)
    plain = dataclasses.replace(TINY, mtp_layers=0)
    with pytest.raises(ValueError, match="no prediction module"):
        InferenceEngine(plain, init_params(ExaoneMoe(plain), plain, batch=1),
                        drafting=True, **ENGINE)
    from raytpu.inference.serving import LLMDeployment

    with pytest.raises(ValueError, match="window layers"):
        LLMDeployment._target(model="exaone_moe", role="prefill")
    with pytest.raises(ValueError, match="'exaone_moe'"):
        LLMDeployment._target(model="exaone")


def test_the_deployment_serves_the_family():
    from raytpu.inference.serving import LLMDeployment

    dep = LLMDeployment._target(model="exaone_moe", engine_options=dict(
        page_size=4, max_num_seqs=2, max_model_len=64))
    try:
        out = list(dep.generate([5, 6, 7, 8, 9], max_new_tokens=6,
                                temperature=1.0, seed=3))
        assert len(out) == 6 and all(0 <= t < 512 for t in out)
        stats = dep.stats()
        assert stats["drafted_tokens"] >= 3
        assert stats["kv_pool_bytes_by_kind"]["window"] > 0
    finally:
        dep.shutdown()


# ---- the chip script, rehearsed -------------------------------------------------


def test_chip_kexaone_rehearsal(capsys):
    """``chip_kexaone.py`` at the benchmark's tiny configuration: the
    program's kept rows and the module's logits inside 1e-4 of the
    reference through steps of one and of two tokens (one engine reused
    from seed to seed), and the four controls that bite in float32 far
    outside it (the fifth rounds bf16 matrices, of which a float32 tree
    has none)."""
    import json

    import chip_kexaone

    tiny = os.path.join(ROOT, "perfbench", "tests", "kexaone")
    chip_kexaone.main([
        "check", "--cpu", "--seeds", "5", "6", "--controls", "1", "--only",
        *chip_kexaone.PROGRAM_CONTROLS, "--config",
        os.path.join(tiny, "configs", "tiny-kexaone.json"), "--mix",
        os.path.join(tiny, "traffic", "tiny-selfdraft-decode.json")])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] is True, out
    assert max(out["rel_errs"] + out["draft_rel_errs"]) < ROUNDING
    kept, drafted = out["accepted_of_drafted"]
    assert 0 < kept < drafted
    last = out["results"][-1]
    assert set(last["caught_by"].values()) == {"max"}
    assert all(last[c]["min"] > 1e-2 for c in chip_kexaone.PROGRAM_CONTROLS)
