"""Pallas kernel tests: numerics in interpret mode on the CPU, and the
lowering for the TPU platform from the CPU host (what Mosaic then makes
of it only the chip, or an AOT compile against libtpu, can say)."""

import functools
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from raytpu.ops.flash_attention import (flash_attention,
                                        flash_attention_part, merge_parts)
from raytpu.ops.grouped_matmul import grouped_matmul, grouped_swiglu
from raytpu.ops.paged_attention import paged_attention
from raytpu.parallel.mesh import build_mesh

# (``raytpu.ops.flash_attention`` is the function, as the package exports it.)
flash_mod = importlib.import_module("raytpu.ops.flash_attention")


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_interpret_matches_reference(self, causal):
        b, h, t, d = 2, 3, 256, 64
        key = jax.random.PRNGKey(0)
        q, k, v = jax.random.normal(key, (3, b, h, t, d), jnp.float32)
        ref = flash_attention(q, k, v, causal=causal, force="reference")
        got = flash_attention(q, k, v, causal=causal, force="interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_cross_length_causal(self):
        """Decode-style t_q < t_kv: the diagonal is bottom-aligned
        (reference tril k=t_kv-t_q); forward AND backward kernels must
        agree with the einsum path."""
        b, h, d = 1, 2, 64
        t_q, t_kv = 128, 256
        key = jax.random.PRNGKey(7)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, h, t_q, d), jnp.float32)
        k = jax.random.normal(kk, (b, h, t_kv, d), jnp.float32)
        v = jax.random.normal(kv_, (b, h, t_kv, d), jnp.float32)
        ref = flash_attention(q, k, v, force="reference")
        got = flash_attention(q, k, v, force="interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

        def loss(mode, q, k, v):
            return jnp.sum(flash_attention(q, k, v, force=mode) ** 2)

        gr = jax.grad(loss, argnums=(1, 2, 3))("reference", q, k, v)
        gp = jax.grad(loss, argnums=(1, 2, 3))("interpret", q, k, v)
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-4, rtol=5e-4)

    def test_gradients_match(self):
        b, h, t, d = 1, 2, 128, 32
        key = jax.random.PRNGKey(1)
        q, k, v = jax.random.normal(key, (3, b, h, t, d), jnp.float32)

        def loss(mode, q, k, v):
            return flash_attention(q, k, v, force=mode).sum()

        g_ref = jax.grad(lambda *a: loss("reference", *a),
                         argnums=(0, 1, 2))(q, k, v)
        g_int = jax.grad(lambda *a: loss("interpret", *a),
                         argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_int, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4)

    def test_bf16(self):
        b, h, t, d = 1, 2, 128, 64
        key = jax.random.PRNGKey(2)
        q, k, v = jax.random.normal(key, (3, b, h, t, d), jnp.bfloat16)
        ref = flash_attention(q, k, v, force="reference")
        got = flash_attention(q, k, v, force="interpret")
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

    @pytest.mark.parametrize("force", ["reference", "interpret"])
    @pytest.mark.parametrize("kv_len", [0, 1, 511, 512, 513, 2048, 2049,
                                        3000, 4096])
    def test_part_with_a_key_limit(self, force, kv_len):
        """Keys from ``kv_len`` on enter no softmax, whatever they hold:
        the part is the attention over ``k[:kv_len]`` and its
        log-sum-exp, with the limit traced, over sub-blocks of 512 keys
        in two major blocks (one past the limit is not visited)."""
        h, t_q, t_kv, d = 2, 128, 4096, 32
        q = jax.random.normal(jax.random.PRNGKey(3), (1, h, t_q, d))
        k, v = jax.random.normal(jax.random.PRNGKey(4), (2, 1, h, t_kv, d))
        dead = jnp.arange(t_kv)[:, None] >= kv_len
        o, lse = flash_attention_part(
            q, jnp.where(dead, 1e4, k), jnp.where(dead, 1e4, v),
            causal=False, sm_scale=d ** -0.5, force=force,
            kv_len=jnp.int32(kv_len))
        if kv_len == 0:
            assert np.asarray(lse).max() < -1e29
            return
        want_o, want_lse = flash_mod._attn_fwd_reference(
            q, k[:, :, :kv_len], v[:, :, :kv_len], False, d ** -0.5)
        np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(lse, want_lse[..., 0], atol=2e-5,
                                   rtol=2e-5)

    def test_parts_merge_to_the_whole(self):
        """Attention over a context in three parts (the last causal, the
        one before it cut short by a key limit) is attention over it
        whole: what a latent chunk does with its cached segments."""
        h, t, d = 2, 64, 16
        q = jax.random.normal(jax.random.PRNGKey(5), (1, h, t, d))
        k, v = jax.random.normal(jax.random.PRNGKey(6), (2, 1, h, 3 * t, d))
        scale, cut = d ** -0.5, 40
        live = np.r_[0:t, t:t + cut, 2 * t:3 * t]
        want = flash_attention(q, k[:, :, live], v[:, :, live],
                               sm_scale=scale, force="reference")
        for force in ("reference", "interpret"):
            part = functools.partial(flash_attention_part, q, sm_scale=scale,
                                     force=force)
            rows = lambda i: (k[:, :, i * t:(i + 1) * t],  # noqa: E731
                              v[:, :, i * t:(i + 1) * t])
            got, _ = merge_parts([
                part(*rows(2), causal=True), part(*rows(0), causal=False),
                part(*rows(1), causal=False, kv_len=cut)])
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_a_key_limit_is_a_part_s_before_the_diagonal(self):
        q = jnp.ones((1, 1, 16, 8))
        with pytest.raises(ValueError, match="causal=False"):
            flash_attention_part(q, q, q, causal=True, sm_scale=1.0,
                                 kv_len=3)

    def test_block_autofit(self):
        # 300 and 768 don't divide the 512-tile default; interpret mode
        # picks the largest fitting divisor instead of erroring.
        q = jnp.ones((1, 1, 300, 64))
        out = flash_attention(q, q, q, force="interpret")
        assert out.shape == q.shape
        q = jnp.ones((1, 1, 768, 64))
        out = flash_attention(q, q, q, force="interpret")
        assert out.shape == q.shape

    def test_block_autofit_hardware_alignment(self):
        from raytpu.ops.flash_attention import _fit_block
        # Hardware path: the block must be a sublane-aligned (%8)
        # divisor >= 64; loose fits are interpret-only.
        assert _fit_block(768, 512, False) == 384
        assert _fit_block(1024, 512, False) == 512
        assert _fit_block(300, 512, True) == 300
        # explicit small override lowers the floor but stays aligned
        assert _fit_block(1024, 32, False) == 32
        # aligned full-sequence block below the floor is fine
        assert _fit_block(32, 512, False) == 32
        for bad_t in (300, 521, 1022, 50):  # no aligned divisor
            with pytest.raises(ValueError):
                _fit_block(bad_t, 512, False)

    def test_bf16_gradients(self):
        # bf16 residuals exercise the "input" dot mode in the backward
        # kernels (p/ds fed to the MXU in bf16); fp32-input tests make
        # those casts no-ops, so without this the production training
        # precision path would be untested.
        b, h, t, d = 1, 2, 128, 64
        key = jax.random.PRNGKey(4)
        q, k, v = jax.random.normal(key, (3, b, h, t, d), jnp.bfloat16)

        def loss(force, q, k, v):
            return flash_attention(q, k, v, force=force).astype(
                jnp.float32).sum()

        g_ref = jax.grad(lambda *a: loss("reference", *a),
                         argnums=(0, 1, 2))(q, k, v)
        g_int = jax.grad(lambda *a: loss("interpret", *a),
                         argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_int, g_ref):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b_, np.float32),
                atol=5e-2, rtol=5e-2)

    def test_bad_block_divisibility(self):
        # A shape the pallas path cannot tile raises even in interpret
        # mode once t exceeds every divisor (prime > default block).
        q = jnp.ones((1, 1, 521, 64))
        with pytest.raises(ValueError):
            flash_attention(q, q, q, force="interpret")


# The kernels' joints: (head_dim, t_q, t_kv, window, dtype, rows of a tile
# of q and of k or None for the table's, rows a grid step holds at most).
_JOINTS = {
    "one_sub_block": (64, 128, 128, None, jnp.float32, None, 2048),
    "table_tiles": (64, 512, 512, None, jnp.float32, None, 2048),
    "several_sub_blocks_d64": (64, 512, 512, None, jnp.float32, 128, 2048),
    "several_sub_blocks_d128": (128, 512, 512, None, jnp.float32, 128, 2048),
    "several_major_blocks": (64, 512, 512, None, jnp.float32, 128, 256),
    "bf16_d64": (64, 512, 512, None, jnp.bfloat16, 128, 2048),
    "bf16_d128": (128, 256, 256, None, jnp.bfloat16, 128, 2048),
    "t384_no_multiple_of_the_table": (64, 384, 384, None, jnp.float32, None,
                                      2048),
    "t768_no_multiple_of_the_table": (64, 768, 768, None, jnp.float32, None,
                                      2048),
    "blocks_of_no_whole_tile": (64, 192, 192, None, jnp.float32, 96, 2048),
    "fewer_queries_than_keys": (64, 128, 512, None, jnp.float32, 128, 2048),
    "fewer_queries_several_blocks": (64, 256, 384, None, jnp.bfloat16, 128,
                                     2048),
    "window_ends_inside_a_sub_block": (64, 512, 512, 200, jnp.float32, 128,
                                       2048),
    "window_shorter_than_a_sub_block": (128, 256, 384, 72, jnp.float32, 128,
                                        256),
    "window_bf16": (64, 512, 512, 300, jnp.bfloat16, 128, 2048),
}


@pytest.fixture
def joint(request, monkeypatch):
    """q, k, v of a case of _JOINTS, with its tiles set in the module."""
    import importlib

    fa = importlib.import_module("raytpu.ops.flash_attention")
    d, t_q, t_kv, window, dtype, tile, major = _JOINTS[request.param]
    monkeypatch.setattr(fa, "DEFAULT_BLOCK_Q", tile)
    monkeypatch.setattr(fa, "DEFAULT_BLOCK_K", tile)
    monkeypatch.setattr(fa, "_MAJOR_ROWS", major)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(t_q + t_kv + d), 3)
    q = jax.random.normal(kq, (1, 2, t_q, d), dtype)
    k = jax.random.normal(kk, (1, 2, t_kv, d), dtype)
    v = jax.random.normal(kv, (1, 2, t_kv, d), dtype)
    return fa, (q, k, v), window


def _pallas_calls(jaxpr):
    """The pallas_call equations of a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


class TestFlashJoints:
    """What the three kernels walk, mask and skip, in the interpreter
    against the reference: forward and the three gradients."""

    @pytest.mark.parametrize("joint", sorted(_JOINTS), indirect=True)
    def test_forward_and_gradients(self, joint):
        _, qkv, window = joint
        bf16 = qkv[0].dtype == jnp.bfloat16

        def loss(force, q, k, v):
            o = flash_attention(q, k, v, window=window, force=force)
            return (o.astype(jnp.float32) ** 2).sum(), o

        (_, ref), g_ref = jax.value_and_grad(
            functools.partial(loss, "reference"), argnums=(0, 1, 2),
            has_aux=True)(*qkv)
        (_, got), g_got = jax.value_and_grad(
            functools.partial(loss, "interpret"), argnums=(0, 1, 2),
            has_aux=True)(*qkv)
        tol = 3e-2 if bf16 else 2e-5
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)
        tol = 5e-2 if bf16 else 5e-4
        for a, b_ in zip(g_got, g_ref):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b_, np.float32),
                                       atol=tol, rtol=tol)

    @pytest.mark.parametrize("joint", sorted(_JOINTS), indirect=True)
    def test_saved_lse_is_the_references(self, joint):
        fa, qkv, window = joint
        scale = qkv[0].shape[-1] ** -0.5
        _, (*_, ref) = fa._flash_fwd(*qkv, True, scale, "reference", window)
        _, (*_, got) = fa._flash_fwd(*qkv, True, scale, "interpret", window)
        assert got.shape == ref.shape == qkv[0].shape[:3]
        assert got.dtype == jnp.float32
        tol = 2e-2 if qkv[0].dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("t,products", [(256, 2), (1024, 4)])
    def test_a_call_of_one_block_is_decided_when_it_is_traced(self, t,
                                                              products):
        """A serving program holds the forward once a layer and traces and
        lowers it as often (48 times in GPT-2 XL), so a prefill of one
        block is one tile and nothing beside it: no loop, no branch, no
        grid index. T = 1,024 under the table is its two chunks."""
        q = jnp.zeros((1, 2, t, 64), jnp.bfloat16)
        call, = _pallas_calls(jax.make_jaxpr(functools.partial(
            flash_attention, force="interpret"))(q, q, q).jaxpr)
        names = [e.primitive.name for e in call.params["jaxpr"].eqns]
        assert not {"while", "scan", "cond", "program_id"} & set(names)
        assert names.count("dot_general") == products

    def test_results_the_benchmark_tells_the_calls_apart_by(self):
        """perfbench's flash_attn_roofline.classify keys on the results of
        the three custom calls: the forward's (bf16 [bh, T, d], f32 of
        three dimensions), dq's one bf16 [bh, T, d], dk/dv's two."""
        bh, t, d = 4, 256, 64
        q = jnp.zeros((1, bh, t, d), jnp.bfloat16)

        def loss(q, k, v):
            return flash_attention(q, k, v, force="interpret").astype(
                jnp.float32).sum()

        calls = sorted(([(v.aval.dtype.name, v.aval.shape) for v in e.outvars]
                        for e in _pallas_calls(jax.make_jaxpr(jax.grad(
                            loss, argnums=(0, 1, 2)))(q, q, q).jaxpr)),
                       key=len)
        out = ("bfloat16", (bh, t, d))
        assert calls[0] == [out]  # dq
        dkv, fwd = sorted(calls[1:], key=lambda c: c[1][0])
        assert dkv == [out, out]
        assert fwd[0] == out
        assert fwd[1][0] == "float32" and len(fwd[1][1]) == 3
        assert len(calls) == 3


def _flash_shapes(b, h, t, d):
    return (jax.ShapeDtypeStruct((b, h, t, d), jnp.bfloat16),) * 3


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, force="tpu")


def _flash_loss(q, k, v):
    return _flash_fwd(q, k, v).astype(jnp.float32).sum()


def _flash_part(q, k, v):
    """A cached segment under a latent chunk: not causal, a traced key
    limit (here from the data, as a chunk's start is from its inputs)."""
    return flash_attention_part(
        q, k, v, causal=False, sm_scale=q.shape[-1] ** -0.5, force="tpu",
        kv_len=jnp.argmax(k[0, 0, :, 0]).astype(jnp.int32))


def _paged_shapes(b, t, h, kv, d, pages=513, page_size=16, width=64):
    pool = jax.ShapeDtypeStruct((pages, page_size, kv * d), jnp.bfloat16)
    return (jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16), pool, pool,
            jax.ShapeDtypeStruct((b, width), jnp.int32),
            jax.ShapeDtypeStruct((b, t), jnp.int32))


_paged = functools.partial(paged_attention, force="tpu")

# GPT-2 124M (12 heads of 64) at the shapes training and the engine use,
# and one GQA 32/8 shape at head_dim 128.
_TPU_CASES = {
    "flash_fwd_124m": (_flash_fwd, _flash_shapes(8, 12, 1024, 64)),
    "flash_bwd_124m": (jax.grad(_flash_loss, argnums=(0, 1, 2)),
                       _flash_shapes(8, 12, 1024, 64)),
    "flash_fwd_prefill_bucket": (_flash_fwd, _flash_shapes(1, 12, 16, 64)),
    # JoyAI's chunk: 32 heads of 128 + 64 against a segment of 2,048 rows.
    "flash_part_latent_segment": (_flash_part,
                                  _flash_shapes(1, 32, 2048, 192)),
    "flash_bwd_gqa_d128": (jax.grad(_flash_loss, argnums=(0, 1, 2)),
                           _flash_shapes(4, 32, 1024, 128)),
    "paged_decode_124m": (_paged, _paged_shapes(8, 1, 12, 12, 64)),
    "paged_chunk_124m": (_paged, _paged_shapes(1, 64, 12, 12, 64)),
    "paged_decode_gqa_d128": (_paged, _paged_shapes(8, 1, 32, 8, 128)),
    # GPT-2 XL: rows of 1600 features, no whole number of 128-lane tiles
    # (the kernel's copies take the last tile's padding along).
    "paged_decode_xl": (_paged, _paged_shapes(8, 1, 25, 25, 64, pages=385)),
}


def _routed_layer(rows, wg, wi, wo, tokens):
    return grouped_matmul(grouped_swiglu(rows, wg, wi, tokens), wo, tokens)


def _routed_shapes(rows, experts, k, n):
    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    return (bf16(rows, k), bf16(experts, k, n), bf16(experts, k, n),
            bf16(experts, n, k), jax.ShapeDtypeStruct((experts,), jnp.int32))


# The routed layer's products at the served families' decode shapes
# (rows = sequences x experts a token) and at chunks of 2,048; K-EXAONE's
# experts go through in blocks of columns.
_ROUTED_CASES = {
    "mellum_decode": _routed_shapes(256, 64, 2304, 896),
    "olmoe_decode": _routed_shapes(128, 64, 2048, 1024),
    "joyai_decode": _routed_shapes(256, 32, 2048, 768),
    "mellum_chunk": _routed_shapes(16384, 64, 2304, 896),
    "lfm2_decode": _routed_shapes(256, 64, 2048, 1536),
    "lfm2_chunk": _routed_shapes(8192, 64, 2048, 1536),
    "kexaone_verify": _routed_shapes(256, 16, 6144, 2048),
    "kexaone_chunk": _routed_shapes(16384, 16, 6144, 2048),
}


class TestRematKeepsTheKernelsResiduals:
    """``remat=True`` saves what ``_flash_bwd`` takes of the forward
    (``flash_attention.RESIDUAL_NAMES``) and recomputes the rest of the
    block, so the gradient of a layer holds the forward kernel once
    (forward, dq, dk/dv: 3 calls) where saving nothing holds it twice
    (4), and the mathematics is the same to the last bit."""

    LAYERS = 3

    @staticmethod
    def saves_nothing(block, remat):
        """The tree's ``remat_block`` before PR 54."""
        import flax.linen as nn

        if not remat or remat == "none":
            return block
        policy = None
        if remat == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return nn.remat(block, prevent_cse=False, policy=policy)

    def value_and_grad(self, remat, scan):
        import dataclasses

        from raytpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn

        cfg = dataclasses.replace(
            GPT2Config.tiny(), n_layer=self.LAYERS, attn_impl="interpret",
            remat=remat, scan_layers=scan)
        model = GPT2(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0,
                                    cfg.vocab_size, jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        fn = jax.value_and_grad(lambda p: gpt2_loss_fn(model, p, tokens))
        return fn, params

    @pytest.mark.parametrize("scan", [True, False],
                             ids=["scanned", "unrolled"])
    def test_one_forward_kernel_a_layer_and_the_same_bits(self, scan,
                                                          monkeypatch):
        from raytpu.models import gpt2

        # A scanned body is in the jaxpr once, an unrolled layer each time.
        layers = 1 if scan else self.LAYERS
        got = {}
        for name, remat in (("kept", True), ("none", "none"),
                            ("nothing", True)):
            if name == "nothing":
                monkeypatch.setattr(gpt2, "remat_block", self.saves_nothing)
            fn, params = self.value_and_grad(remat, scan)
            calls = len(list(_pallas_calls(jax.make_jaxpr(fn)(params).jaxpr)))
            got[name] = (calls, jax.jit(fn)(params))
        assert got["kept"][0] == 3 * layers
        assert got["none"][0] == 3 * layers
        assert got["nothing"][0] == 4 * layers
        for other in ("none", "nothing"):
            for a, b_ in zip(jax.tree.leaves(got["kept"][1]),
                             jax.tree.leaves(got[other][1])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    @pytest.mark.parametrize("remat", ["dots", "none", False])
    def test_the_other_policies_build_what_they_built(self, remat,
                                                      monkeypatch):
        from raytpu.models import gpt2

        fn, params = self.value_and_grad(remat, True)
        now = str(jax.make_jaxpr(fn)(params))
        monkeypatch.setattr(gpt2, "remat_block", self.saves_nothing)
        fn, params = self.value_and_grad(remat, True)
        assert str(jax.make_jaxpr(fn)(params)) == now

    def test_true_and_full_are_one_policy_in_the_three_families(self):
        import flax.linen as nn

        from raytpu.models import gpt2, llama, mixtral
        from raytpu.ops.flash_attention import RESIDUAL_NAMES

        assert llama.remat_block is mixtral.remat_block is gpt2.remat_block
        assert gpt2.remat_block(gpt2.Block, False) is gpt2.Block
        assert gpt2.remat_block(gpt2.Block, "none") is gpt2.Block
        for remat in (True, "full", "dots"):
            assert issubclass(gpt2.remat_block(gpt2.Block, remat), nn.Module)
        assert RESIDUAL_NAMES == ("flash_q", "flash_k", "flash_v", "flash_o",
                                  "flash_lse")

    def test_under_a_mesh_the_names_are_on_the_global_arrays(self):
        """``xl-train-fsdp4``'s form: the kernels run per shard, the
        residuals are named after ``per_shard`` returned."""
        mesh = build_mesh({"fsdp": 4}, jax.devices()[:4])
        with jax.set_mesh(mesh):
            fn, params = self.value_and_grad("none", True)
            ref = jax.jit(fn)(params)
            fn, params = self.value_and_grad(True, True)
            calls = len(list(_pallas_calls(jax.make_jaxpr(fn)(params).jaxpr)))
            got = jax.jit(fn)(params)
        assert calls == 3
        for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


class TestTpuLowering:
    """Every Pallas kernel must lower for the TPU from a CPU host, alone
    and from a program sharded over four devices."""

    @pytest.mark.parametrize("case", sorted(_ROUTED_CASES))
    def test_routed_layer_is_two_kernels_for_the_tpu(self, case):
        traced = jax.jit(_routed_layer).trace(*_ROUTED_CASES[case])
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert len(re.findall(r"stablehlo.custom_call @tpu_custom_call",
                              text)) == 2
        assert "ragged_dot" not in text
        assert "tpu_custom_call" not in traced.lower(
            lowering_platforms=("cpu",)).as_text()

    @pytest.mark.parametrize("case", sorted(_TPU_CASES))
    def test_single_device(self, case):
        fn, shapes = _TPU_CASES[case]
        text = jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text

    @pytest.mark.parametrize("axes", [{"dp": 4}, {"tp": 4},
                                      {"fsdp": 2, "tp": 2}])
    @pytest.mark.parametrize("case", sorted(_TPU_CASES))
    def test_four_device_mesh(self, case, axes):
        fn, shapes = _TPU_CASES[case]
        mesh = build_mesh(axes, jax.devices()[:4])
        batch = tuple(a for a in ("dp", "fsdp") if a in axes) or None
        if shapes[0].shape[0] == 1:  # a batch of one cannot be split
            batch = None
        heads = "tp" if "tp" in axes else None
        if case == "paged_decode_xl":  # 25 heads: whole on every chip
            heads = None
        if case.startswith("flash"):
            specs = (P(batch, heads),) * 3
        else:
            specs = (P(batch, None, heads), P(None, None, heads),
                     P(None, None, heads), P(batch), P(batch))
        shardings = tuple(NamedSharding(mesh, s) for s in specs)
        with jax.set_mesh(mesh):
            text = jax.jit(fn, in_shardings=shardings).trace(
                *shapes).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text

    def test_sharded_program_without_a_mesh_is_refused(self):
        # No jax.set_mesh: XLA would have to partition the kernel.
        fn, shapes = _TPU_CASES["flash_fwd_124m"]
        mesh = build_mesh({"dp": 4}, jax.devices()[:4])
        sh = NamedSharding(mesh, P("dp"))
        with pytest.raises(NotImplementedError, match="shard_map"):
            jax.jit(fn, in_shardings=(sh,) * 3).trace(*shapes).lower(
                lowering_platforms=("tpu",))


@pytest.fixture(scope="module")
def v5e_devices():
    """Devices of libtpu's compile-only client: no chip needed."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # no libtpu here
        pytest.skip(f"no TPU compile-only client: {e}")


@pytest.mark.slow
class TestTpuAotCompile:
    """Past the lowering: Mosaic itself compiles every kernel for the
    v5e. Whether the result is right, only a chip run says."""

    @pytest.mark.parametrize("case", sorted(_TPU_CASES))
    def test_mosaic_accepts(self, case, v5e_devices):
        fn, shapes = _TPU_CASES[case]
        one = NamedSharding(
            jax.sharding.Mesh(np.array(v5e_devices[:1]), ("x",)), P())
        jax.jit(fn, in_shardings=one, out_shardings=one).trace(
            *shapes).lower(lowering_platforms=("tpu",)).compile()

    @pytest.mark.parametrize("case", sorted(_ROUTED_CASES))
    def test_mosaic_accepts_the_routed_layer(self, case, v5e_devices):
        one = NamedSharding(
            jax.sharding.Mesh(np.array(v5e_devices[:1]), ("x",)), P())
        jax.jit(_routed_layer, in_shardings=one, out_shardings=one).trace(
            *_ROUTED_CASES[case]).lower(lowering_platforms=("tpu",)).compile()


class TestPerShard:
    """Numerics of the kernels called from a sharded program: the
    interpreted kernel per shard against the unsharded reference."""

    def test_flash_fwd_bwd_over_batch_and_heads(self):
        mesh = build_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])
        q, k, v = jax.random.normal(jax.random.PRNGKey(5),
                                    (3, 4, 4, 128, 32), jnp.float32)
        sh = NamedSharding(mesh, P("dp", "tp"))

        def loss(force, q, k, v):
            return (flash_attention(q, k, v, force=force) ** 2).sum()

        ref = jax.value_and_grad(functools.partial(loss, "reference"),
                                 argnums=(0, 1, 2))(q, k, v)
        with jax.set_mesh(mesh):
            got = jax.jit(jax.value_and_grad(
                functools.partial(loss, "interpret"), argnums=(0, 1, 2)))(
                    *(jax.device_put(x, sh) for x in (q, k, v)))
        assert got[1][0].sharding.is_equivalent_to(sh, 4)
        for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-4, rtol=5e-4)

    def test_paged_over_kv_heads(self):
        mesh = build_mesh({"tp": 4}, jax.devices()[:4])
        rng = np.random.default_rng(3)
        b, t, h, kv, d, page, width = 2, 1, 8, 4, 32, 8, 4
        q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal(
            (b * width + 1, page, kv * d)), jnp.float32) for _ in range(2))
        tables = jnp.asarray(
            np.arange(1, b * width + 1).reshape(b, width), jnp.int32)
        pos = jnp.asarray([[13], [30]], jnp.int32)
        ref = paged_attention(q, k, v, tables, pos, force="reference")
        pool_sh = NamedSharding(mesh, P(None, None, "tp"))
        with jax.set_mesh(mesh):
            got = jax.jit(functools.partial(
                paged_attention, force="interpret"))(
                    jax.device_put(q, NamedSharding(mesh, P(None, None,
                                                            "tp"))),
                    jax.device_put(k, pool_sh), jax.device_put(v, pool_sh),
                    tables, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_paged_pools_are_split_by_whole_heads_only(self):
        # 2 kv heads of 32 under tp=4: the pool's 64 features and the 4
        # query heads both divide by 4, the kv heads do not, and a quarter
        # of a row is half a head. The call then runs unsplit.
        mesh = build_mesh({"tp": 4}, jax.devices()[:4])
        rng = np.random.default_rng(4)
        b, t, h, kv, d, page, width = 2, 1, 4, 2, 32, 8, 2
        q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal(
            (b * width + 1, page, kv * d)), jnp.float32) for _ in range(2))
        tables = jnp.asarray(
            np.arange(1, b * width + 1).reshape(b, width), jnp.int32)
        pos = jnp.asarray([[5], [14]], jnp.int32)
        ref = paged_attention(q, k, v, tables, pos, force="reference")
        with jax.set_mesh(mesh):
            fn = jax.jit(functools.partial(paged_attention,
                                           force="interpret"))
            specs = re.search(r"in_specs=\(.*?\)\)", str(
                fn.trace(q, k, v, tables, pos).jaxpr)).group(0)
            assert "PartitionSpec" in specs and "tp" not in specs
            got = fn(q, k, v, tables, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
