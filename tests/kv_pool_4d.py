"""The KV pools as they were held before PR 27, as a reference for the
tests of the flat pools: one ``[num_pages, page_size, kv_heads,
head_dim]`` array a layer, written through the ``[pages * page_size,
...]`` view and read by the gather (or by the kernel on a reshaped
copy). The family's own entry points (``config.serving``) run on them,
with the two pool functions they look up in
``raytpu.ops.paged_attention`` replaced; nothing here is donated.
Logits and pool contents of the engine's three programs must equal
these bit for bit.
"""

import contextlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np

# (raytpu.ops exports a function of the module's name.)
pa = importlib.import_module("raytpu.ops.paged_attention")
_real_attention = pa.paged_attention


def scatter_4d(pages, dests, rows):
    n, ps, kv, d = pages.shape
    flat = pages.reshape(n * ps, kv, d).at[dests].set(
        rows.reshape(-1, kv, d).astype(pages.dtype))
    return flat.reshape(pages.shape)


def attention_4d(q, k_pages, v_pages, block_tables, positions, *,
                 sm_scale=None, force=None, window=None):
    assert window is None  # the parent's pools knew no window layer
    b, t, h, d = q.shape
    n, ps, kv, _ = k_pages.shape
    if sm_scale is None:
        sm_scale = d ** -0.5
    positions = positions.astype(jnp.int32)
    if pa.resolve_paged_impl(force) != "reference":
        # What the parent's kernel wrapper made of a pool.
        return _real_attention(
            q, k_pages.reshape(n, ps, kv * d), v_pages.reshape(n, ps, kv * d),
            block_tables, positions, sm_scale=sm_scale, force=force)
    ks = k_pages[block_tables].reshape(b, -1, kv, d)
    vs = v_pages[block_tables].reshape(b, -1, kv, d)
    if kv != h:
        ks = jnp.repeat(ks, h // kv, axis=2)
        vs = jnp.repeat(vs, h // kv, axis=2)
    s = jnp.einsum("bthd,blhd->bhtl", q.astype(jnp.float32),
                   ks.astype(jnp.float32)) * sm_scale
    visible = (jnp.arange(ks.shape[1], dtype=jnp.int32)[None, None, :]
               <= positions[:, :, None])
    s = jnp.where(visible[:, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhtl,blhd->bthd", p, vs.astype(jnp.float32))
    return o.astype(q.dtype)


@contextlib.contextmanager
def pools_4d():
    """The walks traced inside write and read 4-D pools."""
    real = pa.scatter_kv_slots, pa.paged_attention
    pa.scatter_kv_slots, pa.paged_attention = scatter_4d, attention_4d
    try:
        yield
    finally:
        pa.scatter_kv_slots, pa.paged_attention = real


def same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.tobytes() == b.tobytes()


def check_engine_programs(eng, prompt_a, prompt_b, chunk: int = 8):
    """Drive ``eng``'s three programs by hand (a whole prefill of
    ``prompt_a``, ``prompt_b`` in chunks of ``chunk``, then two decode
    steps of both) beside the family's walks on 4-D pools, and assert
    after every program that logits and all 2 x layers pools are the
    same bits. Returns the names of the programs compared."""
    cfg, params, cache = eng._config, eng._params, eng.cache
    kv, d, ps = cache.num_kv_heads, cache.head_dim, cache.page_size
    shape4 = (cache.num_pages, ps, kv, d)
    k4 = [jnp.zeros(shape4, cache.dtype) for _ in range(cache.num_layers)]
    v4 = [jnp.zeros(shape4, cache.dtype) for _ in range(cache.num_layers)]

    def on_4d(fwd):
        """A family's entry point as the engine's program calls it."""
        return lambda params, ks, vs, *inputs: fwd(
            cfg, params, *inputs, ks, vs)[:3]

    from raytpu.inference.engine import _chunk_of, _decode_of

    served = cfg.serving
    prefill_4d, chunk_4d, decode_4d = map(
        on_4d, (served.prefill, _chunk_of(served.step),
                _decode_of(served.step)))

    done = []

    def both(name, fn, fn_4d, *args):
        nonlocal k4, v4
        logits, cache.k, cache.v, *_ = fn(params, cache.k, cache.v, *args)
        with pools_4d():
            want, k4, v4 = jax.jit(fn_4d)(params, k4, v4, *args)
        assert same_bits(logits, want), f"{name}: logits differ"
        for got, ref in zip(cache.k + cache.v, k4 + v4):
            assert got.shape == (shape4[0], ps, kv * d)
            assert same_bits(np.asarray(got).reshape(shape4), ref), \
                f"{name}: pool contents differ"
        done.append(name)

    i32 = np.int32
    # Whole prefill of a, padded to a bucket (padding lands in page 0).
    bucket = 16
    assert len(prompt_a) <= bucket and len(prompt_b) == 2 * chunk
    assert cache.allocate("a", len(prompt_a) + 3)
    tokens = np.zeros((1, bucket), i32)
    tokens[0, :len(prompt_a)] = prompt_a
    both("prefill", eng._prefill_fn, prefill_4d, tokens,
         cache.prefill_dests("a", len(prompt_a), bucket))
    # b in two chunks: the second attends pages the first wrote.
    assert cache.allocate("b", len(prompt_b) + 3)
    for start in (0, chunk):
        both(f"chunk@{start}", eng._chunk_fn, chunk_4d,
             np.asarray([prompt_b[start:start + chunk]], i32),
             np.arange(start, start + chunk, dtype=i32),
             cache.chunk_dests("b", start, chunk, chunk),
             cache.table_array(["b"], cache.num_seq_pages("b")))
    # Two decode steps of both, in a bucket of four (two dummy rows).
    lens = {"a": len(prompt_a), "b": len(prompt_b)}
    width = max(cache.num_seq_pages(s) for s in lens)
    for step in range(2):
        tokens = np.zeros(4, i32)
        positions, dests = np.zeros(4, i32), np.zeros(4, i32)
        context = np.ones(4, i32)
        for i, sid in enumerate(("a", "b")):
            pos = lens[sid] + step
            tokens[i] = 5 + 7 * i + step
            positions[i], context[i] = pos, pos + 1
            dests[i] = cache.slot(sid, pos)
        both(f"decode#{step}", eng._decode_fn, decode_4d, tokens, positions,
             dests, cache.table_array(["a", "b"], width, batch=4), context)
    cache.free("a")
    cache.free("b")
    return done


def pool_facts(eng, lowered_text: str) -> dict:
    """What a lowered program (StableHLO text) does with its pools: how
    many of ``@main``'s arguments it may write in place, and the
    ``reshape`` / ``transpose`` lines that touch a pool-shaped tensor."""
    pool = eng.cache.k[0]
    kind = {"float32": "f32", "bfloat16": "bf16"}[str(pool.dtype)]
    pool_type = "tensor<%sx%s>" % ("x".join(map(str, pool.shape)), kind)
    main = lowered_text[lowered_text.index("@main("):]
    main = main[:main.index(") -> ")]
    donated = [arg for arg in main.split("%arg")[1:]
               if "tf.aliasing_output" in arg or "jax.buffer_donor" in arg]
    relaid = [ln.strip() for ln in lowered_text.splitlines()
              if re.search(r"stablehlo\.(reshape|transpose)\b", ln)
              and pool_type in ln]
    return {"pool_type": pool_type, "pool_args": main.count(pool_type),
            "donated": len(donated),
            "donated_pools": sum(pool_type in arg for arg in donated),
            "relaid": relaid}
