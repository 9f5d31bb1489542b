"""One routed layer's three expert products alone, each way, timed on
the device.

For every shape this runs a chain of ``--layers`` layers (each with
matrices of its own, as a program has them: one set read by every call
would sit in fast memory) through ``jax.lax.ragged_dot``, through
megablox ``gmm`` as shipped with tiles ``(128, K, N)`` (decode shapes
whose experts fit VMEM whole only) and through
``raytpu/ops/grouped_matmul.py``'s kernel, in the blocks of columns its
own rule gives (``kernel``) and, where that rule cuts an expert, in half
and a quarter of them (``kernel tn=<gate and up>,<down>``), and prints
the device's milliseconds a layer beside the least its bytes and FLOPs
allow. Run it on the chip:

    chiprun --chips 1 -- python benchmarks/moe_products.py [shape ...]

A time is the device's own: its busy time in a profiler trace of
``--repeat`` calls of the chain, compile and warm-up excluded (on the
host's clock a call of these sizes is mostly its dispatch). One JSON line
a shape; the whole table in ``chiprun_out/moe_products.json``
(``moe_products_buffer<n>.json`` under ``--buffer-mib n``,
``moe_products_rows<n>.json`` under ``--row-tile n``). Off a TPU
it refuses to time anything.
"""

from __future__ import annotations

import argparse
import functools
import glob
import importlib
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def spread(experts, sizes):
    """``sizes`` rows on as many experts spread evenly over ``experts``."""
    tokens = np.zeros(experts, np.int32)
    tokens[np.linspace(0, experts - 1, len(sizes)).round().astype(int)] = sizes
    return tokens


def drawn(experts, rows):
    """``rows`` rows, each on an expert drawn uniformly."""
    return np.bincount(np.random.default_rng(0).integers(0, experts, rows),
                       minlength=experts).astype(np.int32)


# name: (rows, experts, K, N, rows an expert). The decode shapes as the
# cells' step logs count them (PERF.md, PR 40); JoyAI's rows are 32 x 8
# of which the 32 held experts' share, an eighth, is live.
SHAPES = {
    "mellum-decode": (256, 64, 2304, 896, spread(64, [32] + [8] * 28)),
    "olmoe-decode": (128, 64, 2048, 1024, spread(64, [3] * 18 + [2] * 37)),
    "joyai-decode": (256, 32, 2048, 768, spread(32, [2] * 14 + [1] * 4)),
    "olmoe-prefill-128": (1024, 64, 2048, 1024, drawn(64, 1024)),
    "olmoe-prefill-256": (2048, 64, 2048, 1024, drawn(64, 2048)),
    "mellum-prefill-1280": (10240, 64, 2304, 896, drawn(64, 10240)),
    "mellum-chunk-2048": (16384, 64, 2304, 896, drawn(64, 16384)),
    "joyai-prefill-1280": (10240, 32, 2048, 768, drawn(32, 1280)),
    "joyai-chunk-2048": (16384, 32, 2048, 768, drawn(32, 2048)),
    # Wider than any served program: a routed training batch's rows (no
    # cell trains a routed model), 1,024 to 4,096 an expert.
    "olmoe-wide-65536": (65536, 64, 2048, 1024, drawn(64, 65536)),
    "olmoe-wide-131072": (131072, 64, 2048, 1024, drawn(64, 131072)),
    "mellum-wide-131072": (131072, 64, 2304, 896, drawn(64, 131072)),
    "eight-experts-32768": (32768, 8, 2048, 1024, drawn(8, 32768)),
    # PR 48. LFM2 holds all 64 experts and a step of 64 streams touches
    # 63; K-EXAONE holds 16 of 128, so an eighth of a verify step's 32 x
    # 8 rows is live, on three experts (the cells' step logs, PR 47).
    "lfm2-decode": (256, 64, 2048, 1536, spread(64, [5] * 4 + [4] * 59)),
    "lfm2-chunk-2048": (8192, 64, 2048, 1536, drawn(64, 8192)),
    "kexaone-verify": (256, 16, 6144, 2048, spread(16, [11, 11, 10])),
    "kexaone-chunk-2048": (16384, 16, 6144, 2048, drawn(16, 2048)),
}


def ways(rows, k, n):
    import jax

    from raytpu.ops import grouped_matmul as gm

    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm

    def ragged(x, wg, wi, wo, t):
        return gm._ragged(gm._ragged(x, (wg, wi), t), (wo,), t)

    def megablox(x, wg, wi, wo, t):
        def one(x, w, tiling):
            return gmm(x, w, t, preferred_element_type=x.dtype,
                       tiling=tiling)
        h = jax.nn.silu(one(x, wg, (128, k, n))) * one(x, wi, (128, k, n))
        return one(h, wo, (128, n, k))

    def kernel(x, wg, wi, wo, t, up=None, down=None):
        return gm._moe_grouped_pallas(
            gm._moe_grouped_pallas(x, (wg, wi), t, tn=up), (wo,), t, tn=down)

    found = {"ragged_dot": ragged, "kernel": kernel}
    up, down = gm._block_width(k, n, 2, 2), gm._block_width(n, k, 2, 1)
    if (up, down) == (n, k):
        if rows <= 256:
            found["megablox"] = megablox
        return found
    # An expert the rule cuts: half and a quarter of its blocks too.
    for cut in (2, 4):
        if not (up // cut % 128 or down // cut % 128):
            found[f"kernel tn={up // cut},{down // cut}"] = functools.partial(
                kernel, up=up // cut, down=down // cut)
    return found


def measure(name, layers, repeat, logdir):
    import jax
    import jax.numpy as jnp

    from perfbench import peaks, trace_reduce

    rows, experts, k, n, tokens = SHAPES[name]
    peak = peaks.peaks_for(jax.devices()[0].device_kind)
    keys = jax.random.split(jax.random.PRNGKey(1), 3 * layers + 1)

    @jax.jit
    def seeded(key, scale, like):
        return (jax.random.normal(key, like.shape) * scale).astype(like.dtype)

    def held(i, shape):
        return seeded(keys[i], shape[1] ** -0.5,
                      jax.ShapeDtypeStruct(shape, jnp.bfloat16))

    weights = [(held(3 * i, (experts, k, n)), held(3 * i + 1, (experts, k, n)),
                held(3 * i + 2, (experts, n, k))) for i in range(layers)]
    x = seeded(keys[-1], 1.0, jax.ShapeDtypeStruct((rows, k), jnp.bfloat16))
    live, touched = int(tokens.sum()), int((tokens > 0).sum())
    least_ms = 1e3 * max(touched * 3 * k * n * 2 / peak.hbm_bytes_per_s,
                         live * 3 * 2 * k * n / peak.bf16_flops_per_s)
    line = {"shape": name, "rows": rows, "experts": experts, "k": k, "n": n,
            "live_rows": live, "experts_touched": touched,
            "least_ms_a_layer": round(least_ms, 4)}
    first = None
    for way, layer in ways(rows, k, n).items():
        @jax.jit
        def chain(x, weights, t):
            for wg, wi, wo in weights:
                x = layer(x, wg, wi, wo, t)
                x = x * jax.lax.rsqrt(jnp.mean(jnp.square(
                    x.astype(jnp.float32)), -1, keepdims=True)
                    + 1e-6).astype(x.dtype)
            return x

        t = jnp.asarray(tokens)
        out = np.asarray(jax.block_until_ready(
            chain(x, weights, t)).astype(jnp.float32))  # compiled
        dead, out = out[live:], out[:live]
        first = out if first is None else first
        shutil.rmtree(logdir, ignore_errors=True)
        with jax.profiler.trace(logdir):
            for _ in range(repeat):
                last = chain(x, weights, t)
            jax.block_until_ready(last)
        path, = glob.glob(os.path.join(
            logdir, "plugins/profile/*/*.xplane.pb"))
        trace = trace_reduce.load_xplane(path)
        busy = trace_reduce.measure(
            trace_reduce.busy_intervals(trace, min(trace.device)))
        ms = 1e3 * busy / (repeat * layers)
        line[way] = {
            "ms_a_layer": round(ms, 4),
            "roofline_pct": round(100 * least_ms / ms, 1),
            "differs_from_ragged_dot": float(
                np.abs(out - first).max() / np.abs(first).max()),
            "dead_rows_zero": not dead.any(),
            "ops": [[label, round(1e3 * s / (repeat * layers), 4)]
                    for label, s in trace_reduce.heaviest_ops(trace, top=4)]}
    return line


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("shapes", nargs="*", default=list(SHAPES))
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--repeat", type=int, default=5)
    parser.add_argument(
        "--buffer-mib", type=int,
        help="the kernel's VMEM for expert blocks, where its rule is to be "
             "read at another than ops/grouped_matmul.py's own")
    parser.add_argument(
        "--row-tile", type=int,
        help="the rows of one visit, likewise (a multiple of 16)")
    args = parser.parse_args()
    import jax

    from raytpu.ops import grouped_matmul as gm

    if args.buffer_mib:
        gm._EXPERT_BUFFER_BYTES = args.buffer_mib << 20
        gm._VMEM_LIMIT_BYTES = (args.buffer_mib + 24) << 20
    if args.row_tile:
        gm._ROW_TILE = args.row_tile

    if jax.devices()[0].platform != "tpu":
        sys.exit("moe_products times the device: run it through chiprun")
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    table = []
    out_name = "moe_products{}{}.json".format(
        f"_buffer{args.buffer_mib}" if args.buffer_mib else "",
        f"_rows{args.row_tile}" if args.row_tile else "")
    for name in args.shapes:
        table.append(measure(name, args.layers, args.repeat,
                             os.path.join(out_dir, "moe_products_trace")))
        print(json.dumps(table[-1]), flush=True)
        with open(os.path.join(out_dir, out_name), "w") as f:
            json.dump(table, f, indent=1)
    shutil.rmtree(os.path.join(out_dir, "moe_products_trace"),
                  ignore_errors=True)


if __name__ == "__main__":
    main()
