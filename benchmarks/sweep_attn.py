"""Flash-attention tile sweep: the three kernels, each timed alone.

For every shape and every tile (rows of q x rows of k) this times the
forward, dq and dk/dv kernels of ``raytpu/ops/flash_attention.py`` one at a
time, in ONE process (a process a tile cost a quarter of a minute each to
reach the chip, and SWEEP_ATTN_r05.json is five of them timing out). The
tiles go in through ``DEFAULT_BLOCK_Q/K``, the module attributes that
stand before the file's own table (``None``: the table). Run it on the
chip:

    chiprun --chips 1 -- python benchmarks/sweep_attn.py
    ... --shapes 256x1024x64,16x256x128 --tiles 512x256,256x512
    ... --file some/other/flash_attention.py   # another copy of the kernels

One JSON line per (shape, tile), a summary line with the best tile of each
kernel per shape, and the whole table in chiprun_out/sweep_attn.json. The
first tile of each shape is also checked against the einsum reference.
Off a TPU it refuses to time anything (``--smoke`` runs tiny shapes in the
interpreter to prove the control flow).

A time is the device's own: the mean length of the kernel's custom call
in a profiler trace of ``--repeat`` calls, compile and warm-up excluded
(on the host's clock a call of these sizes is mostly its dispatch).
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SHAPES = "256x1024x64,100x1024x64,16x256x128,25x512x64"
TILES = ",".join(f"{q}x{k}" for q in (128, 256, 512, 1024)
                 for k in (128, 256, 512, 1024))


def load(path):
    spec = importlib.util.spec_from_file_location("flash_under_sweep", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_ms(calls, logdir, repeat):
    """Milliseconds a call of each kernel takes on the device: the mean
    length of its custom call's events in a profiler trace of ``repeat``
    calls of each of ``calls`` (name -> thunk), read and told apart as the
    benchmark's ``flash_attn_roofline`` reads them."""
    import jax

    from perfbench import trace_reduce
    from perfbench.layer_metrics.flash_attn_roofline import classify

    for thunk in calls.values():
        jax.block_until_ready(thunk())  # compile, warm
    shutil.rmtree(logdir, ignore_errors=True)
    with jax.profiler.trace(logdir):
        for thunk in calls.values():
            for _ in range(repeat):
                out = thunk()
            jax.block_until_ready(out)
    path, = glob.glob(os.path.join(logdir, "plugins/profile/*/*.xplane.pb"))
    events = trace_reduce.kernel_events(trace_reduce.load_xplane(path),
                                        trace_reduce.is_pallas)
    spent = {}
    for e in (e for evs in events.values() for e in evs):
        found = classify(e.name)
        if found and found[0] in calls:
            spent.setdefault(found[0], []).append(e.seconds * 1e3)
    return {k: round(sum(v) / len(v), 4) for k, v in spent.items()}


def wall_ms(calls):
    """The interpreter's wall time of one call each: --smoke only."""
    import jax

    out = {}
    for name, thunk in calls.items():
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        out[name] = round((time.perf_counter() - t0) * 1e3, 4)
    return out


def kernels(fa, interpret):
    """The three kernels as functions of (q, k, v, o, lse, g)."""
    import jax

    scale = lambda q: q.shape[-1] ** -0.5  # noqa: E731

    def fwd(q, k, v):
        return fa._flash_forward_pallas(
            q, k, v, True, scale(q), fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K,
            interpret)

    def bwd(q, k, v, o, lse, g):
        return fa._flash_backward_pallas(
            q, k, v, o, lse, g, True, scale(q), fa.DEFAULT_BLOCK_Q,
            fa.DEFAULT_BLOCK_K, interpret)

    # XLA drops the call whose results are not returned.
    return {"fwd": jax.jit(fwd),
            "dq": jax.jit(lambda *a: bwd(*a)[0]),
            "dkv": jax.jit(lambda *a: bwd(*a)[1:])}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=SHAPES,
                    help="batch*heads x T x head_dim, comma-separated")
    ap.add_argument("--tiles", default=TILES,
                    help="rows of q x rows of k, comma-separated; 0x0 is "
                         "the file's own table")
    ap.add_argument("--file", default=os.path.join(
        REPO, "raytpu", "ops", "flash_attention.py"))
    ap.add_argument("--repeat", type=int, default=8,
                    help="traced calls of each kernel a tile")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "sweep_attn.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.smoke:
        sys.exit("sweep_attn times kernels on a TPU; --smoke for the CPU")
    fa = load(args.file)
    shapes = [tuple(int(x) for x in s.split("x"))
              for s in args.shapes.split(",")]
    tiles = [tuple(int(x) for x in s.split("x"))
             for s in args.tiles.split(",")]
    if args.smoke:
        shapes, tiles = [(2, 256, 64)], [(128, 128)]
    names = ("fwd", "dq", "dkv")

    rows = []
    for bh, t, d in shapes:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(kk, (1, bh, t, d), jnp.bfloat16)
                      for kk in keys)
        ref_o, ref_lse = fa._attn_fwd_reference(q, k, v, True, d ** -0.5)
        ref_grads = fa._attn_bwd_reference(q, k, v, ref_o, ref_lse, g, True,
                                           d ** -0.5)
        for n, (tq, tk) in enumerate(tiles):
            # The tile is read when a call is traced: new functions a tile.
            fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K = tq or None, tk or None
            fns = kernels(fa, interpret=args.smoke)
            row = {"shape": [bh, t, d], "tile": [tq, tk]}
            try:
                o, lse = fns["fwd"](q, k, v)
                bwd_args = (q, k, v, o, lse, g)
                calls = {"fwd": lambda: fns["fwd"](q, k, v),
                         "dq": lambda: fns["dq"](*bwd_args),
                         "dkv": lambda: fns["dkv"](*bwd_args)}
                ms = wall_ms(calls) if args.smoke else device_ms(
                    calls, os.path.join(os.path.dirname(args.out),
                                        "sweep_attn_trace"), args.repeat)
                row.update((name + "_ms", ms[name]) for name in names)
                if n == 0:  # against the reference, once a shape
                    got = [o, fns["dq"](*bwd_args), *fns["dkv"](*bwd_args)]
                    want = [ref_o, *ref_grads]
                    row["max_err"] = [
                        float(np.abs(np.asarray(a, np.float32)
                                     - np.asarray(b, np.float32)).max())
                        for a, b in zip(got, want)]
            except Exception as e:  # a tile Mosaic refuses (VMEM, alignment)
                row["error"] = str(e)[-300:]
            rows.append(row)
            print(json.dumps(row), flush=True)

    summary = {"metric": "flash_attention_tile_sweep", "device": str(device),
               "device_kind": device.device_kind, "file": args.file,
               "best": {}}
    for shape in shapes:
        mine = [r for r in rows if r["shape"] == list(shape)]
        summary["best"]["x".join(map(str, shape))] = {
            name: min(([r[name + "_ms"], r["tile"]] for r in mine
                       if name + "_ms" in r), default=None)
            for name in names}
    summary["sweep"] = rows
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "sweep"}))


if __name__ == "__main__":
    main()
