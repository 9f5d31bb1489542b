"""A training cell: ``JaxTrainer.fit()`` over the program's
``make_train_step``, on one chip or sharded over four.

The loop below is what a user of ``raytpu.train`` writes (compare
``chip_smoke.py``): build the model from its configuration, put the state
on a mesh from ``raytpu.parallel``, jit the step with donated state, fetch
the loss every step and ``train.report`` it. The benchmark adds the clock,
the window, the reference check and the trace, nothing to the program.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Mapping

import numpy as np

SEED_MASK = 2**31 - 1  # jax.random.PRNGKey takes what 32 signed bits hold


def build(family, cfg: Mapping, mix: Mapping, devices,
          overrides: Mapping = ()):
    """Everything the loop runs, as jitted callables and shardings:
    ``init(key) -> (params, opt_state)`` in one program on the device(s),
    ``batch(key, step) -> tokens``, ``step(params, opt_state, tokens)``,
    ``ref_loss(params, tokens)``, and the mesh (``None`` on one chip)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from raytpu.parallel import build_mesh
    from raytpu.parallel.sharding import tree_shardings

    pcfg = family.program_config(cfg, overrides)
    init_params, make_step = family.train_parts(pcfg)
    train = cfg.get("train", {})
    opt = optax.adamw(train.get("learning_rate", 3e-4),
                      weight_decay=train.get("weight_decay", 0.1))
    batch_shape = (int(mix["global_batch"]), int(mix["seq_len"]))
    vocab = int(cfg["vocab_size"])

    def init_fn(key):
        params = init_params(key)
        return params, opt.init(params)

    def batch_fn(key, step):
        return jax.random.randint(jax.random.fold_in(key, step),
                                  batch_shape, 0, vocab, jnp.int32)

    traces: List[int] = []  # the body runs once per trace, i.e. compile
    train_step = make_step(opt)

    def counted_step(params, opt_state, tokens):
        traces.append(1)
        return train_step(params, opt_state, tokens)

    mesh = None
    jit_kw: Dict = {}
    init_kw: Dict = {}
    batch_kw: Dict = {}
    mesh_axes = dict(mix.get("mesh") or {})
    if mesh_axes:
        mesh = build_mesh(mesh_axes, list(devices))
        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        state = (tree_shardings(abstract[0], mesh),
                 tree_shardings(abstract[1], mesh))
        data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh_axes)
        tokens_sh = NamedSharding(mesh, PartitionSpec(data_axes or None))
        # The whole state is born on the mesh and what a step returns is
        # pinned to what it took: otherwise the second step sees other
        # layouts than the first and compiles again (PR 21, cause 7).
        init_kw = {"out_shardings": state}
        batch_kw = {"out_shardings": tokens_sh}
        jit_kw = {"in_shardings": state + (tokens_sh,),
                  "out_shardings": state + (None,)}
    return {
        "config": pcfg, "mesh": mesh, "traces": traces,
        "init": jax.jit(init_fn, **init_kw),
        "batch": jax.jit(batch_fn, **batch_kw),
        "step": jax.jit(counted_step, donate_argnums=(0, 1), **jit_kw),
        "ref_loss": jax.jit(lambda p, t: family.loss(cfg, p, t)),
        "tokens_per_step": batch_shape[0] * batch_shape[1],
    }


def memory_peak_bytes(device) -> int:
    """Peak memory of a chip as the runtime counts it: its buffers at
    their peak plus the scratch it reserved for the loaded programs'
    temporaries, which ``peak_bytes_in_use`` leaves out (a train step of
    gpt2-medium: 4.3 GB of buffers, 6.5 GB reserved; my chip run, PR 23)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def mesh_context(mesh):
    import jax

    return jax.set_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()


def train_loop(config: Dict) -> None:
    """Runs on the trainer's worker: set-up, reference check, warm steps,
    then whole steps for ``seconds`` seconds. Everything the benchmark
    wants back goes through ``train.report``."""
    import jax

    from raytpu import train

    from perfbench.byname import load_family

    clock = time.perf_counter
    marks = {"loop_start": clock()}
    devices = jax.devices()[:config["chips"]]
    family = load_family(config["dirs"], config["cfg"])
    built = build(family, config["cfg"], config["mix"], devices,
                  config.get("overrides", ()))
    key = jax.random.PRNGKey(config["seed"] & SEED_MASK)
    step, batch = built["step"], built["batch"]
    with mesh_context(built["mesh"]):
        params, opt_state = built["init"](key)
        jax.block_until_ready(params)
        marks["weights"] = clock()
        tokens = batch(key, 0)
        ref = float(built["ref_loss"](params, tokens))
        marks["check"] = clock()
        losses = []
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
        marks["first_step"] = clock()
        for i in range(1, 1 + int(config["mix"].get("warm_steps", 2))):
            params, opt_state, loss = step(params, opt_state,
                                           batch(key, i))
            losses.append(float(loss))
        marks["warm"] = clock()
        traces_before = len(built["traces"])
        train.report({"phase": "setup", "marks": marks, "ref_loss": ref,
                      "first_loss": losses[0], "warm_losses": losses})

        tracer = None
        if config.get("trace_dir"):
            from perfbench.probe import Tracer

            tracer = Tracer(config["trace_dir"], config["trace_seconds"])
        ends = [clock()]  # step boundaries: the window opens on one
        window_losses = []
        i = len(losses)
        if tracer:
            tracer.start()
        while ends[-1] - ends[0] < config["seconds"]:
            with (tracer.span("pb.train.step") if tracer
                  else contextlib.nullcontext()):
                params, opt_state, loss = step(params, opt_state,
                                               batch(key, i))
                window_losses.append(float(loss))  # the step has finished
            ends.append(clock())
            i += 1
            train.report({"phase": "step", "step": i, "loss":
                          window_losses[-1]})
            if tracer and tracer.running \
                    and ends[-1] - ends[0] >= tracer.seconds:
                tracer.stop()
        if tracer and tracer.running:
            tracer.stop()
    peak = max(memory_peak_bytes(d) for d in devices)
    train.report({"phase": "window", "step_ends": ends,
                  "memory_stats": dict(devices[0].memory_stats() or {}),
                  "losses": window_losses,
                  "retraces": len(built["traces"]) - traces_before,
                  "memory_peak_bytes": int(peak),
                  "tokens_per_step": built["tokens_per_step"],
                  "xplane": tracer.xplane() if tracer else None})


def run(*, cell, cfg, mix, dirs, seed, seconds, trace_dir, trace_seconds, devices,
        process_start, marks, log) -> Dict:
    import raytpu
    from raytpu.train import JaxTrainer, ScalingConfig

    from perfbench.byname import load_family
    from perfbench.rundata import RunData

    raytpu.init()
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "cfg": dict(cfg), "mix": dict(mix), "dirs": list(dirs),
                "seed": int(seed),
                "seconds": float(seconds), "chips": len(devices),
                "overrides": dict(mix.get("model_overrides", ())),
                "trace_dir": trace_dir, "trace_seconds": trace_seconds},
            scaling_config=ScalingConfig(num_workers=1)).fit()
    finally:
        raytpu.shutdown()
    if result.error is not None:
        raise RuntimeError("training failed") from result.error
    by_phase = {m["phase"]: m for m in result.metrics_history
                if m.get("phase") in ("setup", "window")}
    setup, window = by_phase["setup"], by_phase["window"]
    ends = window["step_ends"]
    losses = window["losses"]
    m = setup["marks"]
    marks.update({
        "trainer_start": m["loop_start"] - process_start - marks["import"],
        "weights": m["weights"] - m["loop_start"],
        "check": m["check"] - m["weights"],
        "program_load_first_step": m["first_step"] - m["check"],
        "warm_steps": m["warm"] - m["first_step"],
        "setup_s": ends[0] - process_start})
    rel = abs(setup["first_loss"] - setup["ref_loss"]) \
        / abs(setup["ref_loss"])
    tol = float(mix["check"]["tolerance"])
    log("setup", {k: round(v, 3) for k, v in marks.items()}
        | {"ref_loss": setup["ref_loss"], "first_loss": setup["first_loss"],
           "check_rel_err": rel})
    finite = [bool(np.isfinite(x)) for x in losses]
    step_s = np.diff(ends)
    log("window", {"seconds": ends[-1] - ends[0], "steps": len(losses),
                   "step_ms_min": 1e3 * float(step_s.min()),
                   "step_ms_max": 1e3 * float(step_s.max()),
                   "retraces": window["retraces"],
                   "memory_stats": window["memory_stats"],
                   "first_loss": losses[0], "last_loss": losses[-1]})
    tokens_per_s = len(losses) * window["tokens_per_step"] \
        / (ends[-1] - ends[0])
    data = RunData(
        cell=cell, cfg=cfg, mix=mix, family=load_family(dirs, cfg),
        chips=len(devices), peaks=None, window=(ends[0], ends[-1]),
        end_to_end={"setup_s": ends[0] - process_start,
                    "train_tokens_per_s_chip": tokens_per_s / len(devices)},
        memory_peak_bytes=window["memory_peak_bytes"], step_ends=ends,
        tokens_per_step=window["tokens_per_step"])
    return {"data": data, "xplane": window.get("xplane"),
            "correct": bool(np.isfinite(rel) and rel <= tol and all(finite)
                            and window["retraces"] == 0),
            "attempted": len(losses), "failed": finite.count(False),
            "compared": {"check_rel_err": [rel, tol],
                         "non_finite_losses": [finite.count(False), 0],
                         "retraces": [window["retraces"], 0]}}
