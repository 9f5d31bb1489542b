"""Train stack tests (reference analogues: ``python/ray/train/tests/
test_data_parallel_trainer.py``, ``test_backend.py``)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture
def trainer_env(raytpu_local, tmp_path):
    yield raytpu_local, str(tmp_path)


class TestJaxTrainer:
    def test_fit_reports_metrics(self, trainer_env):
        raytpu, tmp = trainer_env
        from raytpu.train import JaxTrainer, RunConfig, ScalingConfig, report

        def loop(config):
            for step in range(config["steps"]):
                report({"loss": 1.0 / (step + 1), "step": step})

        result = JaxTrainer(
            loop, train_loop_config={"steps": 5},
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(storage_path=tmp),
        ).fit()
        assert result.error is None
        assert len(result.metrics_history) == 5
        assert result.metrics["step"] == 4

    def test_fit_real_training(self, trainer_env):
        raytpu, tmp = trainer_env
        import optax

        from raytpu.models.mlp import MLPClassifier, xent_loss
        from raytpu.train import JaxTrainer, RunConfig, ScalingConfig, report

        def loop(config):
            model = MLPClassifier(hidden=(32,), n_classes=4)
            key = jax.random.PRNGKey(0)
            x = jax.random.normal(key, (64, 8))
            y = (x.sum(axis=1) > 0).astype(jnp.int32) * 3
            params = model.init(key, x)["params"]
            opt = optax.adam(1e-2)
            opt_state = opt.init(params)

            @jax.jit
            def step(params, opt_state):
                loss, grads = jax.value_and_grad(
                    lambda p: xent_loss(model, p, {"x": x, "y": y}))(params)
                updates, opt_state = opt.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state, loss

            losses = []
            for i in range(20):
                params, opt_state, loss = step(params, opt_state)
                losses.append(float(loss))
                report({"loss": float(loss)})

            assert losses[-1] < losses[0]  # actually learning

        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(storage_path=tmp),
        ).fit()
        assert result.error is None
        assert result.metrics["loss"] < 1.0

    def test_checkpointing_and_topk(self, trainer_env):
        raytpu, tmp = trainer_env
        from raytpu.train import (
            Checkpoint,
            CheckpointConfig,
            JaxTrainer,
            RunConfig,
            ScalingConfig,
            report,
        )

        def loop(config):
            import tempfile

            for step in range(4):
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "state.txt"), "w") as f:
                    f.write(str(step))
                report({"score": step}, checkpoint=Checkpoint(d))

        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                storage_path=tmp,
                checkpoint_config=CheckpointConfig(
                    num_to_keep=2, checkpoint_score_attribute="score"),
            ),
        ).fit()
        assert result.error is None
        assert result.checkpoint is not None
        with open(os.path.join(result.checkpoint.path, "state.txt")) as f:
            assert f.read() == "3"

    def test_worker_error_surfaces(self, trainer_env):
        raytpu, tmp = trainer_env
        from raytpu.train import JaxTrainer, RunConfig, ScalingConfig

        def loop(config):
            raise RuntimeError("worker exploded")

        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(storage_path=tmp),
        ).fit()
        assert result.error is not None
        assert "worker exploded" in str(result.error)

    def test_gang_restart_on_failure(self, trainer_env):
        raytpu, tmp = trainer_env
        from raytpu.train import (
            Checkpoint,
            FailureConfig,
            JaxTrainer,
            RunConfig,
            ScalingConfig,
            get_checkpoint,
            report,
        )

        def loop(config):
            import tempfile

            ckpt = get_checkpoint()
            start = 0
            if ckpt is not None:
                with open(os.path.join(ckpt.path, "step.txt")) as f:
                    start = int(f.read()) + 1
            for step in range(start, 6):
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step))
                report({"step": step}, checkpoint=Checkpoint(d))
                if step == 3 and start == 0:
                    raise RuntimeError("simulated mid-train crash")

        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                storage_path=tmp,
                failure_config=FailureConfig(max_failures=1)),
        ).fit()
        assert result.error is None
        assert result.metrics["step"] == 5

    def test_orbax_pytree_roundtrip(self, trainer_env, tmp_path):
        raytpu, tmp = trainer_env
        from raytpu.train import restore_pytree, save_pytree

        tree = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(4)}
        ckpt = save_pytree(tree, os.path.join(tmp, "ptree"))
        out = restore_pytree(ckpt)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))


class TestElasticTrainer:
    def test_gang_downscale_then_upscale(self, trainer_env, monkeypatch):
        """Elastic fit(): a gang failure at full strength re-forms the
        gang at the probed (smaller) world size from the latest
        checkpoint, then scales back up at a checkpoint boundary once
        capacity returns — one continuous metrics history, no error,
        and the rescale itself never burns the failure budget."""
        raytpu, tmp = trainer_env
        import raytpu.train.trainer as trainer_mod
        from raytpu.cluster import constants as tuning
        from raytpu.train import (
            Checkpoint,
            FailureConfig,
            JaxTrainer,
            RunConfig,
            ScalingConfig,
            get_checkpoint,
            get_context,
            report,
        )

        flag = os.path.join(tmp, "capacity-back")

        def feasible(sc, world, held=0):
            # Capacity oracle: one worker always fits; two fit only
            # once the (downscaled) train loop drops the flag file.
            cap = 2 if os.path.exists(flag) else 1
            return world - held <= cap - held

        monkeypatch.setattr(trainer_mod, "_world_feasible", feasible)
        monkeypatch.setattr(tuning, "ELASTIC_UPSCALE_CHECK_PERIOD_S",
                            0.0)

        def loop(config):
            import tempfile
            import time as _t

            world = get_context().world_size
            ckpt = get_checkpoint()
            start = 0
            if ckpt is not None:
                with open(os.path.join(ckpt.path, "step.txt")) as f:
                    start = int(f.read()) + 1
            for step in range(start, 20):
                if step == 2 and world == 2 and start == 0:
                    raise RuntimeError("simulated gang member loss")
                _t.sleep(0.05)
                if step >= 6:
                    with open(config["flag"], "w") as f:
                        f.write("up")
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step))
                report({"step": step, "world": world},
                       checkpoint=Checkpoint(d))

        result = JaxTrainer(
            loop, train_loop_config={"flag": flag},
            scaling_config=ScalingConfig(num_workers=2, min_workers=1,
                                         elastic=True),
            run_config=RunConfig(
                storage_path=tmp,
                failure_config=FailureConfig(max_failures=1)),
        ).fit()
        assert result.error is None
        assert result.metrics["step"] == 19
        steps = [m["step"] for m in result.metrics_history]
        worlds = [m["world"] for m in result.metrics_history]
        # Continuous across both rescales: never regresses, every step
        # of the schedule is covered exactly once.
        assert steps == sorted(steps)
        assert steps == sorted(set(steps))
        assert set(steps) == set(range(20))
        # The run really did shrink and grow back.
        assert worlds[0] == 2
        assert 1 in worlds
        assert worlds[-1] == 2


class TestGPT2Model:
    def test_forward_and_loss(self):
        from raytpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn, init_params

        cfg = GPT2Config.tiny()
        model = GPT2(cfg)
        params = init_params(model, cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, cfg.block_size),
                                    0, cfg.vocab_size)
        logits = model.apply({"params": params}, tokens)
        assert logits.shape == (2, cfg.block_size, cfg.vocab_size)
        loss = gpt2_loss_fn(model, params, tokens)
        # Initial loss ~ log(vocab) for random init.
        assert 0.8 * np.log(cfg.vocab_size) < float(loss) < 1.3 * np.log(
            cfg.vocab_size)

    def test_train_step_learns(self):
        import optax

        from raytpu.models.gpt2 import (
            GPT2, GPT2Config, init_params, make_train_step)

        cfg = GPT2Config.tiny()
        model = GPT2(cfg)
        params = init_params(model, cfg)
        opt = optax.adam(1e-3)
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(model, opt))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, cfg.block_size),
                                    0, cfg.vocab_size)
        losses = []
        for _ in range(10):
            params, opt_state, loss = step(params, opt_state, tokens)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    @staticmethod
    def tiny_cell_losses(steps=4):
        """The losses of the benchmark's tiny training cell
        (``perfbench/tests/rehearsal``: ``remat: true``, the kernels
        interpreted, AdamW), built as ``train_cell`` builds a cell."""
        import os

        from perfbench import byname, train_cell

        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "perfbench")
        dirs = [os.path.join(bench, "tests", "rehearsal"), bench]
        cfg = byname.load_json(dirs, "configs", "tiny-gpt2")
        mix = byname.load_json(dirs, "traffic", "tiny-train")
        built = train_cell.build(byname.load_family(dirs, cfg), cfg, mix,
                                 jax.devices()[:1],
                                 dict(mix["model_overrides"]))
        assert built["config"].remat is True
        key = jax.random.PRNGKey(7)
        params, opt_state = built["init"](key)
        losses = []
        for i in range(steps):
            params, opt_state, loss = built["step"](params, opt_state,
                                                    built["batch"](key, i))
            losses.append(float(loss))
        return losses

    def test_what_remat_keeps_does_not_change_the_mathematics(
            self, monkeypatch):
        """The tiny training cell's first-step loss and its loss after
        three steps are those of the tree before PR 54, whose
        ``remat: true`` saved nothing (a run of commit 96a0a01 here:
        6.71872615814209, 6.712253570556641, 6.674738883972168,
        6.639933109283447). The first is a forward pass and is held to
        the last bit; the later ones move in the last bit with the CPU
        backend's thread count (one core: ...eee, ...4a8), so against the
        recorded values they are held to two units of it, and to the last
        bit against the saving of nothing built in this process."""
        import flax.linen as nn

        from raytpu.models import gpt2

        recorded = [float.fromhex(h) for h in (
            "0x1.adff9c0000000p+2", "0x1.ad95900000000p+2",
            "0x1.ab2eec0000000p+2", "0x1.a8f4aa0000000p+2")]
        kept = self.tiny_cell_losses()
        assert kept[0] == recorded[0]
        np.testing.assert_allclose(kept, recorded, rtol=2 * 2.0 ** -23,
                                   atol=0)
        monkeypatch.setattr(
            gpt2, "remat_block",
            lambda block, remat: nn.remat(block, prevent_cse=False,
                                          policy=None))
        assert self.tiny_cell_losses() == kept

    def test_sharded_train_step_8dev(self):
        """Milestone B shape: GPT-2 with dp x fsdp x tp sharding on the
        virtual 8-device mesh."""
        import optax

        from raytpu.models.gpt2 import (
            GPT2, GPT2Config, init_params, make_train_step)
        from raytpu.parallel.mesh import build_mesh
        from raytpu.parallel.sharding import shard_batch, shard_params

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        cfg = GPT2Config(vocab_size=512, block_size=64, n_layer=2, n_head=4,
                         n_embd=128, dtype=jnp.float32)
        mesh = build_mesh({"dp": 2, "fsdp": 2, "tp": 2})
        model = GPT2(cfg)
        params = init_params(model, cfg)
        params = shard_params(params, mesh)
        opt = optax.sgd(1e-2)
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(model, opt))
        tokens = jax.random.randint(jax.random.PRNGKey(2), (8, cfg.block_size),
                                    0, cfg.vocab_size)
        tokens = shard_batch(tokens, mesh, axes=("dp",))
        params, opt_state, loss = step(params, opt_state, tokens)
        assert np.isfinite(float(loss))


class TestResNetModel:
    def test_forward(self):
        from raytpu.models.resnet import ResNet, ResNetConfig

        cfg = ResNetConfig.tiny()
        model = ResNet(cfg)
        x = jnp.ones((2, 32, 32, 3))
        variables = model.init(jax.random.PRNGKey(0), x)
        logits = model.apply(variables, x)
        assert logits.shape == (2, 10)

    def test_resnet50_is_the_real_bottleneck_architecture(self):
        """The 50/101 family is DEFINED by bottleneck blocks; the
        canonical ResNet-50 has 25.557M parameters — a basic-block
        (3,4,6,3) stack (ResNet-34 shape) has 21.8M and would silently
        misrepresent the reference benchmark family."""
        from raytpu.models.resnet import ResNet, ResNetConfig

        cfg = ResNetConfig.resnet50()
        assert cfg.bottleneck
        model = ResNet(cfg)
        v = model.init(jax.random.PRNGKey(0), jnp.ones((1, 64, 64, 3)))
        n = sum(p.size for p in jax.tree_util.tree_leaves(v["params"]))
        assert 25.4e6 < n < 25.7e6, f"{n/1e6:.2f}M params"
        # train-mode batch stats exist and forward runs
        out, _ = model.apply(v, jnp.ones((2, 64, 64, 3)), train=True,
                             mutable=["batch_stats"])
        assert out.shape == (2, 1000)


class TestPrepareDataLoader:
    """Unit tests of the TorchTrainer migration shim's loader rebuild
    (ADVICE r4 #4 / VERDICT r4 weak #6): constructor attrs preserved,
    loud warnings on the unshardable pass-through cases. A fake world
    of 2 is injected by monkeypatching torch.distributed — construction
    never iterates, so no worker processes spawn."""

    @pytest.fixture
    def world2(self, monkeypatch):
        import torch.distributed as dist

        monkeypatch.setattr(dist, "is_available", lambda: True)
        monkeypatch.setattr(dist, "is_initialized", lambda: True)
        monkeypatch.setattr(dist, "get_world_size", lambda: 2)
        monkeypatch.setattr(dist, "get_rank", lambda: 0)

    def test_rebuild_preserves_loader_attrs(self, world2):
        import torch
        from torch.utils.data import DataLoader, TensorDataset

        from raytpu.train.torch_trainer import prepare_data_loader

        def init_fn(_):
            pass

        gen = torch.Generator()
        ds = TensorDataset(torch.arange(32).float())
        loader = DataLoader(ds, batch_size=4, shuffle=True,
                            num_workers=2, pin_memory=True,
                            worker_init_fn=init_fn, generator=gen,
                            persistent_workers=True, prefetch_factor=4,
                            timeout=7.5, drop_last=True)
        out = prepare_data_loader(loader)
        assert out is not loader
        assert out.batch_size == 4 and out.drop_last
        assert out.pin_memory is True
        assert out.worker_init_fn is init_fn
        assert out.generator is gen
        assert out.persistent_workers is True
        assert out.prefetch_factor == 4
        assert out.timeout == 7.5
        assert out.sampler.shuffle and out.sampler.num_replicas == 2

    def test_rebuild_no_workers_skips_worker_only_kwargs(self, world2):
        import torch
        from torch.utils.data import DataLoader, TensorDataset

        from raytpu.train.torch_trainer import prepare_data_loader

        ds = TensorDataset(torch.arange(8).float())
        out = prepare_data_loader(DataLoader(ds, batch_size=2))
        assert out.num_workers == 0
        assert not out.sampler.shuffle  # eval loader stays ordered

    def test_custom_sampler_replacement_warns(self, world2):
        import torch
        from torch.utils.data import (DataLoader, TensorDataset,
                                      WeightedRandomSampler)

        from raytpu.train.torch_trainer import prepare_data_loader

        ds = TensorDataset(torch.arange(8).float())
        loader = DataLoader(
            ds, batch_size=2,
            sampler=WeightedRandomSampler([1.0] * 8, 8))
        with pytest.warns(UserWarning, match="WeightedRandomSampler"):
            out = prepare_data_loader(loader)
        assert out.sampler.num_replicas == 2  # still sharded

    def test_iterable_dataset_warns_and_passes_through(self, world2):
        import torch
        from torch.utils.data import DataLoader, IterableDataset

        from raytpu.train.torch_trainer import prepare_data_loader

        class Stream(IterableDataset):
            def __iter__(self):
                return iter(range(8))

        loader = DataLoader(Stream(), batch_size=2)
        with pytest.warns(UserWarning, match="FULL dataset"):
            assert prepare_data_loader(loader) is loader

    def test_batch_sampler_loader_warns_and_passes_through(self, world2):
        import torch
        from torch.utils.data import (BatchSampler, DataLoader,
                                      SequentialSampler, TensorDataset)

        from raytpu.train.torch_trainer import prepare_data_loader

        ds = TensorDataset(torch.arange(8).float())
        bs = BatchSampler(SequentialSampler(ds), batch_size=2,
                          drop_last=False)
        loader = DataLoader(ds, batch_sampler=bs)
        with pytest.warns(UserWarning, match="FULL dataset"):
            assert prepare_data_loader(loader) is loader
