"""Migrating a torch training loop with TorchTrainer.

A reference user's ``ray.train.torch`` loop runs here unchanged: swap the
import, keep the loop. The gang forms a gloo process group (this image is
CPU-only torch); ``prepare_model`` DDP-wraps, ``prepare_data_loader``
shards with a DistributedSampler. When ready for TPU, move the loop to
``JaxTrainer`` (see train_gpt2.py) — the surrounding config is identical.

Run:  python examples/torch_migration.py
(nothing here needs an accelerator; JAX_PLATFORMS=cpu leaves one alone)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def train_loop_per_worker(config):
    import torch
    import torch.distributed as dist
    from torch.utils.data import DataLoader, TensorDataset

    from raytpu.train import (get_context, prepare_data_loader,
                              prepare_model, report)

    torch.manual_seed(0)
    model = prepare_model(torch.nn.Sequential(
        torch.nn.Linear(4, 16), torch.nn.ReLU(), torch.nn.Linear(16, 1)))
    opt = torch.optim.SGD(model.parameters(), lr=config["lr"])

    x = torch.randn(256, 4)
    y = (x.sum(dim=1, keepdim=True) > 0).float()
    loader = prepare_data_loader(
        DataLoader(TensorDataset(x, y), batch_size=32, shuffle=True))

    for epoch in range(config["epochs"]):
        if hasattr(loader.sampler, "set_epoch"):
            loader.sampler.set_epoch(epoch)
        total = 0.0
        for xb, yb in loader:
            opt.zero_grad()
            loss = torch.nn.functional.binary_cross_entropy_with_logits(
                model(xb), yb)
            loss.backward()  # DDP averages grads across the gang
            opt.step()
            total += float(loss)
        report({"epoch": epoch, "loss": total,
                "rank": get_context().get_world_rank(),
                "world": dist.get_world_size()})


def main():
    import raytpu
    from raytpu.train import RunConfig, ScalingConfig, TorchTrainer

    raytpu.init(num_cpus=4, ignore_reinit_error=True)
    result = TorchTrainer(
        train_loop_per_worker,
        train_loop_config={"lr": 0.05, "epochs": 3},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path="/tmp/raytpu_torch_example"),
    ).fit()
    print("final:", result.metrics)
    raytpu.shutdown()


if __name__ == "__main__":
    main()
