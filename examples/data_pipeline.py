"""Streaming data pipeline: transforms, distributed shuffle/groupby, and
device-ready batches (reference analogue: Ray Data quickstart).

  python examples/data_pipeline.py
On a machine without an accelerator (or to leave one alone):
  JAX_PLATFORMS=cpu python examples/data_pipeline.py
"""

import os
import sys

# Run in-repo without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import raytpu
import raytpu.data as rd


def main():
    raytpu.init()

    ds = (rd.range(10_000, blocks=8)
          .map_batches(lambda b: {"id": b["id"],
                                  "bucket": b["id"] % 7,
                                  "x": np.sqrt(b["id"].astype(np.float64))})
          .filter(lambda row: row["id"] % 2 == 0))

    # Distributed group-by: every group lands whole on one reducer.
    means = {r["bucket"]: r["mean(x)"]
             for r in ds.groupby("bucket").mean("x").take_all()}
    print("per-bucket mean sqrt:", {k: round(v, 2)
                                    for k, v in sorted(means.items())})

    # Shuffle + split for train/eval, then feed device-ready batches.
    train, test = ds.train_test_split(0.1, shuffle=True, seed=0)
    print("train/test rows:", train.count(), test.count())
    batch = next(train.iter_jax_batches(batch_size=256))
    print("first device batch:", {k: (v.shape, str(v.dtype))
                                  for k, v in batch.items()})

    raytpu.shutdown()


if __name__ == "__main__":
    main()
