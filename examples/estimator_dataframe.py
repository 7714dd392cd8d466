"""Estimator API on a DataFrame — the analog of the reference's Spark
estimator example (``examples/keras_spark_rossmann_estimator.py``
shape: build a DataFrame, declare an estimator with feature/label
columns, ``fit(df)``, predict with the returned model).

Run::

    python examples/estimator_dataframe.py --num-proc 2

The DataFrame materializes into the Store as per-rank shards
(``horovod_tpu/estimator/dataframe.py``, reference
``spark/common/util.py:360-608``), training fans out through the
launcher's run-function mode, and the trained model comes back with
its loss history.
"""

import argparse

import numpy as np

try:
    import horovod_tpu  # noqa: F401
except ImportError:  # running from a source checkout
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

# Honor HOROVOD_PLATFORM=cpu before any jax use: the driver-side
# predict() runs jax too.
from horovod_tpu.common.platform import ensure_platform  # noqa: E402

ensure_platform()


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--num-proc", type=int, default=2)
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--store", default="/tmp/hvd_estimator_store")
    args = p.parse_args()

    import flax.linen as nn
    import pandas as pd

    from horovod_tpu.spark.keras import KerasEstimator, LocalStore

    # A toy tabular problem: y = which of 3 anchors (f1, f2) is nearest.
    rng = np.random.RandomState(0)
    n = 512
    f1, f2 = rng.rand(n).astype(np.float32), rng.rand(n).astype(np.float32)
    anchors = np.array([[0.2, 0.2], [0.8, 0.3], [0.5, 0.9]], np.float32)
    y = np.argmin(((np.stack([f1, f2], 1)[:, None, :] - anchors) ** 2)
                  .sum(-1), axis=1)
    df = pd.DataFrame({"f1": f1, "f2": f2, "label": y})

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(3)(x)

    est = KerasEstimator(
        model=MLP(),
        loss="sparse_categorical_crossentropy",
        optimizer="adam",
        lr=5e-3,
        store=LocalStore(args.store),
        num_proc=args.num_proc,
        epochs=args.epochs,
        batch_size=32,
        validation=0.1,
        feature_cols=["f1", "f2"],
        label_cols=["label"],
    )
    model = est.fit(df)
    print("train loss per epoch:", [round(h, 4) for h in model.history])
    print("val loss per epoch:  ",
          [round(h, 4) for h in model.val_history])

    preds = model.predict(np.stack([f1, f2], axis=1)).argmax(axis=1)
    acc = float((preds == y).mean())
    print(f"train accuracy: {acc:.3f}")
    return 0 if acc > 0.8 else 1


if __name__ == "__main__":
    raise SystemExit(main())
