"""Synthetic-data throughput benchmark — the analog of reference
``examples/tensorflow2_synthetic_benchmark.py`` (its headline benchmark
workload): ResNet-50 forward+backward+update on random ImageNet-shaped
batches, reporting img/sec per device (mean ± 1.96σ) and aggregate.

Run::

    python -m horovod_tpu.run -np 8 python examples/jax_synthetic_benchmark.py
    python examples/jax_synthetic_benchmark.py --model ResNet50 --batch-size 64

The train step is the framework's compiled data-parallel path: a
shard_map over the world mesh with the DistributedOptimizer's traced
psum.
"""

try:
    import horovod_tpu  # noqa: F401
except ImportError:  # running from a source checkout
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import argparse
import time

import numpy as np


def build_trainer(hvd, model_name: str = "ResNet50", batch_size: int = 64,
                  fp16_allreduce: bool = False,
                  image_side: int | None = None):
    """The synthetic data-parallel trainer over ``hvd.world_mesh()``.

    Returns ``(step, state, batch)``: ``step(*state, *batch, step_idx)``
    is the jitted train step returning ``(*state, loss[1])``, ``state``
    is ``(params, batch_stats, opt_state)`` and ``batch`` the fixed
    synthetic ``(images, labels)`` sharded over the ``hvd`` axis.
    ``chip_smoke.py`` drives the same function."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import inception, mnist, resnet, vgg

    registry = {
        "ResNet18": resnet.ResNet18, "ResNet34": resnet.ResNet34,
        "ResNet50": resnet.ResNet50, "ResNet101": resnet.ResNet101,
        "ResNet152": resnet.ResNet152,
        "VGG11": vgg.VGG11, "VGG13": vgg.VGG13, "VGG16": vgg.VGG16,
        "VGG19": vgg.VGG19,
        "InceptionV3": inception.InceptionV3,
        # CPU-smoke stand-in, like the reference tf2 bench's SmallCNN
        "SmallCNN": mnist.SmallCNN,
    }
    if model_name not in registry:
        raise SystemExit(f"unknown model {model_name}; choose from "
                         f"{sorted(registry)}")
    n = hvd.size()
    model_cls = registry[model_name]
    model = model_cls(num_classes=1000, dtype=jnp.bfloat16)
    side = image_side or {"InceptionV3": 299,
                          "SmallCNN": 96}.get(model_name, 224)

    rngs = {"params": jax.random.PRNGKey(0),
            "dropout": jax.random.PRNGKey(1)}
    variables = model.init(rngs, jnp.zeros((1, side, side, 3),
                                           jnp.float32), train=True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    has_bn = "batch_stats" in variables

    compression = (hvd.Compression.fp16 if fp16_allreduce
                   else hvd.Compression.none)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01), op=hvd.Average,
                                   axis_name="hvd",
                                   compression=compression)
    opt_state = opt.init(params)
    mesh = hvd.world_mesh()

    def per_device(params, batch_stats, opt_state, images, labels,
                   step_idx):
        # per-step dropout mask: fold the iteration counter into the
        # key so RNG work isn't constant-folded out of the timing
        droprng = jax.random.fold_in(jax.random.PRNGKey(2), step_idx)

        def loss_fn(p):
            v = {"params": p}
            if has_bn:
                v["batch_stats"] = batch_stats
            logits, mutated = model.apply(
                v, images, train=True,
                mutable=["batch_stats"] if has_bn else [],
                rngs={"dropout": droprng})
            loss = optax.softmax_cross_entropy(
                logits, jax.nn.one_hot(labels, 1000)).mean()
            return loss, mutated.get("batch_stats", batch_stats)

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_stats,
                opt_state, loss.reshape(1))

    rep = jax.tree_util.tree_map(lambda _: P(),
                                 (params, batch_stats, opt_state))
    step = jax.jit(shard_map(per_device, mesh=mesh, check_vma=False,
                             in_specs=(*rep, P("hvd"), P("hvd"), P()),
                             out_specs=(*rep, P())))

    shape = (batch_size * n, side, side, 3)
    rng_np = np.random.RandomState(0)
    data_sh = NamedSharding(mesh, P("hvd"))
    images = jax.device_put(jnp.asarray(rng_np.rand(*shape), jnp.float32),
                            data_sh)
    labels = jax.device_put(
        jnp.asarray(rng_np.randint(0, 1000, shape[0]), jnp.int32), data_sh)
    return step, (params, batch_stats, opt_state), (images, labels)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="ResNet50")
    p.add_argument("--batch-size", type=int, default=64,
                   help="per-device batch")
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-warmup-batches", type=int, default=3)
    p.add_argument("--fp16-allreduce", action="store_true")
    args = p.parse_args()

    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    step, (params, batch_stats, opt_state), (images, labels) = \
        build_trainer(hvd, args.model, args.batch_size,
                      args.fp16_allreduce)
    n_images = images.shape[0]

    def log(msg):
        if hvd.rank() == 0:
            print(msg, flush=True)

    log(f"Model: {args.model}")
    log(f"Batch size: {args.batch_size} per device, {n} device(s)")

    step_no = 0
    for _ in range(args.num_warmup_batches):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels,
            jnp.int32(step_no))
        step_no += 1
    float(np.asarray(loss)[0])  # host sync = real completion barrier

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, images, labels,
                jnp.int32(step_no))
            step_no += 1
        float(np.asarray(loss)[0])
        dt = time.perf_counter() - t0
        rate = n_images * args.num_batches_per_iter / dt / n
        log(f"Iter #{i}: {rate:.1f} img/sec per device")
        img_secs.append(rate)

    mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
    log(f"Img/sec per device: {mean:.1f} +-{conf:.1f}")
    log(f"Total img/sec on {n} device(s): "
        f"{mean * n:.1f} +-{conf * n:.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
