"""Family ``resnet_dp``: a ResNet trained data-parallel through the
product path — ``hvd.DistributedOptimizer(op=Average)`` stage 0 with its
in-graph ``psum`` over ``hvd.world_mesh()``.

The trainer is ``examples/jax_synthetic_benchmark.build_trainer`` with
the seed threaded through (that function takes none), the batches made
on the device, and the dropout key left out: a ResNet has no dropout.
The model comes from ``horovod_tpu.models.resnet`` at the sizes the
configuration file gives.

The plain reference reads the system's flax parameter tree and computes
the same loss in float32 with ``lax.conv_general_dilated`` and
``jax.numpy`` only: no flax module, no ``hvd``.

In a launched world only rank 0 runs one-chip programs (the reference
and the system's sub-batch gradient).  JAX writes its persistent cache
from process 0 alone, and a one-chip program's key holds its device, so
what ranks 1.. compile for their own chip is compiled again in every run
(PR 22: 150 s of each np4 run).  Weights and batches are therefore made
by programs over the whole mesh, whose key every rank shares, and rank 0
makes the first global batch again on its own chip (the generator gives
the same bits however the array is sharded) to compute every rank's
reference gradient.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import agreement

# Step-0 loss and gradient norm of the system (bf16 convolutions, f32
# parameters) against the float32 reference, relative.  A loss or a norm
# averages the rounding of many bf16 terms: on the chip the loss came
# within 5e-6 to 5e-5 and the norm within 6e-4 to 1.1e-3 (PR 22, ResNet-50
# on 32 images, three seeds); the bounds leave ten times that.  A dropped
# block or a missing batch-norm term moves the norm by tens of percent.
LOSS_RTOL = 1e-3
GRAD_NORM_RTOL = 1e-2
# First SGD update of the classifier bias against -lr * (mean over the
# ranks of the reference's gradient), relative in the 2-norm.  A sum in
# place of the average is off by the world size, and a rank left out by
# tens of percent (the ranks' labels differ).
UPDATE_RTOL = 2e-2


# ---------------------------------------------------------------------------
# Operations the architecture requires, from shapes
# ---------------------------------------------------------------------------


def forward_macs(config: dict) -> int:
    """Multiply-accumulates of one image's forward pass through the
    convolutions and the classifier.  Batch norm, ReLU, pooling and the
    loss are elementwise and left out, as is usual."""
    side = -(-config["image_side"] // 2)                 # 7x7, stride 2
    width = config["num_filters"]
    macs = side * side * 49 * config["image_channels"] * width
    side = -(-side // 2)                                 # 3x3 max pool
    channels = width
    bottleneck = config["block"] == "bottleneck"
    for stage, blocks in enumerate(config["stage_sizes"]):
        f = width * 2 ** stage
        out = 4 * f if bottleneck else f
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            after = -(-side // stride)
            if bottleneck:
                macs += side * side * channels * f       # 1x1
                macs += after * after * 9 * f * f        # 3x3, strided
                macs += after * after * f * out          # 1x1
            else:
                macs += after * after * 9 * channels * f
                macs += after * after * 9 * f * f
            if channels != out or stride != 1:           # projection
                macs += after * after * channels * out
            side, channels = after, out
    return macs + channels * config["num_classes"]


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Forward plus backward of one image: 3 x forward (the backward
    pass computes a gradient for the input and one for the weights of
    every layer), 2 FLOPs a multiply-accumulate."""
    return 3.0 * 2.0 * forward_macs(config)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


def _conv(x, kernel, stride: int, padding):
    from jax import lax

    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _batch_norm(x, p):
    """Training mode: the statistics of this batch, eps 1e-5."""
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def reference_logits(config: dict, params: dict, images):
    """The forward pass, float32, from the system's parameter tree."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    x = images.astype(jnp.float32)
    x = _conv(x, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    bottleneck = config["block"] == "bottleneck"
    name = "BottleneckBlock_{}" if bottleneck else "ResNetBlock_{}"
    index = 0
    for stage, blocks in enumerate(config["stage_sizes"]):
        for block in range(blocks):
            p = params[name.format(index)]
            index += 1
            stride = 2 if stage > 0 and block == 0 else 1
            strides = (1, stride, 1) if bottleneck else (stride, 1)
            y = x
            for i, s in enumerate(strides):
                y = _conv(y, p[f"Conv_{i}"]["kernel"], s, "SAME")
                y = _batch_norm(y, p[f"BatchNorm_{i}"])
                if i < len(strides) - 1:
                    y = jax.nn.relu(y)
            if "conv_proj" in p:
                x = _batch_norm(
                    _conv(x, p["conv_proj"]["kernel"], stride, "SAME"),
                    p["norm_proj"])
            x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    dense = params["Dense_0"]
    return jnp.dot(x, dense["kernel"],
                   precision=lax.Precision.HIGHEST) + dense["bias"]


def reference_loss(config: dict, params: dict, images, labels):
    """Mean softmax cross-entropy, float32."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(reference_logits(config, params, images))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def check_first_update(probes: list) -> dict:
    """Whether the first update of the classifier bias, as each rank
    saw it, is ``-lr`` times the mean of the reference's gradients on
    the ranks' batches.  ``probes`` holds one rank's probe each, rank 0's
    with the reference gradients; numpy only, so the parent of a
    launched world can call it."""
    want = -probes[0]["lr"] * np.mean(
        np.asarray(probes[0]["reference_gradients"], np.float64), axis=0)
    errors = [float(np.linalg.norm(np.asarray(p["update"]) - want)
                    / np.linalg.norm(want)) for p in probes]
    return {"ok": bool(max(errors) < UPDATE_RTOL
                       and len(probes[0]["reference_gradients"])
                       == len(probes)),
            "bias_update_rel_err": max(errors), "tolerance": UPDATE_RTOL,
            "ranks": len(probes)}


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


class Trainer:
    """Builds the product trainer; ``hvd.init()`` has returned."""

    def __init__(self, config: dict, job: dict, seed: int, hvd):
        import jax
        import jax.numpy as jnp
        import optax
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        from horovod_tpu.models import resnet

        self.config, self.job = config, job
        self.rank, self.world = hvd.rank(), hvd.size()
        if self.world != job["world"]:
            raise RuntimeError(f"the cell is a world of {job['world']}, "
                               f"hvd.size() is {self.world}")
        side, classes = config["image_side"], config["num_classes"]
        channels = config["image_channels"]
        block = {"bottleneck": resnet.BottleneckBlock,
                 "basic": resnet.ResNetBlock}[config["block"]]
        model = resnet.ResNet(
            stage_sizes=config["stage_sizes"], block_cls=block,
            num_classes=classes, num_filters=config["num_filters"],
            dtype=jnp.dtype(config["compute_dtype"]))
        self.model = model
        mesh = hvd.world_mesh()
        # the key is an argument: as a constant it would make a new
        # program, and a compilation, of every seed
        variables = jax.jit(
            lambda key: model.init(
                key, jnp.zeros((1, side, side, channels), jnp.float32),
                train=True),
            out_shardings=NamedSharding(mesh, P()))(jax.random.PRNGKey(seed))
        params, batch_stats = variables["params"], variables["batch_stats"]

        lr = config["optimizer"]["learning_rate"]
        opt = hvd.DistributedOptimizer(optax.sgd(lr), op=hvd.Average,
                                       axis_name="hvd",
                                       compression=hvd.Compression.none)

        def per_device(params, batch_stats, opt_state, images, labels):
            def loss_fn(p):
                logits, mutated = model.apply(
                    {"params": p, "batch_stats": batch_stats}, images,
                    train=True, mutable=["batch_stats"])
                loss = optax.softmax_cross_entropy(
                    logits, jax.nn.one_hot(labels, classes)).mean()
                return loss, mutated["batch_stats"]

            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_stats,
                    opt_state, loss.reshape(1))

        self.state = (params, batch_stats, opt.init(params))
        rep = jax.tree_util.tree_map(lambda _: P(), self.state)
        self._step = jax.jit(shard_map(
            per_device, mesh=mesh, check_vma=False,
            in_specs=(*rep, P("hvd"), P("hvd")), out_specs=(*rep, P())))

        # a pool of synthetic batches, each from a key of its own, made
        # on the devices in one call
        self.rows = job["batch_per_chip"]

        def make_batch(key):
            k_img, k_lab = jax.random.split(key)
            shape = (self.rows * self.world, side, side, channels)
            return (jax.random.uniform(k_img, shape, jnp.float32),
                    jax.random.randint(k_lab, shape[:1], 0, classes,
                                       jnp.int32))

        self._make_batch = make_batch
        self._keys = jax.random.split(jax.random.PRNGKey(seed + 1),
                                      job["batch_pool"])
        self.batches = jax.jit(
            lambda keys: tuple(make_batch(key) for key in keys),
            out_shardings=NamedSharding(mesh, P("hvd")))(self._keys)
        self.samples_per_step = self.rows * self.world
        self.units_per_sample = 1
        self.compiled = None
        self._bias_before = np.asarray(params["Dense_0"]["bias"])

    def compile(self) -> None:
        self.compiled = self._step.lower(
            *self.state, *self.batches[0]).compile()

    def compiled_text(self) -> str:
        return self.compiled.as_text()

    def run_step(self, i: int):
        """Dispatch step ``i``; returns its loss, still on the device."""
        *state, loss = self.compiled(
            *self.state, *self.batches[i % len(self.batches)])
        self.state = tuple(state)
        return loss

    def params(self):
        return self.state[0]

    def _on_this_chip(self, tree):
        """This process's copy of replicated arrays, as arrays of its own
        chip: a program over them then runs here alone."""
        import jax

        return jax.tree_util.tree_map(lambda a: a.addressable_data(0), tree)

    def _first_global_batch(self):
        """The whole first batch on this process's chip: the one it
        holds in a world of one, made again from its key otherwise."""
        import jax

        if self.world == 1:
            return self.batches[0]
        return jax.jit(self._make_batch)(self._on_this_chip(self._keys)[0])

    def check_reference(self) -> dict:
        """Step-0 loss and global gradient norm on the first
        ``reference_samples`` images: the system's model in its compute
        type against the float32 reference.  Rank 0's work."""
        import jax
        import optax

        if self.rank != 0:
            return {"ok": True, "checked_by": "rank 0"}
        n = self.job["reference_samples"]
        params, batch_stats, _ = self._on_this_chip(self.state)
        images, labels = (a[:n] for a in self._first_global_batch())
        classes = self.config["num_classes"]

        def system_loss(p, batch_stats, images, labels):
            logits, _ = self.model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            return optax.softmax_cross_entropy(
                logits, jax.nn.one_hot(labels, classes)).mean()

        loss, grads = jax.jit(jax.value_and_grad(system_loss))(
            params, batch_stats, images, labels)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, self.config)))(params, images, labels)
        return agreement.against_reference(
            loss, grads, ref_loss, ref_grads, LOSS_RTOL, GRAD_NORM_RTOL)

    def first_update_probe(self) -> dict:
        """Before step 0, on rank 0: the reference's gradient for the
        classifier bias on each rank's whole first batch (a forward pass:
        the bias sees only the logits).  ``observe_first_update`` adds
        what the step did, on every rank."""
        import jax

        probe = {"lr": self.config["optimizer"]["learning_rate"]}
        if self.rank != 0:
            return probe
        params = self._on_this_chip(self.state[0])
        images, labels = self._first_global_batch()

        def loss_of_bias(bias, params, images, labels):
            dense = dict(params["Dense_0"], bias=bias)
            return reference_loss(self.config,
                                  dict(params, Dense_0=dense), images,
                                  labels)

        grad = jax.jit(jax.grad(loss_of_bias))
        probe["reference_gradients"] = [
            np.asarray(grad(params["Dense_0"]["bias"], params,
                            images[r * self.rows:(r + 1) * self.rows],
                            labels[r * self.rows:(r + 1) * self.rows])
                       ).tolist() for r in range(self.world)]
        return probe

    def observe_first_update(self, probe: dict) -> dict:
        after = np.asarray(
            self.state[0]["Dense_0"]["bias"].addressable_data(0))
        probe["update"] = (after - self._bias_before).tolist()
        return probe
