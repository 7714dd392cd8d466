"""The ``joyai-llm-flash.s8192.epshare`` cell's real train step, compiled
here for a described ``v5e:2x2`` chip: the forward flash-attention kernel
at the latent attention's head sizes (192 for q/k, 128 for v) is in it
under its name, and backward kernels at least as often, and the step — 10.9 GB of parameters, gradients and
AdamW moments plus the activations of 16,384 tokens with every block
recomputed — fits the chip's memory.  A compile, not a chip run: it says
nothing about speed.

The topology is described inside a fixture (never while a module is
imported: only one process may load the TPU library) and the compile
runs in this process (on-chip-measurement guide, section 2).  It is a
file of its own beside ``test_benchmark_aot.py`` because a PR may add
benchmark files and not edit them; where the test run does not allow a
second process to load the TPU library, the fixture skips.
"""

import pytest

from benchmark import experts, manifest

CELL = "joyai-llm-flash.s8192.epshare"
HBM_BYTES = 15.75e9          # what the compiler gives a v5e program
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture(scope="module")
def compiled_step(one_chip):
    """The cell's step, built as ``families/lm_moe_mla.py`` builds it,
    from shapes instead of arrays."""
    import jax
    import optax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import transformer
    from horovod_tpu.parallel.mesh import make_mesh

    # a program compiled for a described chip cannot be read back from
    # the persistent cache that tests/conftest.py turns on
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the program asks jax.default_backend() which attention path and
    # whether to interpret its kernels; here that is the CPU, and the
    # step is compiled for the chip
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        cell = manifest.load_cell(CELL)
        family = manifest.load_family(cell)
        config, job = cell.config, cell.job
        cfg = transformer.TransformerConfig(**family._kwargs(config, job))
        mesh = make_mesh(**job["mesh"], devices=[one_chip])
        here = NamedSharding(mesh, P())

        def shapes(tree):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=here), tree)

        opt = optax.adamw(config["optimizer"]["learning_rate"])
        params = jax.eval_shape(
            lambda key: transformer.init_params(
                family._DeviceRandn(key), cfg), jax.random.PRNGKey(0))
        ids = jax.ShapeDtypeStruct(
            (job["batch_per_chip"], job["seq"]), "int32", sharding=here)
        return transformer.make_train_step(cfg, mesh, opt).lower(
            shapes(params), shapes(jax.eval_shape(opt.init, params)),
            ids, ids).compile()
    finally:
        patch.undo()
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _mosaic_calls(text: str) -> list:
    """The names of the instructions that are Mosaic calls."""
    return [line.split(" = ")[0].strip().lstrip("%").replace("ROOT %", "")
            for line in text.splitlines()
            if "tpu_custom_call" in line and " = " in line]


@pytest.mark.parametrize("kernel", KERNELS)
def test_the_three_mosaic_calls_are_in_the_step(compiled_step, kernel):
    """The forward kernel by its own name, as a Mosaic call: what
    ``mla_flash_roofline`` reads; each backward kernel at most as often,
    and the backward kernels (``hvd_flash_bwd*``), however the backward
    pass is split among them, at least as often.  The compiler's own
    grouped-product kernels for ``lax.ragged_dot`` are custom calls too,
    under a name and an ``op_name`` of the compiler's and no scope of the
    program's: ``benchmark/experts.py`` tells them by that name."""
    text = compiled_step.as_text()
    calls = _mosaic_calls(text)

    def named(kernel):
        return [name for name in calls if name.startswith(f"{kernel}.")]

    forward = named("hvd_flash_fwd")
    assert forward
    assert len(named(kernel)) <= len(forward), named(kernel)
    assert len([name for name in calls
                if name.startswith("hvd_flash_bwd")]) >= len(forward)
    assert "hvd_moe_experts" in text and "hvd_mtp" in text
    grouped = [line for line in text.splitlines()
               if line.lstrip().startswith(f"%{experts.GROUPED}-none")]
    # gate, up, down: forward, recomputed, and twice that backward, in
    # each of 5 expert layers, at most (a backward rule that needs fewer
    # products must not fail here)
    assert 0 < len(grouped) <= 5 * 3 * 4
    assert not any("hvd_" in line.split("metadata=")[1][:80]
                   for line in grouped)


def test_step_fits_the_chip(compiled_step):
    """Between a quarter and the whole of the 16 GB: static state 10.9
    GB (680,441,088 parameters x 16 bytes), the rest activations."""
    memory = compiled_step.memory_analysis()
    used = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 0.25 * 16e9 < used < HBM_BYTES, used
    # parameters and the two moments arrive as arguments; the gradients
    # are temporaries
    assert memory.argument_size_in_bytes > 3 * 4 * 680_441_088
