"""The two ``gpt2-124m`` cells' real train steps, compiled here for a
described ``v5e:2x2`` chip: which attention path the compiled step holds
and whether it fits the chip's memory — so a later PR that breaks a
cell's shape or memory is caught with no chip time.  A compile, not a
chip run: it says nothing about speed.

The topology is described inside a fixture (never while a module is
imported: only one process may load the TPU library), the compile runs
in this process, and all of it stays in this one file
(on-chip-measurement guide, section 2).
"""

import pytest

from benchmark import manifest

HBM_BYTES = 15.75e9          # what the compiler gives a v5e program
MOSAIC_CALL = "tpu_custom_call"


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
def compiled_steps(one_chip):
    """``{cell name: compiled step}``, built as ``families/lm_mesh.py``
    builds it, from shapes instead of arrays."""
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
    steps = {}
    try:
        for name in ("gpt2-124m.s1024", "gpt2-124m.s8192"):
            cell = manifest.load_cell(name)
            family = manifest.load_family(cell)
            config, job = cell.config, cell.job
            cfg = transformer.TransformerConfig(
                max_seq=max(config["n_positions"], job["seq"]),
                dtype=config["compute_dtype"], **family._sizes(config))
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
            steps[name] = transformer.make_train_step(cfg, mesh, opt).lower(
                shapes(params), shapes(jax.eval_shape(opt.init, params)),
                ids, ids).compile()
    finally:
        patch.undo()
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return steps


@pytest.mark.parametrize("name, kernel", [("gpt2-124m.s1024", False),
                                          ("gpt2-124m.s8192", True)])
def test_attention_path_of_the_compiled_step(compiled_steps, name, kernel):
    """seq 8192 holds the Pallas kernels, seq 1024 does not: what the
    cells' ``why`` says, and what ``attn_kernel_share`` relies on."""
    assert (MOSAIC_CALL in compiled_steps[name].as_text()) is kernel


@pytest.mark.parametrize("name", ["gpt2-124m.s1024", "gpt2-124m.s8192"])
def test_step_fits_the_chip(compiled_steps, name):
    memory = compiled_steps[name].memory_analysis()
    used = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 0.25 * 16e9 < used < HBM_BYTES, used
