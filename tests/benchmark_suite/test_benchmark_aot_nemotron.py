"""The ``nemotron-twotower-30b-a3b.s8192.epshare`` cell's real train
step, compiled here for a described ``v5e:2x2`` chip: the forward
flash-attention kernel runs once, in the one attention layer (a recomputed
layer keeps what its forward kernel gave), and a backward one at least
once; every other Mosaic call is one of the program's own named kernels
(``hvd_*``) or the compiler's grouped-product kernels for the experts;
and the step — 10.7 GB of parameters, gradients and AdamW
moments plus the activations of 16,384 tokens with every layer
recomputed — fits the chip's memory.  A compile, not a chip run: it says
nothing about speed.

The topology is described inside a fixture (never while a module is
imported: only one process may load the TPU library) and the compile
runs in this process (on-chip-measurement guide, section 2).  It is a
file of its own beside ``test_benchmark_aot.py`` because a PR may add
benchmark files and not edit them; where the test run does not allow a
second process to load the TPU library, the fixture skips.
"""

import re

import pytest

from benchmark import experts, manifest, reduce

CELL = "nemotron-twotower-30b-a3b.s8192.epshare"
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
    """The cell's step, built as ``families/lm_hybrid_ssm.py`` builds
    it, from shapes instead of arrays."""
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
        assert cfg.attn_impl is None            # nothing forced
        mesh = make_mesh(**job["mesh"], devices=[one_chip])
        here = NamedSharding(mesh, P())

        def shapes(tree):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=here), tree)

        opt = optax.adamw(config["optimizer"]["learning_rate"])
        params = jax.eval_shape(
            lambda key: transformer.init_params(
                family._DeviceRandom(key), cfg), jax.random.PRNGKey(0))
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
    return [line.split(" = ")[0].strip().lstrip("%")
            for line in text.splitlines()
            if reduce.MOSAIC_TARGET in line and " = " in line]


def _plain(name: str) -> bool:
    """A kernel without a window or a selection (no ``_win``, ``_sel``)."""
    return not re.search(r"_(win|sel)(\.\d+)?$", name)


@pytest.mark.parametrize("kernel", KERNELS)
def test_each_flash_kernel_runs_once_in_the_attention_layer(compiled_step,
                                                            kernel):
    """One attention layer of nine, recomputed under a policy that keeps
    the forward kernel's ``out`` and ``lse``: the forward kernel is in
    the step exactly once, under ``hvd_attn``, each backward kernel at
    most as often, under the same scope, and the plain backward kernels,
    however the backward pass is split among them, at least once; what
    ``gqa_flash_roofline`` reads.  No kernel has a window or a
    selection."""
    text = compiled_step.as_text()

    def calls(name):
        return [c for c in _mosaic_calls(text)
                if re.fullmatch(re.escape(name) + r"(\.\d+)?", c)]

    assert len(calls("hvd_flash_fwd")) == 1, calls("hvd_flash_fwd")
    assert len(calls(kernel)) <= 1, (kernel, calls(kernel))
    for name in calls(kernel):
        line = next(line for line in text.splitlines()
                    if line.strip().startswith(f"%{name} = "))
        assert re.search(r'op_name="[^"]*hvd_attn', line), line[-300:]
    backward = [c for c in _mosaic_calls(text)
                if c.startswith("hvd_flash_bwd") and _plain(c)]
    assert len(backward) >= 1, backward
    assert all(_plain(c) for c in _mosaic_calls(text)
               if c.startswith("hvd_flash"))


def test_no_other_mosaic_call_of_the_programs(compiled_step):
    """Every Mosaic call is a kernel the program names (``hvd_*``) or
    one of the compiler's own grouped-product kernels for
    ``lax.ragged_dot``: up and down, forward, recomputed and twice that
    backward, in each of 4 expert layers: at most 32 (at most, and not
    exactly: a backward rule that needs fewer products must not fail
    here).  A Mosaic call with neither name fails.  The state-space
    layers (scopes ``hvd_ssm`` and ``hvd_ssm_scan``) are in the step."""
    text = compiled_step.as_text()
    others = [name for name in _mosaic_calls(text)
              if not name.startswith("hvd_")]
    assert others and all(name.startswith(experts.GROUPED)
                          for name in others), others
    grouped = [name for name in others
               if name.startswith(f"{experts.GROUPED}-none")]
    assert 0 < len(grouped) <= 4 * 2 * 4, len(grouped)
    for scope in ("hvd_ssm", "hvd_ssm_scan", "hvd_moe_experts",
                  "hvd_moe_shared", "hvd_loss_head", "hvd_optimizer"):
        assert scope in text, scope


def test_step_fits_the_chip(compiled_step):
    """Between a quarter and the whole of the 16 GB: static state 10.7
    GB (666,963,456 parameters x 16 bytes), the rest activations."""
    memory = compiled_step.memory_analysis()
    used = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 0.25 * 16e9 < used < HBM_BYTES, used
    # parameters and the two moments arrive as arguments; the gradients
    # are temporaries
    assert memory.argument_size_in_bytes > 3 * 4 * 666_963_456
