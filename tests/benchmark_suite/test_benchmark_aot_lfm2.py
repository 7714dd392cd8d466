"""The ``lfm2-24b-a2b.s8192.epshare`` cell's real train step, compiled here
for a described ``v5e:2x2`` chip: the plain forward flash-attention kernel
runs once, in the one attention sub-layer (a recomputed sub-layer keeps
what its forward kernel gave, so the replay holds no ``hvd_flash_fwd``),
and a plain backward one at least once; the
in-projection of each of the four gated short-convolution sub-layers is
computed forward, once more in the replay and transposed for its weight's
gradient, under ``hvd_conv``; the gates and the taps under
``hvd_conv_mix`` in every pass; every other Mosaic call is one of the
program's own named kernels (``hvd_*``) or the compiler's grouped-product
kernels for the experts; and the step — 7.78 GB of parameters, gradients and AdamW
moments plus the activations of 16,384 tokens with every sub-layer
recomputed — fits the chip's memory.  A compile, not a chip run: it says
nothing about speed.

The topology is described inside a fixture (never while a module is
imported: only one process may load the TPU library) and the compile runs
in this process, with no chip attached.  Where the test run does not allow
a second process to load the TPU library, the fixture skips.
"""

import re

import pytest

from benchmark import experts, manifest, reduce

CELL = "lfm2-24b-a2b.s8192.epshare"
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
PARAMETERS = 486_062_208
CONV_LAYERS, EXPERT_LAYERS = 4, 4


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
    """The cell's step, built as ``families/lm_conv_moe.py`` builds it,
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
            if reduce.MOSAIC_TARGET in line and " = " in line]


def _calls_named(text: str, kernel: str) -> list:
    """The Mosaic calls whose instruction is named ``kernel`` (``.<n>``
    apart), each with the line that holds it."""
    found = []
    for name in _mosaic_calls(text):
        if re.fullmatch(re.escape(kernel) + r"(\.\d+)?", name):
            line = next(line for line in text.splitlines()
                        if f"%{name} = " in line)
            found.append((name, line))
    return found


def _products(text: str, shape: str) -> list:
    """The matrix products whose result is ``shape`` (``bf16[...]``,
    layout apart), each with its ``op_name``."""
    pattern = re.compile(re.escape(shape) + r"\{[^}]*\} (convolution|dot)\(")
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines() if pattern.search(line)]


@pytest.mark.parametrize("kernel", KERNELS)
def test_plain_kernels_once_in_the_one_attention_layer(compiled_step,
                                                        kernel):
    """One attention sub-layer of ten, recomputed under a policy that
    keeps the forward kernel's ``out`` and ``lse``: the plain forward
    kernel is in the step exactly once, under ``hvd_rattn`` and
    ``hvd_attn`` — what ``gqa_flash_roofline`` reads — each backward
    kernel at most as often, under the same scopes, and the plain
    backward kernels, however the backward pass is split among them, at
    least once; no windowed or selection kernel is."""
    text = compiled_step.as_text()
    assert len(_calls_named(text, "hvd_flash_fwd")) == 1
    plain = _calls_named(text, kernel)
    assert len(plain) <= 1, (kernel, [name for name, _ in plain])
    for _, line in plain:
        assert re.search(r'op_name="[^"]*hvd_rattn[^"]*hvd_attn', line), \
            line[-300:]
    backward = [name for name in _mosaic_calls(text)
                if name.startswith("hvd_flash_bwd")]
    assert backward, _mosaic_calls(text)
    assert not any(re.search(r"_(win|sel)(\.\d+)?$", name)
                   for name in _mosaic_calls(text))


def test_the_in_projection_forward_recomputed_and_transposed(compiled_step):
    """Each convolution sub-layer's in-projection (16,384 tokens x 2,048
    -> 6,144 in bf16) runs forward and once more in the replay — a
    recomputed sub-layer keeps only its input — and its weight's
    gradient (2,048 x 6,144) once: 8 and 4 products, all under
    ``hvd_conv``."""
    text = compiled_step.as_text()
    projections = _products(text, "bf16[2,8192,6144]")
    assert len(projections) == 2 * CONV_LAYERS, projections
    assert sum("rematted_computation" in name
               for name in projections) == CONV_LAYERS, projections
    weight_grads = _products(text, "bf16[2048,6144,1]")
    assert len(weight_grads) == CONV_LAYERS, weight_grads
    for name in projections + weight_grads:
        assert "hvd_conv" in name and "hvd_conv_mix" not in name, name


def test_the_gates_and_taps_under_their_scope_in_every_pass(compiled_step):
    """``hvd_conv_mix`` holds fusions of the forward pass, of the replay
    and of the backward pass."""
    text = compiled_step.as_text()
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if " fusion(" in line and "hvd_conv_mix" in line]
    forward = [n for n in names if "transpose(" not in n]
    replay = [n for n in names if "rematted_computation" in n]
    backward = [n for n in names if "transpose(" in n
                and "rematted_computation" not in n]
    assert len(forward) >= CONV_LAYERS and len(replay) >= CONV_LAYERS \
        and len(backward) >= CONV_LAYERS, names


def test_no_other_mosaic_call_of_the_programs(compiled_step):
    """Every Mosaic call is a kernel the program names (``hvd_*``) or
    one of the compiler's own grouped-product kernels for
    ``lax.ragged_dot``: gate, up and down, forward and twice that
    backward, and a replay only where something reads its result
    (nothing does here: no norm after a sub-layer), in each of 4 expert
    sub-layers: at most 4 x 3 x 4 (at most, and not exactly: a backward
    rule that needs fewer products must not fail here).  A Mosaic call
    with neither name fails."""
    text = compiled_step.as_text()
    others = [name for name in _mosaic_calls(text)
              if not name.startswith("hvd_")]
    assert others and all(name.startswith(experts.GROUPED)
                          for name in others), others
    grouped = [name for name in others
               if name.startswith(f"{experts.GROUPED}-none")]
    assert 0 < len(grouped) <= EXPERT_LAYERS * 3 * 4, len(grouped)
    for scope in ("hvd_conv", "hvd_conv_mix", "hvd_rattn", "hvd_attn",
                  "hvd_moe_route", "hvd_moe_experts", "hvd_loss_head",
                  "hvd_optimizer"):
        assert scope in text, scope
    assert "hvd_moe_shared" not in text         # no shared expert


def test_step_fits_the_chip(compiled_step):
    """Between a quarter and the whole of the 16 GB: static state 7.78
    GB (486,062,208 parameters x 16 bytes), the rest activations."""
    memory = compiled_step.memory_analysis()
    used = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 0.25 * 16e9 < used < 15.75e9, used
    # parameters and the two moments arrive as arguments; the gradients
    # are temporaries
    assert memory.argument_size_in_bytes > 3 * 4 * PARAMETERS
