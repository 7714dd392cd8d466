"""The ``keye-vl-2.0-30b-a3b.s16384.epshare`` cell's real train step,
compiled here for a described ``v5e:2x2`` chip: the three flash-attention
kernels under a selection (``hvd_flash_*_sel``) run once in each of the
six indexed attention sub-layers (a recomputed sub-layer keeps what its
forward kernel gave) and the selection is made once a layer — one loop
over blocks of query rows under ``hvd_dsa_index``, in the forward pass
alone, because a recomputed sub-layer keeps its selection; everything
else is XLA's — no other Mosaic call of the program's, only the
compiler's own grouped-product kernels for the experts, at most three
products forward and twice that backward in each of six expert
sub-layers (no norm after a sub-layer reads its result, so the replay
runs none: ``trinity-mini``'s rule); and the step — 6.92 GB of
parameters, gradients and AdamW moments plus the activations of 16,384
tokens with every sub-layer recomputed — fits the chip's memory with
room to spare: no (T, T) float32 tensor is alive across blocks of rows.
A compile, not a chip run: it says nothing about speed.

The topology is described inside a fixture (never while a module is
imported: only one process may load the TPU library) and the compile
runs in this process (on-chip-measurement guide, section 2).  It is a
file of its own because a PR may add benchmark files and not edit them;
where the test run does not allow a second process to load the TPU
library, the fixture skips.
"""

import re

import pytest

from benchmark import experts, manifest, reduce

CELL = "keye-vl-2.0-30b-a3b.s16384.epshare"
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
PARAMETERS = 432_696_832
LAYERS = 6


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
    """The cell's step, built as ``families/lm_dsa_moe.py`` builds it,
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


@pytest.mark.parametrize("kernel", KERNELS)
def test_selection_kernels_once_a_layer_and_no_plain_or_windowed_one(
        compiled_step, kernel):
    """Six indexed attention sub-layers of twelve sub-layers, each
    recomputed under a policy that keeps the forward kernel's ``out``
    and ``lse``: each ``*_sel`` kernel is in the step exactly six times,
    under ``hvd_dsa`` and ``hvd_attn``; no attention call of this cell
    runs without a selection."""
    text = compiled_step.as_text()
    selected = _calls_named(text, kernel + "_sel")
    assert len(selected) == LAYERS, (kernel, [name for name, _ in selected])
    for _, line in selected:
        assert re.search(r'op_name="[^"]*hvd_dsa[^"]*hvd_attn', line), \
            line[-300:]
    assert not _calls_named(text, kernel)
    assert not _calls_named(text, kernel + "_win")


def test_one_selection_a_layer(compiled_step):
    """The selection is one loop over blocks of query rows under
    ``hvd_dsa_index`` (inside it the scores' product, the search for the
    rows' thresholds, the packing): the scores' product is in the step
    six times, once a layer, and every operation under that scope in the
    forward pass
    (``jvp(``, never ``transpose(``: no gradient passes the selection)
    and none in a replay (``rematted_computation``): a recomputed layer
    keeps its selection, and the backward kernels read that one."""
    text = compiled_step.as_text()
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if "hvd_dsa_index" in line and 'op_name="' in line]
    assert names
    for name in names:
        assert "jvp(hvd_dsa)/hvd_dsa_index" in name, name
        assert "transpose(" not in name and "rematted" not in name, name
    products = [line for line in text.splitlines()
                if re.search(r" (convolution|dot)\(", line)
                and "bqjd,bkd->bqjk" in line]
    assert len(products) == LAYERS, [line[:120] for line in products]


def test_no_other_mosaic_call_of_the_programs(compiled_step):
    """Everything else is XLA's.  The compiler's own grouped-product
    kernels for ``lax.ragged_dot`` are custom calls too: gate, up and
    down, forward and twice that backward, and a replay only where
    something reads its result (nothing does here: no norm after a
    sub-layer), in each of 6 expert sub-layers: at most 6 x 3 x 4 (at
    most, and not exactly: a backward rule that needs fewer products
    must not fail here; PERF.md section 7)."""
    text = compiled_step.as_text()
    others = [name for name in _mosaic_calls(text)
              if not name.startswith(KERNELS)]
    assert others and all(name.startswith(experts.GROUPED)
                          for name in others), others
    grouped = [name for name in others
               if name.startswith(f"{experts.GROUPED}-none")]
    assert 0 < len(grouped) <= LAYERS * 3 * 4, len(grouped)
    for scope in ("hvd_dsa", "hvd_dsa_index", "hvd_attn", "hvd_moe_route",
                  "hvd_moe_experts", "hvd_loss_head", "hvd_optimizer"):
        assert scope in text, scope
    assert "hvd_moe_shared" not in text         # no shared expert


def test_step_fits_the_chip(compiled_step):
    """Between a quarter and 14 GB of the 16: static state 6.92 GB
    (432,696,832 parameters x 16 bytes), the rest activations; a (T, T)
    float32 tensor (1.07 GB) alive across the blocks of one layer's
    selection, let alone one a layer, would not leave that room."""
    memory = compiled_step.memory_analysis()
    used = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 0.25 * 16e9 < used < 14e9, used
    # parameters and the two moments arrive as arguments; the gradients
    # are temporaries
    assert memory.argument_size_in_bytes > 3 * 4 * PARAMETERS
