"""What stands around the attention kernels in ``gpt2-124m.s8192``'s real
train step, compiled here for a described ``v5e:2x2`` chip: a ring of one
rotates nothing and hands no softmax state through HBM, so the step holds
the three Mosaic calls a layer, no ``collective-permute`` under
``hvd_attn``, and fewer bytes moved by everything else under that scope.
And how often each kernel stands in ``joyai-llm-flash.s8192.epshare``'s
step, whose blocks are recomputed: once a block, the forward kernel too,
because a recomputed block keeps what that kernel gave.  And the grids
the kernels walk at ``trinity-mini.s16384.epshare``'s attention shape:
under the window a band of tiles a row, without one every tile pair.
A compile, not a chip run: it counts bytes and says nothing about time.

The topology is described inside a fixture (never while a module is
imported: only one process may load the TPU library) and the compile
runs in this process (on-chip-measurement guide, section 2).
``tests/benchmark_suite/`` has two files that describe a topology
already and a PR may not edit them, so this is a third: it runs only
where the test run lets several processes load the TPU library, as the
driver's does.
"""

import functools
import os
import re

import pytest

CELL = "gpt2-124m.s8192"
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
# blocks whose attention goes through the kernels: 12 layers; layer 0,
# four expert layers and the MTP module, each recomputed in the backward
# pass (a policy-free checkpoint ran the forward kernel 12 times there)
BLOCKS = {CELL: 12, "joyai-llm-flash.s8192.epshare": 6}
# 16.15 GB at PR 28 (24 self-permutes of dK and dV, the softmax state
# through HBM, f32 gradients and their converts); 12.40 GB at PR 29
BYTES_AROUND_KERNELS = 13e9

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_ITEMSIZE) + r")\[([\d,]*)\]")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_NO_TRAFFIC = ("bitcast", "tuple", "get-tuple-element", "parameter",
               "constant")


def _array_bytes(type_text):
    total = 0
    for dtype, dims in _ARRAY.findall(type_text):
        n = _ITEMSIZE[dtype]
        for dim in filter(None, dims.split(",")):
            n *= int(dim)
        total += n
    return total


def around_kernels(hlo_text, scope="hvd_attn"):
    """``(bytes, permutes, rows)`` of a compiled module's text: operand
    + result bytes of every instruction outside a fused computation
    whose ``op_name`` holds ``scope`` and that is not a Mosaic call
    (bitcasts, tuples and ``*-done`` move nothing and are skipped), how
    many of them start a ``collective-permute``, and one ``(bytes,
    opcode, result type, op_name)`` row each."""
    fused = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", hlo_text))
    total, permutes, rows = 0, 0, []
    computation, sizes = None, {}
    for line in hlo_text.splitlines():
        if line.endswith("{") and "(" in line and " = " not in line:
            head = line.split()
            computation = head[1 if head[0] == "ENTRY" else 0].lstrip("%")
            sizes = {}
            continue
        match = _INSTRUCTION.match(line)
        if not match or computation in fused:
            continue
        name, result, opcode, rest = match.groups()
        sizes[name] = _array_bytes(result)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not op_name or scope not in op_name.group(1):
            continue
        permutes += opcode == "collective-permute-start"
        if (opcode in _NO_TRAFFIC or opcode.endswith("-done")
                or "tpu_custom_call" in line):
            continue
        read = sum(sizes.get(operand, 0) for operand in
                   re.findall(r"%([\w.\-]+)", rest.split("), ")[0]))
        # an asynchronous start's result tuple names its operand again
        moved = sizes[name] if opcode.endswith("-start") else sizes[name] + read
        total += moved
        rows.append((moved, opcode, result.split("{")[0], op_name.group(1)))
    return total, permutes, rows


def test_the_reckoning_on_a_module_written_by_hand():
    text = """
%fused_computation.1 (p: f32[4,8]) -> f32[4,8] {
  %p = f32[4,8]{1,0} parameter(0)
  ROOT %n = f32[4,8]{1,0} negate(%p), metadata={op_name="jit(f)/hvd_attn/neg"}
}

ENTRY %main (a: bf16[4,8]) -> f32[4,8] {
  %a = bf16[4,8]{1,0} parameter(0), metadata={op_name="a"}
  %c = f32[4,8]{1,0} convert(%a), metadata={op_name="jit(f)/hvd_attn/convert"}
  %b = f32[32]{0} bitcast(%c), metadata={op_name="jit(f)/hvd_attn/reshape"}
  %k = (f32[4,8]{1,0}, f32[4,128]{1,0}) custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/hvd_attn/hvd_flash_fwd/pallas_call"}
  %s = (f32[4,8]{1,0}, f32[4,8]{1,0}, u32[]) collective-permute-start(%c), source_target_pairs={{0,0}}, metadata={op_name="jit(f)/hvd_attn/ppermute"}
  %d = f32[4,8]{1,0} collective-permute-done(%s), metadata={op_name="jit(f)/hvd_attn/ppermute"}
  %o = f32[4,8]{1,0} add(%d, %c), metadata={op_name="jit(f)/other/add"}
  ROOT %f = f32[4,8]{1,0} fusion(%d), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/hvd_attn/neg"}
}
"""
    total, permutes, rows = around_kernels(text)
    assert permutes == 1
    # the convert 64 + 128, the permute's own copy 128 + 128 + 4, the
    # fusion 128 + 128; not the kernel, the bitcast, the add
    assert [row[:2] for row in rows] == [
        (192, "convert"), (260, "collective-permute-start"),
        (256, "fusion")]
    assert total == 708


@pytest.fixture(scope="module")
def one_chip():
    if os.environ.get("ALLOW_MULTIPLE_LIBTPU_LOAD") != "1":
        pytest.skip("a third file that loads the TPU library: only where "
                    "ALLOW_MULTIPLE_LIBTPU_LOAD=1 lets several workers")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def compiled_step(cell_name, one_chip):
    """A cell's compiled step, built as the two files of
    ``tests/benchmark_suite/`` build theirs."""
    import jax
    import optax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import manifest
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
        cell = manifest.load_cell(cell_name)
        family = manifest.load_family(cell)
        config, job = cell.config, cell.job
        if hasattr(family, "_kwargs"):
            cfg = transformer.TransformerConfig(**family._kwargs(config, job))
        else:
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
        return transformer.make_train_step(cfg, mesh, opt).lower(
            shapes(params), shapes(jax.eval_shape(opt.init, params)),
            ids, ids).compile()
    finally:
        patch.undo()
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def step_texts(one_chip):
    """``cell -> text``, each cell compiled once."""
    return functools.cache(
        lambda cell: compiled_step(cell, one_chip).as_text())


@pytest.fixture(scope="module")
def step_text(step_texts):
    return step_texts(CELL)


@pytest.mark.parametrize("cell", BLOCKS)
def test_three_mosaic_calls_a_block_by_their_names(cell, step_texts):
    calls = [line.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for line in step_texts(cell).splitlines()
             if "tpu_custom_call" in line and " = " in line]
    # the compiler's own grouped-product kernels of an expert layer are
    # such calls too, under a name of its own
    assert sorted(c for c in calls if not c.startswith("ragged-dot")) == (
        sorted(KERNELS * BLOCKS[cell]))


def test_nothing_is_permuted_and_less_is_moved_around_the_kernels(step_text):
    total, permutes, rows = around_kernels(step_text)
    assert permutes == 0
    largest = sorted(rows, reverse=True)[:8]
    assert 8e9 < total < BYTES_AROUND_KERNELS, (total, largest)


# ---------------------------------------------------------------------------
# The grid a windowed call walks, at trinity-mini.s16384.epshare's shape
# ---------------------------------------------------------------------------

SWA_SHAPE = (1, 16384, 32, 128)        # batch, seq, query heads, head size
SWA_WINDOW = 2048


def _kernel_grids(lowered_text):
    """``[(kernel name, grid)]`` of a lowered module's Mosaic calls, in
    order: each call's payload (MLIR bytecode in its ``backend_config``)
    parsed, its module's name and ``iteration_bounds`` read."""
    import base64

    from jax._src.lib import tpu  # noqa: F401  (registers the dialect)
    from jax._src.lib.mlir import ir

    found = []
    for payload in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                              lowered_text):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        printed = ir.Module.parse(base64.b64decode(payload),
                                  ctx).operation.get_asm()
        bounds = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>",
                           printed)
        found.append((re.search(r"module @(\w+)", printed).group(1),
                      tuple(int(n) for n in bounds.group(1).split(","))))
    return found


@pytest.mark.parametrize("window", [SWA_WINDOW, None])
def test_a_windowed_call_walks_the_band_and_a_plain_one_the_square(
        window, one_chip):
    """One ``ring_attention`` call, both passes, lowered and compiled for
    the described chip (Mosaic takes the kernels at these tiles): under
    the window each ``*_win`` kernel's grid is ``(32, rows, band)``, the
    band ``band_steps`` gives at the tiles ``_block_sizes`` picks for
    the window and the pass, and walks at most a quarter of the ``nq x
    nk`` tile pairs; without one the three grids are ``(bh, nq, nk)`` at
    1024 x 1024 tiles, as before there was a band."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.ops import pallas_attention as pa
    from horovod_tpu.parallel import ring_attention as ra

    batch, seq, heads, d = SWA_SHAPE
    mesh = Mesh(np.array([one_chip]), ("sp",))
    x = jax.ShapeDtypeStruct(SWA_SHAPE, jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))

    def both(q, k, v, do):
        out, vjp = jax.vjp(lambda *qkv: shard_map(
            lambda q, k, v: ra.ring_attention(q, k, v, "sp", impl="pallas",
                                              window=window),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False)(*qkv), q, k, v)
        return out, vjp(do)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        lowered = jax.jit(both).lower(x, x, x, x)
        grids = _kernel_grids(lowered.as_text())
        assert lowered.compile().as_text().count("tpu_custom_call") >= 3
    finally:
        patch.undo()
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()

    end = "" if window is None else "_win"
    assert [name for name, _ in grids] == [k + end for k in KERNELS]
    tiles = [ra._block_sizes(seq, seq, d, 2, d, window, backward)
             for backward in (False, True, True)]
    if window is None:
        assert tiles == [(1024, 1024)] * 3
        assert [grid for _, grid in grids] == [(batch * heads, 16, 16)] * 3
        return
    # the forward kernel at the chunk's tiles, the backward kernels at
    # the band's: 48 and 160 steps a head for 256 and 1,024
    assert tiles == [(1024, 1024), (512, 512), (512, 512)]
    assert [grid for _, grid in grids] == [
        (batch * heads, 16, 3), (batch * heads, 32, 5),
        (batch * heads, 32, 5)]
    for (bq, bk), (_, (_, rows, band)), q_major in zip(
            tiles, grids, (True, True, False)):
        nq, nk = seq // bq, seq // bk
        assert rows * band * 4 <= nq * nk
        assert band == pa.band_steps(bq, bk, window, nq, nk,
                                     seq)[0 if q_major else 1]
        if q_major:
            assert pa.walked_steps(seq, seq, bq, bk, window, seq) == (
                rows * band, band)
