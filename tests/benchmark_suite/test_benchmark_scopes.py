"""``benchmark/scopes.py`` and the per-layer metrics that read the
program's own names (PR 23): the join from instruction to ``op_name``
and the classes of an ``op_name`` on hand-written modules, the toy
cells through the harness on the CPU, and a recorded v5e trace of the
scoped program that pins every new reader's value.

Nothing here loads the TPU library, while a module is imported or after.
"""

import json
import os

import pytest

from benchmark import manifest, reduce, scopes
from test_benchmark_harness import run_cell, toy_root  # noqa: F401 (fixture)

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data",
                     "trace_gpt2-124m.s8192_scoped_2steps.textproto.gz")
NAMES = os.path.join(HERE, "data",
                     "names_gpt2-124m.s8192_scoped.json.gz")
PART_METRICS = ("fwd_share", "bwd_share", "optimizer_share",
                "grad_reduce_share", "unscoped_share")
NEW_METRICS = PART_METRICS + (
    "loss_head_share", "attn_share", "flash_fwd_roofline",
    "flash_bwd_dq_roofline", "flash_bwd_dkv_roofline", "init_backend_s",
    "init_world_s")


# ---------------------------------------------------------------------------
# Hand-written modules, in the wire format a trace holds them in
# ---------------------------------------------------------------------------


def varint(value: int) -> bytes:
    out = bytearray()
    while True:
        value, byte = value >> 7, value & 0x7F
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def field(number: int, value) -> bytes:
    """One field: an ``int`` as a varint, ``bytes``/``str`` length-
    delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def instruction(ident: int, name: str, op_name: str = "", calls=(),
                opcode: str = "multiply") -> bytes:
    """An ``HloInstructionProto``: name 1, opcode 2, metadata 7
    (op_name 2), id 35, called_computation_ids 38."""
    body = field(1, name) + field(2, opcode) + field(35, ident)
    if op_name:
        body += field(7, field(2, op_name))
    return body + b"".join(field(38, c) for c in calls)


def computation(ident: int, root: int, *instructions: bytes) -> bytes:
    """An ``HloComputationProto``: instructions 2, id 5, root_id 6."""
    return (b"".join(field(2, i) for i in instructions)
            + field(5, ident) + field(6, root))


def module(*computations: bytes) -> bytes:
    return b"".join(field(3, c) for c in computations)


def names_of_module(body: bytes) -> dict:
    return scopes.module_op_names(body, (0, len(body)))


STEP = "jit(step)/"


def test_classes_of_an_op_name():
    """The part is the first that fits; the scopes are every ``hvd_*``
    component, whatever JAX wrapped around them."""
    flash = STEP + ("transpose(jvp(hvd_attn))/while/body/closed_call/"
                    "hvd_flash_bwd_dq/pallas_call")
    assert scopes.part_of(flash) == "bwd"
    assert scopes.scopes_of(flash) == ("hvd_attn", "hvd_flash_bwd_dq")
    assert scopes.part_of(STEP + "jvp(hvd_loss_head)/jit(log_softmax)/exp") \
        == "fwd"
    assert scopes.part_of(STEP + "hvd_optimizer/mul") == "optimizer"
    assert scopes.part_of(STEP + "shmap_body/hvd_grad_reduce/psum") \
        == "grad_reduce"
    # a bucket's own scope nests inside and does not hide the part
    assert scopes.part_of(STEP + "hvd_grad_reduce/hvd_overlap_rs0/ppermute") \
        == "grad_reduce"
    assert scopes.part_of(STEP + "jvp(ResNet)/Conv_0/conv_general_dilated") \
        == "fwd"
    assert scopes.part_of(STEP + "add") == "unscoped"
    assert scopes.part_of("") == "unscoped"
    assert scopes.scopes_of(STEP + "add") == ()


def test_join_on_hand_written_instructions():
    """What the issue names: a fusion whose root is unscoped and whose
    body is the optimizer's; a ``transpose(jvp(hvd_attn))`` custom call;
    an async copy with no metadata.  Then the two fusions a count of
    votes gets wrong: an Adam update that also adds up the layers'
    gradients, and a backward fusion that recomputes forward values.
    Then the one the latest part gets wrong: a weight-gradient
    convolution that ends in the optimizer's ``-lr * g``."""
    sgd = computation(
        10, 13,
        instruction(11, "param_0"),
        instruction(12, "mul.1", STEP + "hvd_optimizer/mul"),
        instruction(13, "add.1", STEP + "add"))
    adam = computation(
        20, 26,
        *[instruction(21 + i, f"pad.{i}", STEP + "transpose(jvp())/pad")
          for i in range(3)],
        instruction(24, "mul.2", STEP + "hvd_optimizer/mul"),
        instruction(25, "sqrt.2", STEP + "hvd_optimizer/sqrt"),
        instruction(26, "tuple.2"))
    recompute = computation(
        30, 34,
        instruction(31, "tanh.3", STEP + "jvp()/tanh"),
        instruction(32, "mul.3", STEP + "jvp()/mul"),
        instruction(33, "mul.4", STEP + "jvp(hvd_attn)/mul"),
        instruction(34, "mul.5", STEP + "transpose(jvp(hvd_attn))/mul"))
    weight_gradient = computation(
        40, 43,
        instruction(41, "convolution.6", STEP + "transpose(jvp(ResNet))/"
                    "Conv_0/conv_general_dilated", opcode="convolution"),
        instruction(42, "mul.6", STEP + "hvd_optimizer/mul"),
        instruction(43, "add.6", STEP + "add", opcode="add"))
    entry = computation(
        1, 6,
        instruction(7, "multiply_add_fusion.4", STEP + "add", calls=[40],
                    opcode="fusion"),
        instruction(2, "fusion.1", STEP + "add", calls=[10]),
        instruction(3, "hvd_flash_bwd_dq.7",
                    STEP + "transpose(jvp(hvd_attn))/hvd_flash_bwd_dq/"
                           "pallas_call"),
        instruction(4, "copy-start.9"),
        instruction(5, "fusion.2", STEP + "transpose(jvp())/pad",
                    calls=[20]),
        instruction(6, "fusion.3", STEP + "jvp()/tanh", calls=[30]))
    names = names_of_module(module(sgd, adam, recompute, weight_gradient,
                                   entry))
    assert scopes.part_of(names["multiply_add_fusion.4"]) == "bwd"
    assert scopes.part_of(names["fusion.1"]) == "optimizer"
    assert names["fusion.1"] == STEP + "hvd_optimizer/mul"
    assert scopes.part_of(names["hvd_flash_bwd_dq.7"]) == "bwd"
    assert scopes.scopes_of(names["hvd_flash_bwd_dq.7"]) \
        == ("hvd_attn", "hvd_flash_bwd_dq")
    assert names["copy-start.9"] == ""
    assert scopes.part_of(names["copy-start.9"]) == "unscoped"
    assert scopes.part_of(names["fusion.2"]) == "optimizer"     # 2 of 6
    assert scopes.part_of(names["fusion.3"]) == "bwd"           # 1 of 4
    assert scopes.scopes_of(names["fusion.3"]) == ("hvd_attn",)
    # a member keeps its own name
    assert names["mul.1"] == STEP + "hvd_optimizer/mul"


def test_scopes_vote_by_count_and_the_root_breaks_a_tie():
    """``elect`` on ``(op_name, is the root, is a product)``."""
    fwd = STEP + "jvp()/mul"
    attn = STEP + "jvp(hvd_attn)/mul"
    head = STEP + "jvp(hvd_loss_head)/mul"
    no, root = (False, False), (True, False)
    assert scopes.elect([(fwd, *no), (attn, *no), (attn, *no)]) == attn
    assert scopes.elect([(fwd, *no), (attn, *no), (head, *root)]) == head
    assert scopes.elect([(fwd, *no), (attn, *no), ("", *root)]) == fwd
    assert scopes.elect([("x", *no), ("", *root)]) == "x"
    # a product decides alone, also against later parts and the count
    opt = STEP + "hvd_optimizer/mul"
    assert scopes.elect([(opt, *no), (opt, *root),
                         (head, False, True)]) == head
    assert scopes.elect([(opt, *no), ("", False, True)]) == opt


def test_a_trace_file_names_its_own_instructions(tmp_path):
    """``load`` finds the module in the ``/host:metadata`` plane of an
    ``XSpace``, the largest first, and gives nothing for a program
    without an ``hvd_*`` scope."""
    def xspace(*modules: bytes) -> bytes:
        # XSpace.planes 1 { name 2, event_metadata 4 { value 2 {
        # stats 5 { bytes_value 6: HloProto { hlo_module 1 }}}}}
        entries = b"".join(
            field(4, field(1, k) + field(2, field(5, field(6, field(1, m)))))
            for k, m in enumerate(modules))
        return (field(1, field(2, "/host:CPU"))
                + field(1, field(2, scopes.METADATA_PLANE) + entries))

    small = module(computation(1, 2, instruction(2, "fusion.1",
                                                 STEP + "jvp()/neg")))
    large = module(computation(
        1, 3, instruction(2, "fusion.1", STEP + "hvd_optimizer/mul"),
        instruction(3, "fusion.2", STEP + "transpose(jvp())/mul")))
    path = tmp_path / "a.xplane.pb"
    path.write_bytes(xspace(small, large))
    assert scopes.load(str(path)) == {
        "fusion.1": STEP + "hvd_optimizer/mul",
        "fusion.2": STEP + "transpose(jvp())/mul"}
    other = tmp_path / "b.xplane.pb"
    other.write_bytes(xspace(small))
    assert scopes.load(str(other)) is None
    with pytest.raises(ValueError, match="protobuf"):
        other.write_bytes(b"\x0b\x00\x00")
        scopes.load(str(other))


def test_parts_partition_busy_time_where_operations_overlap():
    """On the CPU's thread pool operations overlap: an instant belongs
    to the first part that runs in it, wrappers are left out, and the
    five parts add up to the leaves' busy time exactly."""
    names = {"a": STEP + "jvp()/x", "b": STEP + "transpose(jvp())/x",
             "c": STEP + "hvd_optimizer/x", "d": "",
             "e": STEP + "hvd_grad_reduce/psum",
             "while.1": STEP + "jvp()/while"}
    ops = [reduce.Op("a", 0, 100), reduce.Op("b", 50, 150),
           reduce.Op("c", 140, 200), reduce.Op("d", 190, 260),
           reduce.Op("e", 300, 310), reduce.Op("f", 310, 320),
           reduce.Op("while.1", 0, 1000)]
    parts = scopes.part_ns(ops, names)
    assert parts == {"grad_reduce": 10, "optimizer": 60, "bwd": 90,
                     "fwd": 50, "unscoped": 70}
    assert sum(parts.values()) \
        == reduce.total(reduce.busy(reduce.leaves(ops))) == 280
    assert scopes.scope_ns(ops, names, "hvd_optimizer") == 60


def test_flash_costs_add_up_to_the_familys():
    """2 / 2 / 3 of the seven required products, and every byte of the
    family's count, shared out over the three kernels."""
    cell = manifest.load_cell("gpt2-124m.s8192")
    costs = scopes.flash_costs(cell)
    assert set(costs) == set(scopes.FLASH_KERNELS)
    whole = manifest.load_family(cell).kernel_costs(
        cell.config, cell.job)["flash_attn"]
    product = 12 * 24 * 64 * 8192 * 8193
    assert [costs[k]["flops"] for k in scopes.FLASH_KERNELS] \
        == [2 * product, 2 * product, 3 * product]
    for key in ("flops", "bytes"):
        assert sum(c[key] for c in costs.values()) == whole[key]
    peaks = manifest.load_peaks(cell, "TPU v5 lite")
    for cost in costs.values():      # FLOP-bound, each of them
        assert cost["flops"] / peaks["bf16_flops_per_s"] \
            > cost["bytes"] / peaks["hbm_bytes_per_s"] > 0


# ---------------------------------------------------------------------------
# The toy cells through the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def scoped_root(toy_root):
    """``test_benchmark_harness.toy_root`` (this module's own copy of
    it) with the new metrics in its manifest: the real manifest's
    entries, each for the toy cells of the family its real cells have."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        toy = json.load(f)
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW_METRICS:
        entry = dict(real[name])
        cells = entry.pop("workloads", None)
        if cells is not None and not any("resnet" in c for c in cells):
            entry["workloads"] = ["toy-lm.s32"]
        elif cells is not None:          # the one-chip cells: the inline ones
            entry["workloads"] = ["toy-lm.s32", "toy-resnet.b8"]
        toy["per_layer"].append(entry)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(toy, f)
    return toy_root


@pytest.mark.parametrize("workload", ["toy-lm.s32", "toy-resnet.b8",
                                      "toy-resnet.b8.np2"])
def test_toy_cells_give_the_new_metrics(scoped_root, capfd, workload):
    """Every new metric of the cell is on the traced line, but the
    flash kernels' where no kernel runs (the toy sequence is short) and,
    through the launcher, the spans of ``hvd.init()``, which this
    process did not run; the five parts add up to 1."""
    code, line, cell = run_cell(scoped_root, capfd, workload, True)
    assert code == 0
    got = line["metrics"]
    want = {m["name"] for m in cell.per_layer}
    absent = {m for m in want if m.startswith("flash_")}
    assert set(got) == want - absent
    assert sum(got[m]["value"] for m in PART_METRICS) \
        == pytest.approx(1.0, abs=1e-6)
    for name in PART_METRICS:
        assert 0 <= got[name]["value"] <= 1
        assert got[name]["unit"] == "frac_of_busy"
    assert got["fwd_share"]["value"] > 0 and got["bwd_share"]["value"] > 0
    assert got["optimizer_share"]["value"] > 0
    assert got["unscoped_share"]["value"] < 0.5
    if cell.job["world"] > 1:        # a real collective, under its name
        assert got["grad_reduce_share"]["value"] > 0
    if cell.config["family"] == "lm_mesh":
        assert 0 < got["attn_share"]["value"] < 1
        assert 0 < got["loss_head_share"]["value"] < 1
    if cell.job["launcher"] == "inline":
        assert 0 < got["init_backend_s"]["value"] < 60
        assert 0 <= got["init_world_s"]["value"] < 60
    else:
        assert "init_backend_s" not in want


def test_a_program_without_names_gives_no_metric(scoped_root, monkeypatch):
    """Laid over the parent's checkout, whose step has no ``hvd_*``
    scope and whose ``hvd.init()`` no span, every new reader returns
    nothing and none raises."""
    from horovod_tpu.runtime import flight

    path = os.path.join(scoped_root, "BENCHMARK.json")
    cell = manifest.load_cell("toy-lm.s32", path)
    trace = reduce.Trace({"chip": [reduce.Op("fusion.1", 0, 10)]}, [], 1)
    monkeypatch.setattr(scopes, "names_of", lambda cell: None)
    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(16))
    flight.record("init", rank=0)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite")}
    for name in NEW_METRICS:
        read = manifest.load_layer_reader(cell, name)
        assert read(trace, counters, cell) is None, name


# ---------------------------------------------------------------------------
# A recorded trace of the scoped program
# ---------------------------------------------------------------------------


def test_recorded_scoped_trace_through_the_new_readers(monkeypatch):
    """Two steps of ``gpt2-124m.s8192`` on a v5e (my chip run, PR 23;
    cut by ``cut_trace.py`` as it is), beside the instruction ->
    ``op_name`` map of the same trace file (``python -m benchmark.scopes
    <trace> <map>``): every new device metric, pinned."""
    cell = manifest.load_cell("gpt2-124m.s8192")
    trace = reduce.read_trace(TRACE, 2, ("bench_step", "dispatch", "block"))
    names = scopes.load(NAMES)
    monkeypatch.setattr(scopes, "names_of", lambda cell: names)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite")}
    got = {m["name"]: manifest.load_layer_reader(cell, m["name"])(
               trace, counters, cell)
           for m in cell.per_layer
           if m["name"] in NEW_METRICS and m["source"] == "device_trace"}
    assert got == {
        "fwd_share": pytest.approx(0.378868, rel=1e-5),
        "bwd_share": pytest.approx(0.618023, rel=1e-5),
        "optimizer_share": pytest.approx(0.00226797, rel=1e-5),
        "grad_reduce_share": 0.0,     # dp = 1: the psum is no operation
        "unscoped_share": pytest.approx(0.000840512, rel=1e-5),
        "loss_head_share": pytest.approx(0.0159875, rel=1e-5),
        "attn_share": pytest.approx(0.954873, rel=1e-5),
        "flash_fwd_roofline": pytest.approx(1.74723, rel=1e-5),
        "flash_bwd_dq_roofline": pytest.approx(2.50739, rel=1e-5),
        "flash_bwd_dkv_roofline": pytest.approx(2.84604, rel=1e-5),
    }
    assert sum(got[m] for m in PART_METRICS) == pytest.approx(1.0, abs=1e-9)
    # the three kernels are told apart by name, and are all the kernels
    ops = next(iter(trace.chips.values()))
    by_name = sum(scopes.kernel_ns(ops, names, k)
                  for k in scopes.FLASH_KERNELS)
    assert by_name == reduce.kernel_ns(ops) > 0
    # (an operand copy the compiler puts before flash_bwd_dkv carries the
    # kernel's op_name: under the scope, 4.2 ms a step, and no kernel)
    assert scopes.scope_ns(ops, names, "hvd_flash_bwd_dkv") \
        - scopes.kernel_ns(ops, names, "hvd_flash_bwd_dkv") == 8338875
    assert got["attn_share"] > by_name / reduce.total(
        reduce.busy(reduce.leaves(ops))) > 0.9
