"""``benchmark/reduce.py`` held to fixed values on a recorded v5e trace,
so that no later PR can quietly redefine a per-layer metric.

``data/trace_gpt2-124m.s8192_2steps.textproto.gz`` is two steps of a
traced run of ``gpt2-124m.s8192`` on a TPU v5 lite (PR 22), cut by
``cut_trace.py`` to the device's ``XLA Ops`` and ``Steps`` lines and the
host line with the loop's annotations.  It is read through the same
``ProfileData`` path as a whole trace.
"""

import os

import pytest

from benchmark import reduce
from benchmark.reduce import Op

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_gpt2-124m.s8192_2steps.textproto.gz")
SPANS = ("bench_step", "dispatch", "block")

FUSION = ("%fusion.7 = f32[16,1024,50257]{1,2,0:T(8,128)} fusion(bf16[16,"
          "1024,50257]{1,2,0:T(8,128)(2,1)} %get-tuple-element.2040, f32[16,"
          "1024]{1,0:T(8,128)S(1)} %fusion.9), kind=kLoop, calls=%fused.1")
KERNEL = ("%closed_call.187 = f32[24,8192,64]{2,1,0:T(8,128)} custom-call("
          "s32[2]{0:T(128)S(1)} %copy-done.417, bf16[24,8192,64]{2,1,0:T(8,"
          "128)(2,1)} %bitcast.1532), custom_call_target=\"tpu_custom_call\","
          " operand_layout_constraints={s32[2]{0}}")
TUPLE = ("%all-reduce-start.3 = (f32[768]{0:T(1024)}, f32[768]{0:T(1024)S(1)"
         "}) all-reduce-start(f32[768]{0:T(1024)} %x), replica_groups={}")


@pytest.fixture(scope="module")
def recorded():
    return reduce.read_trace(RECORDED, 2, SPANS)


def test_interval_arithmetic():
    merged = reduce.merge([[5, 7], [0, 2], [1, 3], [7, 9], [20, 21]])
    assert merged == [[0, 3], [5, 9], [20, 21]]
    assert reduce.total(merged) == 8
    assert reduce.intersect(merged, [[2, 6], [8, 30]]) \
        == [[2, 3], [5, 6], [8, 9], [20, 21]]
    assert reduce.merge([]) == [] and reduce.intersect([], merged) == []


@pytest.mark.parametrize("text, name, opcode, signature, kernel", [
    (FUSION, "fusion.7", "fusion", "fusion f32[16,1024,50257]", False),
    (KERNEL, "closed_call.187", "custom-call",
     "custom-call f32[24,8192,64]", True),
    (TUPLE, "all-reduce-start.3", "all-reduce-start",
     "all-reduce-start (f32[768], f32[768])", False),
    ("dot_general.1", "dot_general.1", "dot_general", "dot_general.1",
     False),                              # the CPU backend's bare names
])
def test_an_event_name_is_parsed(text, name, opcode, signature, kernel):
    op = Op(text, 0, 1)
    assert (op.name, op.opcode, op.signature, op.is_kernel) \
        == (name, opcode, signature, kernel)


def test_collectives_in_flight_and_exposed():
    """Asynchronous ones run from ``-start`` to the next ``-done`` of
    their kind, first in first out; a synchronous one is its own event;
    exposed is the part under which no other leaf ran."""
    def op(opcode, start, end):
        return Op(f"%{opcode}.1 = f32[8]{{0}} {opcode}(f32[8]{{0}} %x)",
                  start, end)

    ops = [op("all-reduce-start", 0, 1), op("fusion", 1, 4),
           op("all-reduce-start", 4, 5), op("all-reduce-done", 6, 8),
           op("fusion", 8, 9), op("all-reduce-done", 9, 12),
           op("collective-permute", 20, 25), op("while", 0, 30)]
    assert reduce.collective_intervals(ops) == [[0, 12], [20, 25]]
    # in flight 17; fusions cover [1,4] and [8,9]; the while is a wrapper
    assert reduce.exposed_collective_ns(ops) == 17 - 4
    assert reduce.collective_kind(ops[1]) is None
    assert reduce.collective_kind(ops[6]) == "collective-permute"
    with pytest.raises(ValueError, match="closes no all-gather-start"):
        reduce.collective_intervals([op("all-gather-done", 3, 4)])


def test_recorded_trace_window_busy_and_idle(recorded):
    assert list(recorded.chips) == ["/device:TPU:0"]
    ops = recorded.chips["/device:TPU:0"]
    assert len(ops) == 3754
    assert reduce.window(ops) == (6025366, 4016322322)
    assert reduce.total(reduce.busy(ops)) == 4010279572
    longest = reduce.idle_gaps(ops)[0]
    assert longest == (2011130882, 2011142230)     # between the two steps
    assert reduce.label_gaps([longest], recorded.spans) \
        == [["block", pytest.approx(1.1348e-05)]]


def test_recorded_trace_collectives_and_kernels(recorded):
    ops = recorded.chips["/device:TPU:0"]
    # ring attention's ppermute at sp=1: a chip sending to itself
    assert {reduce.collective_kind(op) for op in ops} \
        == {None, "collective-permute"}
    assert reduce.total(reduce.collective_intervals(ops)) == 137105484
    assert reduce.exposed_collective_ns(ops) == 4943867
    # 2 steps x 12 layers x (forward, dq, dkv): the Mosaic custom calls
    assert sum(op.is_kernel for op in ops) == 72
    assert reduce.kernel_ns(ops) == 3763255993


def test_recorded_trace_operations_by_signature(recorded):
    ops = recorded.chips["/device:TPU:0"]
    top = reduce.seconds_by_signature(ops)
    assert top[0] == [
        "custom-call (f32[24,8192,128], f32[24,8192,64]) "
        "[closed_call.168, x24]", pytest.approx(1.437625294)]
    assert top[1][0].startswith("custom-call (f32[24,8192,64], f32[24,8192,"
                                "64]) [closed_call.188, x24]")
    assert top[2][0] == "custom-call f32[24,8192,64] [closed_call.187, x24]"
    assert sum(seconds for _, seconds in top) \
        == pytest.approx(sum(op.end - op.start for op in ops) * 1e-9)


def test_recorded_trace_through_the_metric_readers(recorded):
    """The per-layer readers on the recorded trace: the numbers PERF.md
    quotes for this cell's first trace."""
    from benchmark import manifest

    cell = manifest.load_cell("gpt2-124m.s8192")
    family = manifest.load_family(cell)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite"),
                "kernel_costs": family.kernel_costs(cell.config, cell.job)}

    def read(metric):
        return manifest.load_layer_reader(cell, metric)(recorded, counters,
                                                        cell)

    assert read("device_idle_share") == pytest.approx(4.35e-6, rel=0.01)
    assert read("attn_kernel_share") == pytest.approx(0.93840, rel=1e-4)
    assert read("flash_attn_roofline") == pytest.approx(2.336, rel=1e-3)
    assert read("coll_s_per_step") == pytest.approx(0.068553, rel=1e-4)
    assert read("coll_exposed_share") == pytest.approx(1.2328e-3, rel=1e-3)
    # no kernel ran: the roofline reader returns nothing
    empty = reduce.Trace({"chip": [Op(FUSION, 0, 10)]}, [], 1)
    assert manifest.load_layer_reader(cell, "flash_attn_roofline")(
        empty, counters, cell) is None
    assert manifest.load_layer_reader(cell, "attn_kernel_share")(
        empty, counters, cell) == 0


def test_a_trace_without_device_operations_fails_loudly(tmp_path):
    import gzip

    path = str(tmp_path / "empty.textproto.gz")
    with gzip.open(path, "wt") as f:
        f.write('planes { name: "/host:CPU" lines { id: 0 name: "python3" '
                'events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 } } '
                'event_metadata { key: 1 value { id: 1 name: "block" } } }\n')
    with pytest.raises(ValueError, match="no operation ran on a device"):
        reduce.read_trace(path, 1, SPANS)
