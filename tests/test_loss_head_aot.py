"""What the chip's compiler makes of the loss head in the two GPT-2
cells' real train steps, compiled here for a described ``v5e:2x2`` chip:
no instruction's result is a float32 tensor of the logits' shape (the
parent wrote the whole ``log_softmax`` so, 3.29 GB, for the targets'
gather to read 16,384 numbers of it), and at seq 1024, where that
tensor stood at the step's peak, the temporaries are 3 GB under the
parent's.  A compile, not a chip run: it counts bytes
and says nothing about time.

The fixture that describes the topology and the step's builder are
``tests/test_attention_aot.py``'s; like that file this is one more that
loads the TPU library beside the two of ``tests/benchmark_suite/``: it
runs only where the test run lets several processes load it, as the
driver's does.
"""

import re

import pytest
from test_attention_aot import compiled_step, one_chip  # noqa: F401

# cell -> (the logits' shape, ``memory_analysis().temp_size_in_bytes`` of
# the parent's step, commit 1926655, compiled the same way, the bytes
# this step's must lie under it).  At seq 1024 the f32 tensor stood at
# the step's peak (10,243,095,552 now); at seq 8192 the peak is
# elsewhere in the step, where that tensor was dead already
# (8,332,404,736 now), and is held only not to grow.
CELLS = {"gpt2-124m.s1024": ("16,1024,50257", 13_557_677_056, 3_000_000_000),
         "gpt2-124m.s8192": ("2,8192,50257", 8_289_668_608, -100_000_000)}

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(")


def written(hlo_text):
    """``[(name, result type)]`` of every instruction outside a fused
    computation: what a fusion computes inside itself is not written."""
    fused = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", hlo_text))
    found, computation = [], None
    for line in hlo_text.splitlines():
        if line.endswith("{") and "(" in line and " = " not in line:
            head = line.split()
            computation = head[1 if head[0] == "ENTRY" else 0].lstrip("%")
            continue
        match = _INSTRUCTION.match(line)
        if match and computation not in fused:
            found.append((match.group(1), match.group(2)))
    return found


def test_the_reckoning_on_a_module_written_by_hand():
    text = """
%fused_computation.1 (p: bf16[2,4,8]) -> f32[2,4] {
  %p = bf16[2,4,8]{2,1,0} parameter(0)
  %c = f32[2,4,8]{2,1,0} convert(%p)
  ROOT %r = f32[2,4]{1,0} reduce(%c, %zero), dimensions={2}, to_apply=%add
}

ENTRY %main (a: bf16[2,4,8]) -> f32[2,4] {
  %a = bf16[2,4,8]{2,1,0} parameter(0)
  %w = (bf16[2,4]{1,0}, f32[2,4,8]{2,1,0}) fusion(%a), kind=kLoop, calls=%fused_computation.2
  ROOT %f = f32[2,4]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1
}
"""
    assert [name for name, _ in written(text)] == ["a", "w", "f"]
    assert [name for name, result in written(text)
            if "f32[2,4,8]" in result] == ["w"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_step_writes_no_float32_tensor_of_the_logits_shape(cell,
                                                               one_chip):
    shape, parent_temp, least_freed = CELLS[cell]
    compiled = compiled_step(cell, one_chip)
    text = compiled.as_text()
    results = written(text)
    # the compute-type logits are there, so the search can find a shape
    assert any(f"bf16[{shape}]" in result for _, result in results)
    wide = [row for row in results if f"f32[{shape}]" in row[1]]
    assert not wide, wide
    # both passes of the rule carry the scope's name
    names = set(re.findall(r'op_name="([^"]*hvd_loss_head[^"]*)"', text))
    assert any(n.startswith("jit(step)/jvp(hvd_loss_head)") for n in names)
    assert any("transpose(jvp(hvd_loss_head))" in n for n in names)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= parent_temp - least_freed, (temp, parent_temp)
