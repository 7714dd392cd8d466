"""What the chip's compiler makes of SwiGLU's backward rule
(``moe.swiglu``) in two cells' real train steps, compiled here for a
described ``v5e:2x2`` chip.  In ``lfm2-24b-a2b.s8192.epshare``'s dense
sub-layer (d_ff 11,776) no backward product reads an f32 (tokens x
11,776) tensor, neither as an operand nor through a producer fused into
it (JAX's own rule left XLA to fuse ``silu(gate) * up`` and its
derivative into five of them, each then reading the f32 ``gate`` and
``up`` again), and the sub-layer runs as many products as before: 3
forward, 2 recomputed, 6 backward; with ``remat`` off, 3 and 6, as
JAX's rule ran, none of them reading such a tensor either.  In
``trinity-mini.s16384.epshare`` the shared expert's backward products
still carry ``hvd_moe_shared``, which ``moe_share`` reads, and read no
f32 (tokens x 1,024) tensor either.  A compile, not a chip run: it
says nothing about time.

The fixture that describes the topology and the step's builder are
``tests/test_attention_aot.py``'s: this file runs only where the test
run lets several processes load the TPU library
(``ALLOW_MULTIPLE_LIBTPU_LOAD=1``) and skips elsewhere.
"""

import math
import re

import pytest
from test_attention_aot import compiled_step, one_chip  # noqa: F401

_HEAD = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")


def instructions(hlo_text):
    """``{computation: {name: (type, opcode, operands, called, op_name,
    parameter number)}}`` and ``{computation: root}``."""
    computations, roots, current = {}, {}, None
    for line in hlo_text.splitlines():
        head = _HEAD.match(line)
        if head:
            current = head.group(1)
            computations[current] = {}
            continue
        match = _INSTRUCTION.match(line)
        if current is None or not match:
            continue
        name, kind, opcode, rest = match.groups()
        depth, end = 1, 0
        while depth and end < len(rest):
            depth += {"(": 1, ")": -1}.get(rest[end], 0)
            end += 1
        called = re.search(r"calls=%([\w.\-]+)", rest)
        op_name = re.search(r'op_name="([^"]*)"', rest)
        number = re.match(r"(\d+)\)", rest) if opcode == "parameter" else None
        computations[current][name] = (
            kind.split("{")[0], opcode,
            re.findall(r"%([\w.\-]+)", rest[:end - 1]),
            called and called.group(1), op_name and op_name.group(1) or "",
            number and int(number.group(1)))
        if line.lstrip().startswith("ROOT"):
            roots[current] = name
    return computations, roots


def operand_types(computations, roots, computation, name):
    """The types of everything a product computes its operands from,
    down to what it reads from memory: through the fusions it sits in
    and the producers fused into them."""
    found = []

    def walk(computation, name, outer):
        kind, opcode, operands, called, _, number = \
            computations[computation][name]
        found.append(kind)
        if opcode == "parameter":
            if outer is not None:
                walk(*outer[number])
        elif opcode == "fusion":
            walk(called, roots[called],
                 [(computation, a, outer) for a in operands])
        else:
            for a in operands:
                if a in computations[computation]:
                    walk(computation, a, outer)

    for a in computations[computation][name][2]:
        walk(computation, a, None)
    return found


def products(hlo_text, width):
    """``[(result type, op_name, operand types)]`` of every matrix
    product with ``width`` among the dimensions of its result or of
    anything its operands are computed from."""
    computations, roots = instructions(hlo_text)
    found = []
    for computation, body in computations.items():
        for name, (kind, opcode, _, _, op_name, _) in body.items():
            if opcode not in ("convolution", "dot"):
                continue
            types = operand_types(computations, roots, computation, name)
            if any(_has(t, width) for t in [kind] + types):
                found.append((kind, op_name, types))
    return found


def _has(kind, width):
    return re.search(rf"[\[,]{width}[\],]", kind) is not None


def wide_f32(types, tokens, width):
    """The f32 types among ``types`` that hold ``tokens x width``
    elements with ``width`` a dimension of theirs."""
    return [t for t in types if t.startswith("f32[") and _has(t, width)
            and math.prod(int(d) for d in re.findall(r"\d+", t[3:]))
            == tokens * width]


def test_the_reckoning_on_a_module_written_by_hand():
    """A product fed by a fusion that computes its bf16 operand from an
    f32 one reads that f32 tensor; one fed a bf16 parameter does not."""
    text = """
%fused_computation.1 (p: f32[4,48]) -> bf16[4,48] {
  %p = f32[4,48]{1,0} parameter(0)
  %m = f32[4,48]{1,0} multiply(%p, %p)
  ROOT %c = bf16[4,48]{1,0} convert(%m)
}

%fused_computation.2 (a: f32[4,48], w: bf16[48,8]) -> f32[4,8] {
  %a = f32[4,48]{1,0} parameter(0)
  %f = bf16[4,48]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %w = bf16[48,8]{1,0} parameter(1)
  ROOT %d = f32[4,8]{1,0} convolution(%f, %w), dim_labels=bf_io->bf, metadata={op_name="jit(f)/transpose(jvp())/dot_general"}
}

ENTRY %main (x: f32[4,48], y: bf16[4,48], w: bf16[48,8]) -> (f32[4,8], f32[4,8]) {
  %x = f32[4,48]{1,0} parameter(0)
  %y = bf16[4,48]{1,0} parameter(1)
  %w = bf16[48,8]{1,0} parameter(2)
  %g = f32[4,8]{1,0} fusion(%x, %w), kind=kOutput, calls=%fused_computation.2
  %h = f32[4,8]{1,0} convolution(%y, %w), dim_labels=bf_io->bf, metadata={op_name="jit(f)/jvp()/dot_general"}
  ROOT %t = (f32[4,8]{1,0}, f32[4,8]{1,0}) tuple(%g, %h)
}
"""
    found = products(text, 48)
    assert [(kind, op_name) for kind, op_name, _ in found] == [
        ("f32[4,8]", "jit(f)/transpose(jvp())/dot_general"),
        ("f32[4,8]", "jit(f)/jvp()/dot_general")]
    # the entry's parameter, the fused computations' and the multiply
    assert set(wide_f32(found[0][2], 4, 48)) == {"f32[4,48]"}
    assert wide_f32(found[1][2], 4, 48) == []


@pytest.fixture(scope="module")
def lfm2_dense(one_chip):  # noqa: F811
    return products(compiled_step("lfm2-24b-a2b.s8192.epshare",
                                  one_chip).as_text(), 11776)


def test_no_backward_product_of_the_dense_layer_reads_float32(lfm2_dense):
    """Neither an f32 ``[2,8192,11776]`` operand nor an f32 producer of
    that shape in any product of the backward pass: they read the
    ``hidden``, ``d_gate`` and ``d_up`` the rule rounded."""
    backward = [row for row in lfm2_dense if "transpose(" in row[1]]
    assert backward
    for kind, op_name, types in backward:
        assert not wide_f32(types, 2 * 8192, 11776), (kind, op_name)


def test_the_dense_layer_runs_as_many_products_as_before(lfm2_dense):
    """3 forward (gate, up, down); under the backward pass the 2
    recomputed (gate and up) and ``d_hidden``, each a (tokens x 11,776)
    result, the three weights' gradients and the input's two products."""
    forward = [kind for kind, op_name, _ in lfm2_dense
               if "transpose(" not in op_name]
    backward = sorted(kind for kind, op_name, _ in lfm2_dense
                      if "transpose(" in op_name)
    assert len(forward) == 3, forward
    assert backward == sorted(["f32[2,8192,11776]"] * 3
                              + ["f32[2048,11776,1]"] * 2
                              + ["f32[11776,2048,1]"]
                              + ["f32[2,8192,2048]"] * 2), backward


@pytest.fixture(scope="module")
def lfm2_dense_kept(one_chip):  # noqa: F811
    """The same step with ``remat`` off: no layer is computed again."""
    from horovod_tpu.models import transformer

    whole = transformer.TransformerConfig
    patch = pytest.MonkeyPatch()
    patch.setattr(transformer, "TransformerConfig",
                  lambda **kw: whole(**{**kw, "remat": False}))
    try:
        return products(compiled_step("lfm2-24b-a2b.s8192.epshare",
                                      one_chip).as_text(), 11776)
    finally:
        patch.undo()


def test_without_a_checkpoint_nothing_is_computed_again(lfm2_dense_kept):
    """The rule keeps ``gate`` and ``up`` from the forward pass, so where
    no checkpoint replays the layer it runs JAX's rule's 9 products: 3
    forward, ``d_hidden`` and five more backward, none of which reads an
    f32 (tokens x 11,776) tensor (JAX's rule left four that did)."""
    forward = [kind for kind, op_name, _ in lfm2_dense_kept
               if "transpose(" not in op_name]
    backward = [row for row in lfm2_dense_kept if "transpose(" in row[1]]
    assert len(forward) == 3, forward
    assert sorted(kind for kind, _, _ in backward) == sorted(
        ["f32[2,8192,11776]"] + ["f32[2048,11776,1]"] * 2
        + ["f32[11776,2048,1]"] + ["f32[2,8192,2048]"] * 2), backward
    for kind, op_name, types in backward:
        assert not wide_f32(types, 2 * 8192, 11776), (kind, op_name)


def test_the_shared_experts_backward_keeps_its_scope(one_chip):  # noqa: F811
    """``trinity-mini.s16384.epshare``: in each of its 4 expert
    sub-layers the shared expert's 2 recomputed and 6 backward products
    are named under ``hvd_moe_shared`` (the rule runs inside the scope
    its caller opened), and none reads an f32 (tokens x 1,024)
    tensor."""
    shared = [row for row in products(
        compiled_step("trinity-mini.s16384.epshare", one_chip).as_text(),
        1024) if "hvd_moe_shared" in row[1] and "transpose(" in row[1]]
    assert len(shared) == 4 * 8, [kind for kind, _, _ in shared]
    for kind, op_name, types in shared:
        assert not wide_f32(types, 16384, 1024), (kind, op_name)
