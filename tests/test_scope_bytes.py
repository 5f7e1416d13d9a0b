"""`benchmarks/scope_bytes.py`: the bytes a compiled step moves through HBM
under a named scope, summed from XLA's text of the program: what stands
outside a fusion moves its operands and its result, what is inside one moves
nothing, and an instruction's phase is read from its `op_name`."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "scope_bytes", os.path.join(HERE, "..", "benchmarks", "scope_bytes.py"))
scope_bytes = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(scope_bytes)

TEXT = '''HloModule jit_step

%fused_computation.1 (param_0.1: bf16[2,64,256]) -> f32[2,64,256] {
  %param_0.1 = bf16[2,64,256]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %convert.9 = f32[2,64,256]{2,1,0:T(8,128)} convert(%param_0.1), metadata={op_name="jit(step)/jvp()/mamba/mamba_norm/convert_element_type"}
}

ENTRY %main.3 (x.1: bf16[2,64,256], w.1: f32[256]) -> (f32[2,64,256], bf16[2,64,128]) {
  %x.1 = bf16[2,64,256]{2,1,0:T(8,128)(2,1)} parameter(0)
  %w.1 = f32[256]{0:T(256)} parameter(1)
  %bitcast.4 = bf16[2,64,256]{2,1,0:T(8,128)(2,1)} bitcast(%x.1)
  %fusion.1 = f32[2,64,256]{2,1,0:T(8,128)} fusion(%bitcast.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp()/mamba/mamba_norm/convert_element_type" stack_frame_id=7}
  %copy.2 = f32[2,64,256]{2,1,0:T(8,128)} copy(%fusion.1), metadata={op_name="jit(step)/transpose(jvp())/checkpoint/rematted_computation/mamba/mamba_norm/mul"}
  %mamba_conv_bwd.5 = (bf16[2,64,128]{2,1,0:T(8,128)(2,1)}, f32[5,8,128]{2,1,0:T(8,128)}) custom-call(%x.1, %w.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/checkpoint/mamba/mamba_conv/mamba_conv_bwd"}
  %get-tuple-element.6 = bf16[2,64,128]{2,1,0:T(8,128)(2,1)} get-tuple-element(%mamba_conv_bwd.5), index=0
  ROOT %tuple.7 = (f32[2,64,256]{2,1,0:T(8,128)}, bf16[2,64,128]{2,1,0:T(8,128)(2,1)}) tuple(%copy.2, %get-tuple-element.6)
}
'''
WIDE = 2 * 64 * 256


def test_shapes_are_counted_at_their_dtypes():
    assert scope_bytes.shape_bytes("bf16[2,64,256]{2,1,0}") == WIDE * 2
    assert scope_bytes.shape_bytes(
        "(bf16[2,64,128]{2,1,0}, f32[5,8,128])") == WIDE + 5 * 8 * 128 * 4
    assert scope_bytes.shape_bytes("s32[]") == 4
    assert scope_bytes.shape_bytes("token[]") == 0


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/jvp()/while/body/mamba/mamba_conv/mul", "forward"),
    ("jit(step)/transpose(jvp())/checkpoint/mamba/mamba_conv/mul", "backward"),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/mamba/mul",
     "again"),
])
def test_a_phase_is_read_from_the_op_name(op_name, phase):
    assert scope_bytes.phase_of(op_name) == phase


def test_what_stands_outside_a_fusion_moves_its_operands_and_its_result():
    moved = {name: (opcode, size)
             for name, opcode, size, _ in scope_bytes.instructions(TEXT)}
    # the convert inside the fusion, the parameters, the bitcast and the
    # tuple's parts move nothing
    assert moved == {
        "%fusion.1": ("fusion", WIDE * 2 + WIDE * 4),
        "%copy.2": ("copy", WIDE * 4 + WIDE * 4),
        "%mamba_conv_bwd.5": (
            "custom-call", WIDE * 2 + 256 * 4 + WIDE + 5 * 8 * 128 * 4),
    }


def test_bytes_are_summed_by_scope_and_phase():
    found = scope_bytes.by_scope(TEXT, ["mamba_norm", "mamba_conv", "mamba"])
    assert {phase: [m[1] for m in moved]
            for phase, moved in found["mamba_norm"].items()} == {
        "forward": ["%fusion.1"], "again": ["%copy.2"]}
    assert [m[1] for m in found["mamba_conv"]["backward"]] == [
        "%mamba_conv_bwd.5"]
    # a scope is a whole part of the name: `mamba` holds all three, and
    # `mamba_conv` is not counted under `conv`
    assert sum(len(v) for v in found["mamba"].values()) == 3
    assert scope_bytes.by_scope(TEXT, ["conv"])["conv"] == {}
