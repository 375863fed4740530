"""Attribution of a compiled program's instructions (``hlo.kinds``)."""
import hlo

TEXT = """HloModule jit_train_step

%fused_computation.4 (p0: f32[3,3,3,64], p1: f32[8,8,8,3]) -> f32[8,8,8,64] {
  %p0 = f32[3,3,3,64]{3,2,1,0} parameter(0)
  %p1 = f32[8,8,8,3]{3,2,1,0} parameter(1)
  ROOT %convolution.3 = f32[8,8,8,64]{3,2,1,0} convolution(%p1, %p0), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(train_step)/jvp()/conv_general_dilated"}
}

%fused_computation.7 (p0: f32[8,4096], p1: f32[4096,1000]) -> f32[8,1000] {
  %p0 = f32[8,4096]{1,0} parameter(0)
  %p1 = f32[4096,1000]{1,0} parameter(1)
  ROOT %convolution.9 = f32[8,1000]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/jvp()/dot_general"}
}

ENTRY %main.87 (a: f32[3,3,3,64], b: f32[8,8,8,3], c: f32[8,4096], d: f32[4096,1000]) -> f32[8,1000] {
  %a = f32[3,3,3,64]{3,2,1,0} parameter(0)
  %b = f32[8,8,8,3]{3,2,1,0} parameter(1)
  %c = f32[8,4096]{1,0} parameter(2)
  %d = f32[4096,1000]{1,0} parameter(3)
  %broadcast_maximum_fusion = (f32[8,8,8,64]{0,3,2,1:T(8,128)}, f32[8,8,8,64]{0,3,2,1:T(8,128)}) fusion(%a, %b), kind=kOutput, calls=%fused_computation.4
  %fusion.2 = f32[8,1000]{1,0:T(8,128)} fusion(%c, %d), kind=kOutput, calls=%fused_computation.7
  %all-reduce-start.1 = f32[8,1000]{1,0} all-reduce-start(%fusion.2), replica_groups={{0,1,2,3}}
  ROOT %all-reduce-done.1 = f32[8,1000]{1,0} all-reduce-done(%all-reduce-start.1)
}
"""


def test_kinds_tell_convolutions_from_matmuls_and_collectives():
    k = hlo.kinds(TEXT)
    assert k["broadcast_maximum_fusion"] == "conv"
    assert k["fusion.2"] == "matmul"
    assert k["all-reduce-start.1"] == "collective"
    assert k["all-reduce-done.1"] == "collective"
    assert k["a"] == "other"
