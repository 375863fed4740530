"""Step: model FLOPs utilization.  The FLOPs that forward and backward
require per sample, counted from the configuration's shapes
(``flops.step_flops_per_sample``), times the samples the window completed,
over the window's seconds, the chips and the chip's peak."""


def read(ctx):
    flops = ctx["cfg"]["step_flops_per_sample"] * ctx["samples"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (ctx["window_s"] * peak)
