"""Kernels: the convolutions' share of their roofline.  The least time the
chip could take for the window's convolutions (forward, input gradient,
weight gradient, counted from shapes: ``flops.conv_least_seconds``), over
the device time of the ops that compute a ``conv_general_dilated``
(``hlo.kinds``), summed over the chips."""
import flops


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["conv_s"] <= 0:
        return None
    least = flops.conv_least_seconds(
        ctx["cfg"], trace["samples"], ctx["peaks"]["bf16_flops_per_s"],
        ctx["peaks"]["hbm_bytes_per_s"])
    if least <= 0:
        return None
    return 100.0 * least / trace["conv_s"]
