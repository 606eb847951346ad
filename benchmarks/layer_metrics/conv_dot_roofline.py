"""Kernels: the least time the chip could take for the configuration's
forward + backward operations of one step (``train_flops_per_sample`` x batch
over chips x the peak of the cell's compute type; for ``resnet50`` all of it
is convolution and dense products) over ``step_conv_dot_ms``. Bound by
operations, not bytes. Time fused onto a convolution counts as its time, so
the share errs low."""

from benchmarks.layer_metrics import step_conv_dot_ms


def read(ctx):
    conv_dot_ms, cell = step_conv_dot_ms.read(ctx), ctx["cell"]
    if not conv_dot_ms:
        return None
    flops = ctx["module"].train_flops_per_sample(cell.config, cell.traffic) * cell.traffic["batch"]
    peak = ctx["peaks"]["flops_per_s"][cell.config["compute_dtype"]]
    return 100.0 * flops / (ctx["chips"] * peak) / (1e-3 * conv_dot_ms)
