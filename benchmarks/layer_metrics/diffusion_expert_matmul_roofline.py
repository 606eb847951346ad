"""Kernels: ``expert_matmul_roofline`` in the block-diffusion decoder's cell: the
least time of the grouped products (the configuration module's
``expert_flops_per_sample`` and ``expert_bytes_per_sample``, one held expert a
position of the 2L on average) over the device time under ``expert_matmul``."""

from benchmarks.layer_metrics import expert_matmul_roofline


def read(ctx):
    return expert_matmul_roofline.read(ctx)
