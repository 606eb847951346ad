"""Kernels: ``attention_kernel_roofline`` over this configuration's counts: the
band's (query, key) pairs on the sliding layers and the causal half on the full
ones, k and v moved once a group of query heads; the tiles the kernel works
cover more than the band, so the share errs low."""

from benchmarks.layer_metrics import attention_kernel_roofline


def read(ctx):
    return attention_kernel_roofline.read(ctx)
