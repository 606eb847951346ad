"""Sparse experts: ``moe_ms`` in the block-diffusion decoder's cell, whose expert
layers route all 2L positions of ``x_t || x_0`` and hold the expert its masked
positions rank first beside 15 others: the device time a step under a
``*.SparseExpertsLayer`` scope or in the grouped products' kernel calls,
forward, recomputed and backward."""

from benchmarks.layer_metrics import moe_ms


def read(ctx):
    return moe_ms.read(ctx)
