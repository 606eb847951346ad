"""Sparse experts: of ``diffusion_moe_ms``, the time outside the grouped
products (``moe_route_ms`` in the block-diffusion decoder's cell): the router's
product and softmax, top-k, the sort and the group sizes, the row gathers of
dispatch and combine, forward, recomputed and backward."""

from benchmarks.layer_metrics import moe_route_ms


def read(ctx):
    return moe_route_ms.read(ctx)
