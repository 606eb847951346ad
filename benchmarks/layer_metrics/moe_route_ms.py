"""Sparse experts: of ``moe_ms``, the time outside the grouped products: the
router's product and softmax, top-k, the sort and the group sizes, the gather
into sorted rows and the weighted gather back, forward, recomputed and
backward. These carry bytes and no operations of the model's count."""

from benchmarks.layer_metrics import moe_ms
from benchmarks.layer_metrics.loop_stack_ms import scoped_ms


def read(ctx):
    if moe_ms.read(ctx) is None:
        return None
    return scoped_ms(ctx, moe_ms.EXPERTS, outside=moe_ms.EXPERT_MATMUL)
