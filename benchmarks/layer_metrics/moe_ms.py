"""Sparse experts: the device time a step in operations of the expert layers —
under a ``*.SparseExpertsLayer`` scope (``router``, ``route``, ``dispatch``,
``expert_matmul``, ``combine``: forward, what ``remat`` recomputes and
backward) or named ``ragged-dot-*``, the kernel calls the TPU compiler makes of
a grouped product, which lose the scope they were written under; the busiest
device, the mean over the traced steps. A program without such a layer gives
nothing to read."""

import re

from benchmarks.layer_metrics.loop_stack_ms import scoped_ms

GROUPED_PRODUCT = r"^ragged-dot-"
EXPERTS = re.compile(r"[(/][^()/]*\.SparseExpertsLayer[)/]|" + GROUPED_PRODUCT)
EXPERT_MATMUL = re.compile(r"/expert_matmul/|" + GROUPED_PRODUCT)


def read(ctx):
    # the scope, not the bare kernel calls, says that the layer is there
    if scoped_ms(ctx, re.compile(r"\.SparseExpertsLayer[)/]")) is None:
        return None
    return scoped_ms(ctx, EXPERTS)
