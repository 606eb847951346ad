"""Sparse-attention mixer: the device time a step in operations under a
``mixer.SparseAttentionLayer`` scope, the mixer whole: the q, k and v
projections with their norms and M-RoPE, the indexer (its projections, its
choice of keys and its loss, scope ``indexer``), the attention over the chosen
keys (the op's ``sparse_attention`` scope, the Pallas pair on the chip) and
``Wo``, forward, recomputed and backward; the busiest device, the mean over the
traced steps. A program without such a layer gives nothing to read."""

import re

from benchmarks.layer_metrics.loop_stack_ms import scoped_ms

SPARSE_MIXER = re.compile(r"[(/]mixer\.SparseAttentionLayer[)/]")


def read(ctx):
    return scoped_ms(ctx, SPARSE_MIXER)
