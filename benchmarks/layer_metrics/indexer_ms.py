"""Sparse-attention mixer: of ``sparse_attention_ms``, the time under the
mixer's ``indexer`` scope: the indexer's three projections, its LayerNorm and
rotary turn, the choice of keys (``indexer_topk``: the scores of every causal
pair and each row's top-k, one Pallas kernel on the chip) and the indexer's loss
against the attention's weights, forward and backward (the choice is kept under
``remat``, so it runs once a step)."""

import re

from benchmarks.layer_metrics import sparse_attention_ms
from benchmarks.layer_metrics.loop_stack_ms import scoped_ms

INDEXER = re.compile(sparse_attention_ms.SPARSE_MIXER.pattern + r"(.*[(/])?indexer[)/]")


def read(ctx):
    if sparse_attention_ms.read(ctx) is None:
        return None
    return scoped_ms(ctx, INDEXER)
