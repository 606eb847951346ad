"""Sparse-attention mixer: of the causal 512 x 512 tiles of (query, key) pairs,
the share that holds a key the indexer chose, in per cent, the worst layer
(the most) of the traced run's last delivered step: the tiles the attention
kernels work, where 100 is a dense causal attention's. Source: the program's
gauge ``dl4j_train_sparse_attn_tiles_visited{layer=}`` (a program counter; set
from each sparse-attention mixer's state when a step's score is delivered, with
monitoring on). A program without the gauge gives nothing to read."""


def read(ctx):
    from deeplearning4j_tpu import monitoring

    family = monitoring.registry().get("dl4j_train_sparse_attn_tiles_visited")
    shares = [] if family is None else [child.value for _, child in family.children()]
    return 100.0 * max(shares) if shares else None
