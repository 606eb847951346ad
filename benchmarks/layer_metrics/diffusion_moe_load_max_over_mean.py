"""Sparse experts: ``moe_load_max_over_mean`` in the block-diffusion decoder's
cell: the largest held expert's (token, expert) pairs over the mean held
expert's, the worst layer of the traced run's last delivered step. There the
expert the masked positions rank first takes every masked position's row beside
its share of the others, so the reading says how much of the step's expert
work that one expert carries. Source: the program's gauge
``dl4j_train_moe_load_max_over_mean{layer=}``."""

from benchmarks.layer_metrics import moe_load_max_over_mean


def read(ctx):
    return moe_load_max_over_mean.read(ctx)
