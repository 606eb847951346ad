"""Sparse experts: the largest held expert's (token, expert) pairs over the
mean held expert's, the worst layer of the traced run's last delivered step: 1
is an even router, ``held experts`` every pair on one. Source: the program's
gauge ``dl4j_train_moe_load_max_over_mean{layer=}`` (a program counter; set
from the expert layers' state when a step's score is delivered, with
monitoring on). A program without the gauge gives nothing to read."""


def read(ctx):
    from deeplearning4j_tpu import monitoring

    family = monitoring.registry().get("dl4j_train_moe_load_max_over_mean")
    loads = [] if family is None else [child.value for _, child in family.children()]
    return max(loads) if loads else None
