"""Kernels: the least time the chip could take for the indexer's choice of keys
in a step, over the device time under the op registry's ``indexer_topk`` scope
(the Pallas kernel that scores every causal pair and takes each row's top-k,
and the operands' relayouts around it). The least time is the larger of the
configuration module's ``indexer_flops_per_sample`` x batch over chips x the
peak of the cell's compute type and its ``indexer_bytes_per_sample`` x batch
over chips x the memory's bytes a second (``peaks.json``): the scores'
products of every causal pair, and the operands read and the choice written
once; the top-k's counts on the vector unit are time and not work, so the
share errs low and cannot pass 100."""

import re

from benchmarks.layer_metrics.loop_stack_ms import scoped_ms

KERNEL = re.compile(r"[(/]indexer_topk[)/]")


def read(ctx):
    cell, module = ctx["cell"], ctx["module"]
    flops, moved = (getattr(module, name, None) for name in
                    ("indexer_flops_per_sample", "indexer_bytes_per_sample"))
    if flops is None or moved is None:
        return None
    kernel_ms = scoped_ms(ctx, KERNEL)
    if not kernel_ms:
        return None
    batch, chips, peaks = cell.traffic["batch"], ctx["chips"], ctx["peaks"]
    least_s = max(
        flops(cell.config, cell.traffic) * batch / (chips * peaks["flops_per_s"][cell.config["compute_dtype"]]),
        moved(cell.config, cell.traffic) * batch / (chips * peaks["hbm_bytes_per_s"]))
    return 100.0 * least_s / (1e-3 * kernel_ms)
