"""Kernels: the least time the chip could take for the attention over the
chosen keys in a step, over the device time under the op registry's
``sparse_attention`` scope (the Pallas forward and backward calls and the
wrapper's sums: the tile table, ``delta``, a key-value head's dk and dv over
its group). The least time is the larger of the configuration module's
``sparse_attention_flops_per_sample`` x batch over chips x the peak of the
cell's compute type and its ``sparse_attention_bytes_per_sample`` x batch over
chips x the memory's bytes a second (``peaks.json``): 7 products a **chosen**
pair and query head, and the operands, their gradients and the choice moved
once. The kernels work whole tiles of the causal triangle, so the share errs
low and cannot pass 100."""

import re

from benchmarks.layer_metrics.loop_stack_ms import scoped_ms

KERNEL = re.compile(r"[(/]sparse_attention[)/]")


def read(ctx):
    cell, module = ctx["cell"], ctx["module"]
    flops, moved = (getattr(module, name, None) for name in
                    ("sparse_attention_flops_per_sample", "sparse_attention_bytes_per_sample"))
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
