"""Kernels: the least time the chip could take for the expert layers' grouped
products in a step, over the device time under ``expert_matmul`` (the products
whatever implements them, and the gate's element-wise pass between them). The
least time is the larger of the configuration module's
``expert_flops_per_sample`` x batch over chips x the peak of the cell's compute
type and its ``expert_bytes_per_sample`` x batch over chips x the memory's bytes
a second (``peaks.json``). The counts take the expected held pairs under an
even router and no recomputation, the time holds what ``remat`` repeats: the
share errs low."""

from benchmarks.layer_metrics import moe_ms
from benchmarks.layer_metrics.loop_stack_ms import scoped_ms


def read(ctx):
    cell, module = ctx["cell"], ctx["module"]
    flops, moved = (getattr(module, name, None) for name in
                    ("expert_flops_per_sample", "expert_bytes_per_sample"))
    if flops is None or moved is None or moe_ms.read(ctx) is None:
        return None
    matmul_ms = scoped_ms(ctx, moe_ms.EXPERT_MATMUL)
    if not matmul_ms:
        return None
    batch, chips, peaks = cell.traffic["batch"], ctx["chips"], ctx["peaks"]
    least_s = max(
        flops(cell.config, cell.traffic) * batch / (chips * peaks["flops_per_s"][cell.config["compute_dtype"]]),
        moved(cell.config, cell.traffic) * batch / (chips * peaks["hbm_bytes_per_s"]))
    return 100.0 * least_s / (1e-3 * matmul_ms)
