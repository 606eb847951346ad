"""Kernels: the least time the chip could take for the attention kernel's
work in a step, over ``attention_kernel_ms``. The least time is the larger of
the configuration module's ``attention_flops_per_sample`` x batch over chips x
the peak of the cell's compute type and its ``attention_bytes_per_sample`` x
batch over chips x the memory's bytes a second (``peaks.json``); at the cell's
4,096 positions the operations bound it. The forward calls ``remat`` repeats
are time and not work, so the share errs low and cannot pass 100."""

from benchmarks.layer_metrics import attention_kernel_ms


def read(ctx):
    kernel_ms, cell, module = attention_kernel_ms.read(ctx), ctx["cell"], ctx["module"]
    flops, moved = (getattr(module, name, None) for name in
                    ("attention_flops_per_sample", "attention_bytes_per_sample"))
    if not kernel_ms or flops is None or moved is None:
        return None
    batch, chips, peaks = cell.traffic["batch"], ctx["chips"], ctx["peaks"]
    least_s = max(
        flops(cell.config, cell.traffic) * batch / (chips * peaks["flops_per_s"][cell.config["compute_dtype"]]),
        moved(cell.config, cell.traffic) * batch / (chips * peaks["hbm_bytes_per_s"]))
    return 100.0 * least_s / (1e-3 * kernel_ms)
