"""Kernels: the least time the chip could take for the block-mask attention in
a step, over ``block_diffusion_attention_ms``. The least time is the larger of
the configuration module's ``block_diffusion_attention_flops_per_sample`` x
batch over chips x the peak of the cell's compute type and its
``block_diffusion_attention_bytes_per_sample`` x batch over chips x the
memory's bytes a second (``peaks.json``): 4 x head_dim operations forward and
10 x backward for every **visible** pair and query head of every layer, and
the operands and their gradients moved once. The kernels work whole tiles
that hold a visible pair, so the share errs low and cannot pass 100."""

from benchmarks.layer_metrics import block_diffusion_attention_ms


def read(ctx):
    cell, module = ctx["cell"], ctx["module"]
    flops, moved = (getattr(module, name, None) for name in
                    ("block_diffusion_attention_flops_per_sample",
                     "block_diffusion_attention_bytes_per_sample"))
    if flops is None or moved is None:
        return None
    kernel_ms = block_diffusion_attention_ms.read(ctx)
    if not kernel_ms:
        return None
    batch, chips, peaks = cell.traffic["batch"], ctx["chips"], ctx["peaks"]
    least_s = max(
        flops(cell.config, cell.traffic) * batch / (chips * peaks["flops_per_s"][cell.config["compute_dtype"]]),
        moved(cell.config, cell.traffic) * batch / (chips * peaks["hbm_bytes_per_s"]))
    return 100.0 * least_s / (1e-3 * kernel_ms)
