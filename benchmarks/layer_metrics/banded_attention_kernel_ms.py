"""Kernels: ``attention_kernel_ms`` in a cell whose layers mix a causal window
with full causal attention and share key-value heads between query heads: the
device time a step under the op registry's ``flash_attention`` scope (the
forward call, the fused backward call, and the wrapper's sums, the group's dk
and dv among them)."""

from benchmarks.layer_metrics import attention_kernel_ms


def read(ctx):
    return attention_kernel_ms.read(ctx)
