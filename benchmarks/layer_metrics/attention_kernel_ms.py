"""Kernels: the device time a step under the op registry's ``flash_attention``
scope: the Pallas kernel's forward call, the forward calls ``remat`` repeats,
both backward calls (``bwd_dkv``, ``bwd_dq``) and the few sums and converts
the kernel's wrapper runs beside them; the busiest device, the mean over the
traced steps. Nothing to read where XLA's own attention ran (under the
predicate's 2,048 positions)."""

import re

from benchmarks.layer_metrics.loop_stack_ms import scoped_ms

FLASH_ATTENTION = re.compile(r"/flash_attention/")


def read(ctx):
    return scoped_ms(ctx, FLASH_ATTENTION)
