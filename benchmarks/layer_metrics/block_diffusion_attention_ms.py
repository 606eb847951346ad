"""Kernels: the device time a step under the block-diffusion mask's scope,
``block_diffusion`` (inside the op registry's ``flash_attention``): the Pallas
forward call, the fused backward call (``flash_attention_block_diffusion_*``)
and the wrapper's sums beside them (``delta``, a key-value head's dk and dv
over its group), every layer; the busiest device, the mean over the traced
steps. A program whose attention has no such mask gives nothing to read."""

import re

from benchmarks.layer_metrics.loop_stack_ms import scoped_ms

BLOCK_DIFFUSION = re.compile(r"[(/]block_diffusion[)/]")


def read(ctx):
    return scoped_ms(ctx, BLOCK_DIFFUSION)
