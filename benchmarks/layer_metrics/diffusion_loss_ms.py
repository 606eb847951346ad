"""Block-diffusion objective: the device time a step under the noising layer's
scope, ``block_diffusion_noise`` (the rates and masks drawn, the mask id
written, the table's rows of ``x_t || x_0`` gathered), and the output layer's,
``block_diffusion_loss`` (the head over the noised half, the cross-entropy
weighted 1/t), forward and backward; the busiest device, the mean over the
traced steps. A program without the two layers gives nothing to read."""

import re

from benchmarks.layer_metrics.loop_stack_ms import scoped_ms

OBJECTIVE = re.compile(r"[(/]block_diffusion_(noise|loss)[)/]")


def read(ctx):
    return scoped_ms(ctx, OBJECTIVE)
