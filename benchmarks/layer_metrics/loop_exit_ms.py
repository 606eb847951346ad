"""Exits: the device time a step in operations under the ``exit`` scope (every
pass's head, gate and cross-entropy, forward, recomputed and backward) or the
``loss`` scope (what mixes the passes: the exit distribution, the expected
cross-entropy, the entropy term); the busiest device, the mean over the traced
steps. Read only where the step has an ``exit`` scope: every model's loss has
a ``loss`` scope, and that alone is not this layer."""

import re

from benchmarks.layer_metrics.loop_stack_ms import EXITS, scoped_ms

EXIT = re.compile(r"/exit/")


def read(ctx):
    return scoped_ms(ctx, EXITS) if scoped_ms(ctx, EXIT) else None
