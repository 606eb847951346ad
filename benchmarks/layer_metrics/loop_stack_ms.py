"""Looped stack: the device time a step in operations under a ``*.LoopedStack``
scope — every pass of the held layers, forward, the recomputation ``remat``
adds and backward, the attention kernel's calls included, and the container's
own ``while`` — and outside the exits' ``exit`` and ``loss`` scopes; the busiest
device, the mean over the traced steps. Source: the device trace, named by the
HLO metadata the layers' scopes write. A program without such a scope gives
nothing to read."""

import re

from benchmarks import program_trace

LOOPED_STACK = re.compile(r"[(/][^()/]*\.LoopedStack[)/]")
EXITS = re.compile(r"[(/]loss[)/]|/exit/")      # the exits' layer: ``loop_exit_ms``


def scoped_ms(ctx, inside, outside=None):
    """Device milliseconds a step in operations whose ``op_name`` matches
    ``inside`` and not ``outside`` (each operation's time less that of the
    operations nested in it), or None where the scope readers have nothing
    to read or no operation matches."""
    pt = program_trace.load(ctx)
    if pt is None or not program_trace.scope_seconds(pt):
        return None
    seconds = sum(s for op, s in program_trace.self_seconds(pt.step_ops)
                  if op.op_name and inside.search(op.op_name)
                  and not (outside and outside.search(op.op_name)))
    return 1e3 * seconds / pt.steps if seconds else None


def read(ctx):
    return scoped_ms(ctx, LOOPED_STACK, outside=EXITS)
