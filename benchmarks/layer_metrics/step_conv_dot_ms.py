"""Kernels: the device time a step in operations whose ``op_name`` ends in
``conv_general_dilated`` or ``dot_general`` — forward, input gradient and
weight gradient, with whatever XLA fused onto them; the busiest device, the
mean over the traced steps. Source: the device trace, named by the HLO
metadata the layers' scopes write."""

from benchmarks import program_trace


def read(ctx):
    pt = program_trace.load(ctx)
    kinds = None if pt is None else program_trace.scope_seconds(pt)
    return None if not kinds or not kinds["conv_dot"] else 1e3 * kinds["conv_dot"]
