"""Kernels: the device time a step in operations under a
``*.BatchNormalization*`` / ``*.LayerNormalization*`` scope, forward and
backward (a convolution fused with one counts as a convolution). A program
without layer scopes gives nothing to read."""

from benchmarks import program_trace


def read(ctx):
    pt = program_trace.load(ctx)
    kinds = None if pt is None else program_trace.scope_seconds(pt)
    return None if not kinds or not kinds["scoped"] else 1e3 * kinds["norm"]
