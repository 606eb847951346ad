"""Fit loop / dispatch: how far ahead of the device the host runs — the
median over the traced steps of (start of the step's ``train_step`` execution
on the device - end of its ``fit.dispatch`` span). Near 0 the device starves.
Source: the program's span and the device trace on one clock, matched as
``program_trace.check_causality`` says."""

from benchmarks import program_trace


def read(ctx):
    pt = program_trace.on_shared_clock(ctx)
    return None if pt is None else program_trace.median_ms(pt.causality.lead_s)
