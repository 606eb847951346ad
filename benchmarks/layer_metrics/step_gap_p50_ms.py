"""Fit loop / dispatch: the median idle gap on the device between the end of
one ``train_step`` execution and the start of the next, from the trace; the
device on which it is largest."""

import statistics

from benchmarks import trace_reduce


def read(ctx):
    red, worst = ctx["trace"], None
    for device in red.devices:
        runs = trace_reduce.steps_in_window(red, device)
        gaps = [b.start - a.end for a, b in zip(runs, runs[1:])]
        if gaps:
            gap = statistics.median(gaps)
            worst = gap if worst is None else max(worst, gap)
    return None if worst is None else 1e3 * max(worst, 0.0)
