"""Device: 1 - the union of the device's operation intervals over the trace's
window; the idlest device."""

from benchmarks import trace_reduce


def read(ctx):
    red = ctx["trace"]
    return 100.0 * max(1.0 - trace_reduce.busy_seconds(red, d) / red.window_s
                       for d in red.devices)
