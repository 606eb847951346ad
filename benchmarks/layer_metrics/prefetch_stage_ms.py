"""Input pipeline: the median ``prefetch.stage`` span inside the traced
stretch — one host batch from the producer's hands to staged arrays ready on
the device (the producer waits for them while monitoring is on). The layer's
time busy a batch, beside ``data_wait_pct``, its time waited for: against
``step_device_ms`` it says how much faster the step may get before the input
thread sets the pace. Source: the program's span, on the trace's clock."""

from benchmarks import program_trace


def read(ctx):
    spans = program_trace.spans_in_stretch(ctx, "prefetch.stage")
    return program_trace.median_ms([s.end - s.start for s in spans])
