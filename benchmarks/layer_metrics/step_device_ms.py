"""Jitted step: the device's busy time inside one ``train_step`` execution,
the mean over the traced steps, from the trace; the slowest device."""

from benchmarks import trace_reduce


def read(ctx):
    red, worst = ctx["trace"], None
    for device in red.devices:
        runs = trace_reduce.steps_in_window(red, device)
        if not runs:
            continue
        spans = [(o.start, o.end) for o in device.ops]
        busy = sum(trace_reduce.total(trace_reduce.union(trace_reduce.clip(spans, r.start, r.end)))
                   for r in runs) / len(runs)
        worst = busy if worst is None else max(worst, busy)
    return None if not worst else 1e3 * worst
