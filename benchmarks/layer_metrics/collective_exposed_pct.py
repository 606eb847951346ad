"""Collectives: of one traced step, the time inside collective operations
that no other operation of the same device covers, over ``step_device_ms``;
the device on which it is largest. A collective is an operation whose
``hlo_category`` (the event's metadata record) or, where the record has none,
whose opcode is ``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute`` or ``all-to-all``, alone, as the ``-start`` / ``-done``
half of its asynchronous form, or as a fusion of one
(``program_trace.COLLECTIVE``).

A device runs the operations of the "XLA Ops" line one after the other, so an
asynchronous collective's transfer is covered by the operations between its
``-start`` and its ``-done``, and the wait that is left shows only as the
``-done`` operation's duration: that duration *is* the exposed time. A trace
of one device, or one in which no device ran a collective, gives nothing to
read."""

from benchmarks import program_trace, trace_reduce
from benchmarks.layer_metrics import step_device_ms


def read(ctx):
    red, pt, step_ms = ctx["trace"], program_trace.load(ctx), step_device_ms.read(ctx)
    if pt is None or len(red.devices) < 2 or not step_ms:
        return None
    lo, hi = red.window
    worst = None
    for device in red.devices:
        steps = len(trace_reduce.steps_in_window(red, device))
        inside, others = [], []
        for op in pt.device_ops.get(device.id, []):
            (inside if op.is_collective else others).append((op.start, op.end))
        inside = trace_reduce.clip(trace_reduce.union(inside), lo, hi)
        if not steps or not inside:
            continue
        exposed = trace_reduce.total(trace_reduce.subtract(inside, others)) / steps
        worst = exposed if worst is None else max(worst, exposed)
    return None if worst is None else 100.0 * 1e3 * worst / step_ms
