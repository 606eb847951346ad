"""Device: of the idle time of the busiest device in the traced stretch, the
share that lies under a span of the program (any thread's). What the host was
doing in the ten longest gaps is ``program_trace.named_gaps``."""

from benchmarks import program_trace, trace_reduce


def read(ctx):
    pt, red = program_trace.on_shared_clock(ctx), ctx["trace"]
    if pt is None:
        return None
    named = program_trace.name_gaps(
        trace_reduce.idle_gaps(red, program_trace.busiest(red)), pt.spans)
    idle = sum(seconds for _, seconds, _ in named)
    return None if idle <= 0 else 100.0 * sum(under for _, _, under in named) / idle
