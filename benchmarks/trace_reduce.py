"""From a profiler trace (``.xplane.pb``) to what the per-layer readers take:
per device the operations that ran (name, start, end), the executions of
each compiled program by name, the busy time and the idle gaps; and the
benchmark's own host spans on the same clock, where the host tracer is on.

Everything below ``read_xplane`` works on plain tuples, so the tests feed it
small hand-made event lists. Times are seconds on the trace's clock.

``python3 benchmarks/trace_reduce.py <file.xplane.pb>`` prints what a trace
holds (planes, lines, stat keys, the heaviest events): look at one by hand
before trusting a reader on a new runtime.
"""

from __future__ import annotations

import dataclasses
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, PROGRAMS_LINE = "XLA Ops", "XLA Modules"
HOST_SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    id: int
    ops: list           # [Op], by start
    programs: list      # [Op]: one per execution of a compiled program


@dataclasses.dataclass
class Reduced:
    devices: list       # [Device]
    host_spans: list    # [Op] named bench.*
    program: str        # the program the window is cut to
    window: tuple       # (start, end): first to last whole execution of it

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


# ------------------------------------------------------------ interval sums
def union(intervals) -> list:
    """Merge (start, end) pairs into disjoint ones, in order."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(i) for i in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The part of the disjoint intervals ``a`` that no interval of ``b`` covers."""
    out, b, first = [], union(b), 0
    for s, e in union(a):           # both in order: one pass over each
        cur = s
        while first < len(b) and b[first][1] <= cur:
            first += 1
        k = first
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals, lo: float, hi: float) -> list:
    """The idle stretches of [lo, hi] that no interval covers."""
    return subtract([(lo, hi)], clip(intervals, lo, hi))


# ------------------------------------------------------------- the reduction
def program_runs(device: Device, program: str) -> list:
    return [p for p in device.programs if program_name(p.name) == program]


def program_name(event_name: str) -> str:
    """``jit_train_step(1234)`` -> ``train_step``."""
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def cut_window(devices: list, program: str, skip_first: int = 0, skip_last: int = 0,
               stall_s: float = float("inf"), min_steps: int = 2) -> tuple:
    """The stretch of the trace that counts: the longest run of consecutive
    executions of ``program`` with no stall between them, less ``skip_first``
    at its head (the pipeline refills after a stall) and ``skip_last`` at its
    tail. A stall is a gap of more than ``stall_s`` between two executions:
    on this runtime the profiler halts the host for seconds when it is
    switched on and off (and, with the host tracer on, each time its buffer
    fills), while the device drains its queue; a run without the profiler has
    no such gap. What is left has to hold ``min_steps`` executions or more:
    a shorter stretch is an error, never a different cut, so that a metric
    means the same in every run. The stretch is chosen on the first device;
    the window runs from the earliest start to the latest end of the same
    executions over all devices, so that each holds them whole."""
    runs = program_runs(devices[0], program)
    stretches, current = [], []
    for r in runs:
        if current and r.start - current[-1].end > stall_s:
            stretches.append(current)
            current = []
        current.append(r)
    stretches.append(current)
    best = max(stretches, key=len)
    best = best[skip_first:len(best) - skip_last]
    if len(best) < max(2, min_steps):
        raise ValueError(
            f"{len(runs)} executions of {program!r} in the trace in stretches of "
            f"{[len(s) for s in stretches]} with no stall over {stall_s} s between them: the "
            f"longest, less {skip_first} at its head and {skip_last} at its tail, holds "
            f"{len(best)}, and the cell asks for {max(2, min_steps)}")
    first, last = best[0].start, best[-1].end
    lo, hi = first, last
    for d in devices[1:]:
        # the same executions on another device start and end a little apart
        # from the first's (each has its middle inside the first's stretch):
        # the window takes them whole on every device
        same = [r for r in program_runs(d, program) if first < 0.5 * (r.start + r.end) < last]
        if len(same) != len(best):
            raise ValueError(f"device {d.id}: {len(same)} executions of {program!r} in the "
                             f"stretch of {len(best)} on device {devices[0].id}")
        lo, hi = min(lo, same[0].start), max(hi, same[-1].end)
    return lo, hi


def reduce_events(devices: list, host_spans: list, program: str, skip_first: int = 0,
                  skip_last: int = 0, stall_s: float = float("inf"),
                  min_steps: int = 2) -> Reduced:
    for d in devices:
        d.ops.sort(key=lambda o: o.start)
        d.programs.sort(key=lambda o: o.start)
    return Reduced(devices, sorted(host_spans, key=lambda o: o.start), program,
                   cut_window(devices, program, skip_first, skip_last, stall_s, min_steps))


def busy_seconds(red: Reduced, device: Device) -> float:
    lo, hi = red.window
    return total(union(clip([(o.start, o.end) for o in device.ops], lo, hi)))


def idle_gaps(red: Reduced, device: Device) -> list:
    lo, hi = red.window
    return gaps([(o.start, o.end) for o in device.ops], lo, hi)


def steps_in_window(red: Reduced, device: Device) -> list:
    lo, hi = red.window
    return [p for p in program_runs(device, red.program) if p.start >= lo and p.end <= hi]


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time on the busiest device, and
    the longest idle gaps there, each named by the benchmark's host span that
    covers most of it, if one does."""
    device = max(red.devices, key=lambda d: busy_seconds(red, d))
    lo, hi = red.window
    by_name: dict = {}
    for o in device.ops:
        if o.end > lo and o.start < hi:
            by_name[o.name] = by_name.get(o.name, 0.0) + min(o.end, hi) - max(o.start, lo)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    named = []
    for s, e in sorted(idle_gaps(red, device), key=lambda g: g[0] - g[1])[:top]:
        best, cover = "fit loop (no benchmark span)", 0.5 * (e - s)
        for h in red.host_spans:        # a span names a gap it covers most of
            c = min(e, h.end) - max(s, h.start)
            if c > cover:
                best, cover = h.name, c
        named.append([best, e - s])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}


# ------------------------------------------------------------ reading a file
def read_xplane(path: str, program: str, skip_first: int = 0, skip_last: int = 0,
                stall_s: float = float("inf"), min_steps: int = 2) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, programs = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [_op(e) for e in line.events]
                elif line.name == PROGRAMS_LINE:
                    programs = [_op(e) for e in line.events]
            devices.append(Device(int(m.group(1)), ops, programs))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append(Op(e.name, e.start_ns * 1e-9,
                                       (e.start_ns + e.duration_ns) * 1e-9))
    if not devices:
        raise ValueError(f"no device plane in {path}: "
                         f"{[p.name for p in data.planes]}")
    return reduce_events(devices, host, program, skip_first, skip_last, stall_s, min_steps)


def _op(event) -> Op:
    start = event.start_ns * 1e-9
    return Op(short_name(event.name), start, start + event.duration_ns * 1e-9)


def short_name(event_name: str) -> str:
    """On this runtime an operation's event is named by its whole HLO
    instruction (``%fusion.7 = bf16[...] fusion(...), kind=...``): keep the
    instruction's name and its opcode, ``fusion.7 fusion``."""
    if not event_name.startswith("%") or " = " not in event_name:
        return event_name
    name, _, rest = event_name[1:].partition(" = ")
    opcode = re.search(r"\)?\s*([a-z][a-z0-9\-]*)\(", rest)
    return f"{name} {opcode.group(1)}" if opcode else name


def describe(path: str, top: int = 25) -> str:
    """What a trace holds, for a reader's eyes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            keys = sorted({k for e in events[:200] for k, _ in e.stats})
            out.append(f"  LINE {line.name!r}: {len(events)} events; stat keys {keys}")
            heavy: dict = {}
            for e in events:
                heavy[e.name] = heavy.get(e.name, 0.0) + e.duration_ns
            for name, ns in sorted(heavy.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"      {ns * 1e-6:10.3f} ms  {name[:110]}")
            for e in events[:2]:
                out.append(f"      first: {e.name[:60]} start={e.start_ns} dur={e.duration_ns} "
                           f"stats={[(k, str(v)[:80]) for k, v in e.stats][:12]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
