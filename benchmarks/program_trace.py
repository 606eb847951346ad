"""The program's own record of a traced run, put on the trace's clock: its
spans (``monitoring.spans()``, stamped with ``time.time_ns()``) and the scopes
its jitted step carries (``op_name`` in each operation's HLO metadata).

A trace holds a plane ``Task Environment`` whose stat ``profile_start_time``
is the Unix time in nanoseconds at which the profiler started, and every
event's ``start_ns`` counts from there: a span lands on the trace's clock by
one subtraction, with the host tracer off. Before a reader trusts that clock
it has to pass a check of causality over every traced step *k*:

- its ``fit.dispatch`` starts before its ``train_step`` execution starts on
  the device;
- its ``fit.drain``, the blocking fetch of its loss, returns after that
  execution ends, and soon after: within ``FETCH_MAX_S`` (or half the
  execution's length, if that is less) of the later of the execution's end
  and the drain's own start. A drain that waited returns when its execution
  ends; one that came late returns at once; neither returns a step later.

Dispatches are matched to executions by order, under the one shift of the
two lists for which all of this holds on every step of the stretch. The
upper limit on the drain is what makes the shift one: the async window keeps
the host up to two steps ahead of the device, so a later dispatch also
precedes the execution and its drain also follows it, a whole step late.
With no such shift, or more than one, the readers of the shared clock
return nothing. What the check bounds is in ``Causality``: spans cannot be
early by more than the smallest end-to-drain distance, nor late by more than
the limit less the largest; a clock off by less, or by a whole number of
steps, passes unseen.

The readers of scopes need each operation's ``op_name``. On this runtime
(jax 0.9.0, libtpu 0.0.34) the trace has it with ``enable_hlo_proto`` off, but
not where ``jax.profiler.ProfileData`` looks: an event is named by its whole
HLO instruction *less* the ``metadata={...}`` group, its own stats are
offsets and durations, and ``op_name`` sits in the stats of the event's
*metadata* record as ``tf_op`` (``jit(train_step)/jvp(conv1.ConvolutionLayer)/
conv_general_dilated:``), beside ``hlo_category``, ``flops`` and
``bytes_accessed``. ``ProfileData`` does not expose those, so
``metadata_op_names`` reads them from the file's own bytes (the protobuf wire
format of ``XSpace``; no module but the standard library). The instruction's
text and an event stat are tried first, for a runtime that puts it there.
Operations the compiler itself puts in to move data (``copy``, ``copy-start``
/ ``copy-done``, a slice's ``async-start`` / ``async-done``: prefetches
between memory spaces) come from no line of the program and carry no
``op_name`` by nature: they are counted as ``moves``. An exchange between
chips (``COLLECTIVE``) is counted as ``collective`` whatever scope it carries:
GSPMD gives an all-reduce the ``op_name`` of the sum it completes. Any other operation of
the ``train_step`` executions without an ``op_name`` is ``unnamed``, and those
may hold ``UNNAMED_MAX`` of the step's device time; past that the scope readers
return nothing: a stale compile cache or a lost scope leaves a metric out,
it does not print one.

Everything below ``load`` works on plain tuples; the tests feed it hand-made
events. A program that records no spans (``monitoring.spans`` is new with
the spine) gives the shared-clock readers nothing to read, and they return
``None`` without raising.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics
import struct

from benchmarks import trace_reduce

ANCHOR_PLANE, ANCHOR_STAT = "Task Environment", "profile_start_time"
UNNAMED_MAX = 0.02          # of the step's device time
FETCH_MAX_S = 0.010         # the longest the fetch of a ready scalar may take,
FETCH_MAX_SHARE = 0.5       # and of the execution's own length, whichever is less
OP_NAME = re.compile(r'op_name="([^"]*)"')
OP_NAME_STATS = ("op_name", "tf_op")
CONV_DOT = re.compile(r"/(conv_general_dilated|dot_general)$")
NORM_SCOPE = re.compile(r"[(/][^()/]*\.(Batch|Layer)Normalization[^()/]*[)/]")
LAYER_SCOPE = re.compile(r"jvp\([^()/]+\.[A-Za-z0-9_]+\)")
MOVE_OPCODES = ("copy", "copy-start", "copy-done")
MOVE_ASYNC = re.compile(r"^(dynamic-)?slice-(start|done)")     # async-start / async-done of a slice
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
                        r"(-start|-done)?( fusion)?$")
FIT_THREAD_SPAN, NO_SPAN = "fit.dispatch", "no program span"


@dataclasses.dataclass(frozen=True)
class NamedOp:
    name: str               # ``<instruction> <opcode>``, as ``trace_reduce.short_name`` gives it
    op_name: str | None     # None: the event carries none
    start: float            # seconds on the trace's clock
    end: float
    category: str | None = None     # ``hlo_category`` of the metadata record, if it has one

    @property
    def moves_data(self) -> bool:
        instruction, _, opcode = self.name.rpartition(" ")
        return opcode in MOVE_OPCODES or MOVE_ASYNC.match(instruction) is not None

    @property
    def is_collective(self) -> bool:
        """An exchange between chips, by ``hlo_category`` where the record has
        one, else by opcode."""
        return COLLECTIVE.match(self.category or self.name.rpartition(" ")[2]) is not None


@dataclasses.dataclass(frozen=True)
class HostSpan:
    """A program span on the trace's clock (seconds from ``profile_start_time``)."""
    name: str
    start: float
    end: float
    tid: int
    args: dict


@dataclasses.dataclass
class Causality:
    ok: bool
    why: str
    steps: int = 0
    lead_s: list = dataclasses.field(default_factory=list)      # execution start - dispatch end
    dispatch_to_start_s: float | None = None    # the smallest execution start - dispatch start
    end_to_drain_s: tuple | None = None         # the smallest and largest drain end - execution end


@dataclasses.dataclass
class ProgramTrace:
    anchor_ns: int | None
    spans: list | None      # [HostSpan], None: the program records none
    step_ops: list          # [NamedOp] inside the stretch's train_step executions, busiest device
    steps: int              # executions those operations belong to
    causality: Causality
    device_ops: dict = dataclasses.field(default_factory=dict)   # {device id: [NamedOp]}, the whole trace


# ------------------------------------------------------------------ op names
def op_name_of(event_name: str, stats=()) -> str | None:
    """``op_name`` from an event named by its whole HLO instruction
    (``... metadata={op_name="jit(train_step)/jvp(conv1.ConvolutionLayer)/conv_general_dilated" ...}``),
    else from a stat that carries it."""
    m = OP_NAME.search(event_name)
    if m:
        return m.group(1) or None
    for key, value in stats:
        if key in OP_NAME_STATS and value:
            return str(value)
    return None


# ----------------------------------------- the file's own bytes (XSpace, wire format)
def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint, a
    view of the bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an .xplane.pb?")
        yield key >> 3, value


def _map_value(entry):
    """The value of a ``map<int64, Message>`` entry (key = 1, value = 2)."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def metadata_stats(path: str, wanted: tuple) -> dict:
    """{event name: {stat name: value}} for the stats named in ``wanted``, from
    the events' *metadata* records (``XPlane.event_metadata[...].stats``) of
    every plane of the ``.xplane.pb``: ``tf_op`` (= ``<op_name>:<type>``),
    ``hlo_category``, ``flops``, ``bytes_accessed``. XSpace: planes = 1.
    XPlane: lines = 3 (skipped whole), event_metadata = 4, stat_metadata = 5.
    XEventMetadata: name = 2, stats = 5. XStat: metadata_id = 1, double_value
    = 2, uint64_value = 3, int64_value = 4, str_value = 5, ref_value = 7
    (the id of a stat's name). XStatMetadata: id = 1, name = 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        stat_names, events = {}, []
        for f, value in _fields(plane):
            if f == 5:
                meta = dict(_fields(_map_value(value)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode("utf-8", "replace")
            elif f == 4:
                events.append(_map_value(value))
        ids = {i for i, name in stat_names.items() if name in wanted}
        if not ids:
            continue
        for event in events:
            name, found = None, {}
            for f, value in _fields(event):
                if f == 2:
                    name = bytes(value).decode("utf-8", "replace")
                elif f == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) not in ids:
                        continue
                    if 7 in stat:
                        got = stat_names.get(stat[7], "")
                    elif 5 in stat:
                        got = bytes(stat[5]).decode("utf-8", "replace")
                    elif 2 in stat:
                        got = struct.unpack("<d", stat[2])[0]
                    else:
                        got = stat.get(3, stat.get(4, 0))
                    found[stat_names[stat[1]]] = got
            if name and found:
                out.setdefault(name, {}).update(found)
    return out


def metadata_op_names(path: str) -> dict:
    return op_names_of(metadata_stats(path, OP_NAME_STATS))


def op_names_of(records: dict) -> dict:
    """{event name: op_name} from ``metadata_stats``'s records: the stat
    ``tf_op`` (``op_name`` where a runtime calls it that), less its ``:<type>``."""
    out = {}
    for name, stats in records.items():
        text = stats.get("tf_op") or stats.get("op_name")
        if text:
            op_name = text.rpartition(":")[0] if ":" in text else text
            if op_name:
                out[name] = op_name
    return out


def self_seconds(ops: list) -> list:
    """(op, seconds) with an operation's time less that of the operations
    nested inside it, so that the sum is the union of all intervals."""
    out, stack = [], []                 # stack: [op, self seconds, covered until]

    def close(until):
        while stack and stack[-1][0].end <= until:
            op, own, cursor = stack.pop()
            own += max(0.0, op.end - cursor)
            out.append((op, own))
            if stack:
                stack[-1][2] = max(stack[-1][2], op.end)

    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        close(op.start)
        if stack:
            top = stack[-1]
            top[1] += max(0.0, min(op.start, top[0].end) - top[2])
            top[2] = max(top[2], op.start)
        stack.append([op, 0.0, op.start])
    close(float("inf"))
    return out


def scope_seconds(pt: ProgramTrace) -> dict | None:
    """Device seconds a step by kind of operation, or None where unnamed
    operations hold more than ``UNNAMED_MAX`` of the step: ``conv_dot``
    (``op_name`` ends in ``conv_general_dilated`` or ``dot_general``: forward,
    input gradient and weight gradient, with whatever XLA fused onto them),
    ``norm`` (under a ``*.BatchNormalization*`` / ``*.LayerNormalization*``
    scope, forward and backward), ``other``, ``moves`` (the compiler's own
    copies between memory spaces), ``collective`` (an exchange between chips,
    whatever scope it carries: GSPMD gives a gradient's all-reduce the
    convolution's ``op_name``), ``unnamed``; and ``scoped``: whether any
    operation carries a layer's scope at all."""
    if not pt.steps or not pt.step_ops:
        return None
    kinds = {"conv_dot": 0.0, "norm": 0.0, "other": 0.0, "moves": 0.0, "unnamed": 0.0,
             "collective": 0.0}
    scoped = False
    for op, seconds in self_seconds(pt.step_ops):
        name = op.op_name
        if op.is_collective:        # carries the scope of what it reduces: not that layer's time
            kinds["collective"] += seconds
            continue
        if name is None:
            kinds["moves" if op.moves_data else "unnamed"] += seconds
            continue
        scoped = scoped or LAYER_SCOPE.search(name) is not None
        if CONV_DOT.search(name):
            kinds["conv_dot"] += seconds
        elif NORM_SCOPE.search(name):
            kinds["norm"] += seconds
        else:
            kinds["other"] += seconds
    whole = sum(kinds.values())
    if whole <= 0 or kinds["unnamed"] > UNNAMED_MAX * whole:
        return None
    out = {k: v / pt.steps for k, v in kinds.items()}
    out["scoped"] = scoped
    return out


# ----------------------------------------------------------------- causality
def check_causality(executions: list, stretch: list, spans: list | None) -> Causality:
    """``executions``: every execution of the step in the trace, (start, end)
    in order; ``stretch``: the indices of those the cut keeps; ``spans``: the
    program's, on the trace's clock."""
    if not spans:
        return Causality(False, "the program recorded no spans")
    dispatches = sorted((s for s in spans if s.name == "fit.dispatch"), key=lambda s: s.start)
    drains = {s.args.get("step"): s for s in spans if s.name == "fit.drain"}
    if not dispatches or not stretch:
        return Causality(False, "no fit.dispatch span, or no traced step")
    holds = []
    for shift in range(-stretch[0], len(dispatches) - stretch[-1]):
        lead, to_start, to_drain = [], [], []
        for i in stretch:
            d, (start, end) = dispatches[i + shift], executions[i]
            drain = drains.get(d.args.get("step"))
            if drain is None or not (d.start < start and end < drain.end):
                break
            if drain.end - max(end, drain.start) > min(FETCH_MAX_S, FETCH_MAX_SHARE * (end - start)):
                break
            lead.append(start - d.end)
            to_start.append(start - d.start)
            to_drain.append(drain.end - end)
        else:
            holds.append((lead, min(to_start), (min(to_drain), max(to_drain))))
    if len(holds) != 1:
        return Causality(False, f"{len(holds)} ways to match {len(dispatches)} dispatches to the "
                                f"{len(stretch)} traced executions in causal order, not one")
    return Causality(True, "one match in causal order", len(stretch), *holds[0])


# ------------------------------------------------------- idle time and spans
def _innermost(spans: list, lo: float, hi: float):
    """The span covering most of [lo, hi]; of two alike, the one begun last."""
    over = [(min(hi, s.end) - max(lo, s.start), s.start, s) for s in spans
            if s.end > lo and s.start < hi]
    return max(over, key=lambda c: c[:2])[2] if over else None


def name_gaps(gaps: list, spans: list) -> list:
    """[(span name, seconds, seconds under a span)] for each idle gap: the
    innermost span of the fit-loop thread at that time, else of another of
    the program's threads (the prefetch thread)."""
    fit_tids = {s.tid for s in spans if s.name == FIT_THREAD_SPAN}
    fit = [s for s in spans if s.tid in fit_tids]
    rest = [s for s in spans if s.tid not in fit_tids]
    covering = trace_reduce.union((s.start, s.end) for s in spans)
    out = []
    for lo, hi in gaps:
        span = _innermost(fit, lo, hi) or _innermost(rest, lo, hi)
        under = trace_reduce.total(trace_reduce.clip(covering, lo, hi))
        out.append((span.name if span else NO_SPAN, hi - lo, under))
    return out


def named_gaps(ctx, top: int = 10) -> list | None:
    """The longest idle gaps of the busiest device in the stretch as
    ``[span name, seconds]``, the form ``breakdown.idle_gaps`` has."""
    pt, red = on_shared_clock(ctx), ctx["trace"]
    if pt is None:
        return None
    gaps = sorted(trace_reduce.idle_gaps(red, busiest(red)), key=lambda g: g[0] - g[1])[:top]
    return [[name, seconds] for name, seconds, _ in name_gaps(gaps, pt.spans)]


# ------------------------------------------------------------ reading a run
def busiest(red):
    """The device the breakdown and the device readers look at."""
    return max(red.devices, key=lambda d: trace_reduce.busy_seconds(red, d))


def shift_spans(spans, anchor_ns: int) -> list:
    """``monitoring.spans()`` tuples (name, start_ns, end_ns, tid, thread, id,
    parent, args) onto the trace's clock."""
    return [HostSpan(s[0], (s[1] - anchor_ns) * 1e-9, (s[2] - anchor_ns) * 1e-9, s[3], s[7])
            for s in spans]


def newest_xplane(trace_dir) -> str | None:
    files = sorted(glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb")),
                   key=os.path.getmtime)
    return files[-1] if files else None


def read_file(path: str) -> tuple:
    """(anchor in ns or None, {device id: [NamedOp]}) of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    anchor, devices = None, {}
    records = metadata_stats(path, OP_NAME_STATS + ("hlo_category",))
    from_metadata = op_names_of(records)
    for plane in ProfileData.from_file(path).planes:
        if plane.name == ANCHOR_PLANE:
            anchor = next((int(v) for k, v in plane.stats if k == ANCHOR_STAT), None)
            continue
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                devices[int(m.group(1))] = [
                    NamedOp(trace_reduce.short_name(e.name),
                            op_name_of(e.name, e.stats) or from_metadata.get(e.name),
                            e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                            records.get(e.name, {}).get("hlo_category"))
                    for e in line.events]
    return anchor, devices


def program_spans():
    """The program's recorded spans, or None where it has no spine."""
    from deeplearning4j_tpu import monitoring

    read = getattr(monitoring, "spans", None)
    return None if read is None else read()


def assemble(red, anchor_ns, device_ops: dict, spans) -> ProgramTrace:
    """``red``: the traced run as ``trace_reduce`` cut it; ``device_ops``: the
    same trace's operations with their ``op_name``; ``spans``: the program's."""
    device = busiest(red)
    runs = trace_reduce.steps_in_window(red, device)
    ops = sorted(device_ops.get(device.id, []), key=lambda o: o.start)
    step_ops, r = [], 0
    for op in ops:                      # both lists are in order of start
        while r < len(runs) and runs[r].end <= op.start:
            r += 1
        if r < len(runs) and op.start >= runs[r].start and op.end <= runs[r].end:
            step_ops.append(op)
    shifted = None if spans is None or anchor_ns is None else shift_spans(spans, anchor_ns)
    first = red.devices[0]
    every = trace_reduce.program_runs(first, red.program)
    lo, hi = red.window
    stretch = [i for i, p in enumerate(every) if p.start >= lo and p.end <= hi]
    causality = check_causality([(p.start, p.end) for p in every], stretch, shifted)
    return ProgramTrace(anchor_ns, shifted, step_ops, len(runs), causality, device_ops)


def load(ctx) -> ProgramTrace | None:
    """The traced run's program trace, read once a run and kept in the
    readers' context; None where the run left no trace file."""
    if "program_trace" not in ctx:
        from benchmarks.drivers.fit import TRACE_DIR

        path = newest_xplane(TRACE_DIR)
        ctx["program_trace"] = None if path is None else assemble(
            ctx["trace"], *read_file(path), program_spans())
    return ctx["program_trace"]


def on_shared_clock(ctx) -> ProgramTrace | None:
    """The program trace, if its spans passed the check of causality."""
    pt = load(ctx)
    return pt if pt is not None and pt.causality.ok else None


def spans_in_stretch(ctx, name: str) -> list:
    pt = on_shared_clock(ctx)
    if pt is None:
        return []
    lo, hi = ctx["trace"].window
    return [s for s in pt.spans if s.name == name and s.start >= lo and s.end <= hi]


def median_ms(seconds: list):
    return 1e3 * statistics.median(seconds) if seconds else None
