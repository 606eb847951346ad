"""Host-side span tracer: the program's one span spine.

Complements profiler.trace() (the jax.profiler DEVICE timeline) with the
HOST timeline the reference never had: where a training step's wall time
goes between data wait, the jitted device step, and listener callbacks.
Spans are nestable context managers and thread-aware (each span records the
emitting thread's id), so serving worker threads and the fit loop interleave
correctly on separate tracks.

One clock: a span starts and ends at ``time.time_ns()``, the clock the
profiler stamps its trace with (the ``Task Environment`` plane's
``profile_start_time``), so a span lands on a device trace's timeline by one
subtraction. Each span also records the span that caused it — the
enclosing span on its thread, or an explicit ``parent=`` — and enters a
``jax.profiler.TraceAnnotation`` of the same name and arguments, so a
profiler trace taken with its host tracer on shows the same spans beside
the device's operations. ``spans()`` reads the ring out as plain tuples.
Span ids are unique in the process and the spans open on a thread are one
stack whatever tracer holds them, so a span in one tracer may name its
cause in another (the start-up record's ``compile`` under the ring's
``fit.dispatch``).

The saved file is the Chrome trace-event format — begin/end ("B"/"E") event
pairs, "X" complete events, and "M" metadata under ``{"traceEvents": [...]}``
— which Perfetto (https://ui.perfetto.dev) and chrome://tracing load
directly. Its timestamps are microseconds from tracer start.

The event buffer is a RING: past ``max_events`` (constructor arg, else
``DL4J_TPU_TRACE_MAX_EVENTS``, default 100k) the oldest events are dropped
and counted — in ``.dropped`` and, when monitoring is enabled, in
``dl4j_trace_events_dropped_total`` — so a long-running gateway with
tracing armed holds memory flat instead of leaking its whole history.
Metadata events (process_name, and a ``thread_name`` emitted automatically
the first time each thread records an event, so Perfetto tracks read as
``pi-mnist-0`` / ``dl4j-autoscaler`` instead of bare tids) live outside the
ring: names survive however many payload events are dropped.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from deeplearning4j_tpu.common.env import env

_ids = itertools.count(1)       # span ids, unique in the process
_open = threading.local()       # per thread: ids of the spans open on it, any tracer's


class Span(NamedTuple):
    """One recorded span, as ``SpanTracer.spans()`` reads it out. Times are
    ``time.time_ns()``; ``parent`` is the ``id`` of the span that caused it."""

    name: str
    start_ns: int
    end_ns: int
    tid: int
    thread: str
    id: int
    parent: Optional[int]
    args: Dict


def _json_safe(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return str(v)


class SpanTracer:
    """Collects nested, thread-aware spans as Chrome trace events.

    Usage::

        tracer = SpanTracer()
        with tracer.span("fit.iteration", step=3):
            with tracer.span("fit.device_step"):
                ...
        tracer.save("trace.json")   # open in Perfetto
    """

    def __init__(self, process_name: str = "deeplearning4j_tpu",
                 max_events: Optional[int] = None) -> None:
        self._lock = threading.Lock()
        self._cap = max(1, int(max_events if max_events is not None
                               else env.trace_max_events))
        self._events: Deque[Dict] = collections.deque()
        self.start_ns = time.time_ns()
        self._pid = os.getpid()
        self._named_tids: set = set()
        self._meta: List[Dict] = [{
            "name": "process_name", "ph": "M", "pid": self._pid, "tid": 0,
            "args": {"name": process_name}}]
        self.dropped = 0

    def _us(self, t_ns: int) -> float:
        return (t_ns - self.start_ns) * 1e-3

    @staticmethod
    def current() -> Optional[int]:
        """The id of the innermost span open on this thread, in any tracer."""
        stack = getattr(_open, "stack", None)
        return stack[-1] if stack else None

    @staticmethod
    def _stack() -> List[int]:
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        return stack

    def _append(self, ev: Dict) -> None:
        """Ring append: names the emitting thread on first sight, evicts
        (and counts) the oldest event at capacity."""
        tid = ev.get("tid")
        overflowed = False
        with self._lock:
            if tid and tid not in self._named_tids:
                self._named_tids.add(tid)
                self._meta.append({
                    "name": "thread_name", "ph": "M", "pid": self._pid,
                    "tid": tid,
                    "args": {"name": threading.current_thread().name}})
            if len(self._events) >= self._cap:
                self._events.popleft()
                self.dropped += 1
                overflowed = True
            self._events.append(ev)
        if overflowed:
            from deeplearning4j_tpu import monitoring

            if monitoring.enabled():
                monitoring.registry().counter(
                    "dl4j_trace_events_dropped_total",
                    "Span-tracer ring-buffer events dropped at capacity",
                ).inc()

    @contextlib.contextmanager
    def span(self, name: str, parent: Optional[int] = None, **args):
        """Time a section as a begin/end event pair on this thread, and as
        a ``TraceAnnotation`` in the profiler's own trace. ``parent`` names
        the span that caused this one where that is not the enclosing span
        on this thread."""
        tid = threading.get_ident()
        stack = self._stack()
        sid = next(_ids)
        if parent is None and stack:
            parent = stack[-1]
        args = {k: _json_safe(v) for k, v in args.items()}
        with TraceAnnotation(name, **args):
            t = time.time_ns()
            begin: Dict = {"name": name, "ph": "B", "ts": self._us(t),
                           "pid": self._pid, "tid": tid, "t_ns": t,
                           "sid": sid, "parent": parent}
            if args:
                begin["args"] = args
            self._append(begin)
            stack.append(sid)
            try:
                yield self
            finally:
                stack.pop()
                t = time.time_ns()
                self._append({"name": name, "ph": "E", "ts": self._us(t),
                              "pid": self._pid, "tid": tid, "t_ns": t,
                              "sid": sid})

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (thread-scoped)."""
        ev: Dict = {"name": name, "ph": "i", "s": "t",
                    "ts": self._us(time.time_ns()), "pid": self._pid,
                    "tid": threading.get_ident()}
        if args:
            ev["args"] = {k: _json_safe(v) for k, v in args.items()}
        self._append(ev)

    def complete(self, name: str, dur_s: float, **args) -> None:
        """Record an already-measured span (ended ~now, ``dur_s`` long) as
        an "X" complete event, caused by the span open on this thread — how
        request-trace spans (monitoring/context.py) mirror into the process
        timeline without holding the tracer lock for their whole duration."""
        dur_ns = int(max(0.0, float(dur_s)) * 1e9)
        t = max(self.start_ns, time.time_ns() - dur_ns)
        self.record(name, t, t + dur_ns, self.current(), **args)

    def record(self, name: str, start_ns: int, end_ns: int,
               parent: Optional[int] = None, sid: Optional[int] = None,
               **args) -> int:
        """Record a finished span from ``start_ns`` to ``end_ns`` as one "X"
        event, whole: a ring that drops it drops all of it. Returns its id."""
        sid = next(_ids) if sid is None else sid
        ev: Dict = {"name": name, "ph": "X", "ts": self._us(start_ns),
                    "dur": (end_ns - start_ns) * 1e-3, "pid": self._pid,
                    "tid": threading.get_ident(), "t_ns": start_ns,
                    "sid": sid, "parent": parent}
        if args:
            ev["args"] = {k: _json_safe(v) for k, v in args.items()}
        self._append(ev)
        return sid

    @contextlib.contextmanager
    def measured(self, name: str, **args):
        """Time a section as one "X" event recorded when it ends, open on
        this thread meanwhile (the spans it causes name it). Yields its
        ``args``, which the section may add to before it ends."""
        stack = self._stack()
        parent, sid, t = self.current(), next(_ids), time.time_ns()
        stack.append(sid)
        try:
            yield args
        finally:
            stack.pop()
            self.record(name, t, time.time_ns(), parent, sid, **args)

    def spans(self) -> List[Span]:
        """Every finished span still in the ring, by start: begin/end pairs
        and already-measured ("X") spans. A span whose begin the ring has
        dropped, or that is still open, is left out."""
        with self._lock:
            events = list(self._events)
            threads = {m["tid"]: m["args"]["name"] for m in self._meta
                       if m["name"] == "thread_name"}
        out, begun = [], {}
        for ev in events:
            ph = ev["ph"]
            if ph == "B":
                begun[ev["sid"]] = ev
            elif ph == "E":
                b = begun.pop(ev["sid"], None)
                if b is not None:
                    out.append(Span(b["name"], b["t_ns"], ev["t_ns"], b["tid"],
                                    threads.get(b["tid"], ""), b["sid"],
                                    b["parent"], b.get("args", {})))
            elif ph == "X":
                out.append(Span(ev["name"], ev["t_ns"],
                                ev["t_ns"] + round(ev["dur"] * 1e3), ev["tid"],
                                threads.get(ev["tid"], ""), ev["sid"],
                                ev["parent"], ev.get("args", {})))
        out.sort(key=lambda s: s.start_ns)
        return out

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._meta) + list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def to_dict(self) -> Dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        """Write the Perfetto/chrome://tracing-loadable JSON file."""
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_dict(), f)
        return str(path)


def validate_nesting(events: List[Dict]) -> None:
    """Raise ValueError unless every thread's B/E events form balanced,
    properly nested pairs (the invariant trace viewers rely on). Used by
    tests; cheap enough to run on any saved trace."""
    stacks: Dict[int, List[str]] = {}
    for ev in events:
        ph = ev.get("ph")
        if ph not in ("B", "E"):
            continue
        stack = stacks.setdefault(ev["tid"], [])
        if ph == "B":
            stack.append(ev["name"])
        else:
            if not stack or stack[-1] != ev["name"]:
                raise ValueError(
                    f"unbalanced trace: E {ev['name']!r} closes "
                    f"{stack[-1] if stack else None!r} on tid {ev['tid']}")
            stack.pop()
    leftover = {tid: s for tid, s in stacks.items() if s}
    if leftover:
        raise ValueError(f"unclosed spans at end of trace: {leftover}")
