"""Set-up as the program records it: the spans of its start-up record
(``monitoring.spans()``; kept with monitoring off) that end before the traced
run's first ``fit.*`` span. Monitoring is switched on at the window's start,
so the window and what follows it (the reference's compiles) are left out.
``setup.init`` is a model's ``init()``; ``compile.trace``, ``compile.lower``
and ``compile`` are the three phases of building a program, by its
``program`` name, ``compile`` with its ``cache`` outcome."""

from benchmarks import program_trace

STEP = "train_step"


def spans():
    """The set-up spans, or None where the program recorded no
    ``setup.init`` or no ``compile`` of the step before the window (a
    program without the start-up record)."""
    every = program_trace.program_spans() or []
    window = min((s.start_ns for s in every if s.name.startswith("fit.")), default=None)
    if window is None:
        return None
    out = [s for s in every if s.end_ns <= window]
    if not any(s.name == "setup.init" for s in out) or not step_phases(out, "compile"):
        return None
    return out


def step_phases(spans, *names) -> list:
    return [s for s in spans if s.name in names and s.args.get("program") == STEP]


def seconds(spans) -> float:
    return sum(s.end_ns - s.start_ns for s in spans) * 1e-9
