"""Set-up: seconds under ``compile.trace`` and ``compile.lower`` of
``train_step`` before the window: what every start pays, compile cache or not
(a Pallas kernel's body is traced and lowered at every start). Source: the
program's start-up record, JAX's own start and end of each phase."""

from benchmarks.layer_metrics import _set_up


def read(ctx):
    spans = _set_up.spans()
    return None if spans is None else _set_up.seconds(
        _set_up.step_phases(spans, "compile.trace", "compile.lower"))
