"""Set-up: seconds under the ``compile`` span of ``train_step`` before the
window: XLA's compile where the persistent cache misses, the cache's read
where it hits. Source: the program's start-up record, JAX's own start and end
of the backend compile."""

from benchmarks.layer_metrics import _set_up


def read(ctx):
    spans = _set_up.spans()
    return None if spans is None else _set_up.seconds(_set_up.step_phases(spans, "compile"))
