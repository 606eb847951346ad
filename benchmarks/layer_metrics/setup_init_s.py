"""Set-up: seconds under ``setup.init``, a model's ``init()`` (its leaf-by-leaf
draws and the eager programs they build), before the window. Source: the
program's start-up record, on the host's clock."""

from benchmarks.layer_metrics import _set_up


def read(ctx):
    spans = _set_up.spans()
    return None if spans is None else _set_up.seconds(
        [s for s in spans if s.name == "setup.init"])
