"""Set-up: how many programs built before the window missed the persistent
compile cache (``compile`` spans with ``cache`` = ``miss``), the step's and
every other. Warm, 0; a cell whose cache directory outgrows its cap compiles
again on every run. Source: the program's start-up record."""

from benchmarks.layer_metrics import _set_up


def read(ctx):
    spans = _set_up.spans()
    return None if spans is None else sum(
        1 for s in spans if s.name == "compile" and s.args.get("cache") == "miss")
