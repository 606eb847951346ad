"""The four set-up readers on hand-made spans: what each reads, that the
window's first ``fit.*`` span ends set-up (the reference's compiles after the
window are left out), and nothing where the start-up record is missing."""

import importlib

import pytest

from benchmarks import harness, program_trace
from deeplearning4j_tpu.monitoring import Span

S = 1_000_000_000              # ns a second
T0 = 1_790_000_000 * S
NAMES = ("setup_init_s", "setup_step_lower_s", "setup_step_load_s", "setup_cache_misses")


def span(name, start_s, end_s, **args):
    return Span(name, T0 + int(start_s * S), T0 + int(end_s * S), 7, "MainThread", 0, None, args)


def compile_phases(program, at, trace, lower, load, cache):
    return [span("compile.trace", at, at + trace, program=program),
            span("compile.lower", at + trace, at + trace + lower, program=program),
            span("compile", at + trace + lower, at + trace + lower + load, program=program,
                 cache=cache)]


def run(window=True, init=True, step=True, reference=True):
    """A run's spans: init 4 s holding two eager programs (one missed), the
    step traced 3 s, lowered 2 s and read from the cache in 1.5 s, a program
    of the benchmark's own, then the window and the reference's compiles."""
    out = []
    if init:
        out += [span("setup.init", 1.0, 5.0, leaves=12, params=4096),
                *compile_phases("_normal", 1.5, 0.1, 0.2, 0.3, "miss"),
                *compile_phases("_uniform", 3.0, 0.1, 0.1, 0.1, "hit")]
    out += compile_phases("make_params", 6.0, 0.5, 0.5, 0.5, "hit")
    if step:
        out += compile_phases("train_step", 8.0, 3.0, 2.0, 1.5, "hit")
    if window:
        out += [span("fit.data_wait", 20.0, 20.001), span("fit.dispatch", 20.001, 20.004)]
        if reference:
            out += compile_phases("train_step", 50.0, 9.0, 9.0, 9.0, "miss")
    return sorted(out, key=lambda s: s.start_ns)


def read(monkeypatch, spans):
    monkeypatch.setattr(program_trace, "program_spans", lambda: spans)
    return {name: importlib.import_module(f"benchmarks.layer_metrics.{name}").read({})
            for name in NAMES}


def test_every_set_up_metric_is_entered_in_conftest_with_its_cases_here(scoped_metric_cases):
    assert set(scoped_metric_cases) == set(NAMES)
    entries = [m for m in harness.load_manifest()["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in entries] == list(NAMES)
    assert all(m["source"] == "program_span" and m["layer"] == "set-up" and m["moves"] == "setup_s"
               and "workloads" not in m and m["better"] == "lower" for m in entries)
    assert [m["unit"] for m in entries] == ["s", "s", "s", "programs"]


def test_each_reader_reads_its_spans_of_set_up(monkeypatch):
    got = read(monkeypatch, run())
    assert got["setup_init_s"] == pytest.approx(4.0)
    assert got["setup_step_lower_s"] == pytest.approx(5.0)
    assert got["setup_step_load_s"] == pytest.approx(1.5)
    assert got["setup_cache_misses"] == 1


def test_what_compiles_after_the_windows_first_fit_span_is_not_set_up(monkeypatch):
    """The reference's step after the window (9 s a phase, a miss) changes
    nothing; without it the readings are the same."""
    assert read(monkeypatch, run()) == read(monkeypatch, run(reference=False))
    late = run() + [span("setup.init", 30.0, 40.0)]
    assert read(monkeypatch, late)["setup_init_s"] == pytest.approx(4.0)


@pytest.mark.parametrize("missing", ["init", "step", "window", "record"])
def test_nothing_is_read_where_the_spans_are_absent(monkeypatch, missing):
    if missing == "record":
        spans = None                           # a program without spans()
    else:
        spans = run(**{missing: False})
    assert read(monkeypatch, spans) == dict.fromkeys(NAMES)


def test_a_program_whose_compiles_fall_in_the_window_only_gives_nothing(monkeypatch):
    """The parent's spine: ``compile`` spans recorded with monitoring on only,
    without ``program``, and no ``setup.init``."""
    spans = [span("fit.data_wait", 20.0, 20.001), span("compile", 20.002, 21.0)]
    assert read(monkeypatch, spans) == dict.fromkeys(NAMES)


def test_the_result_line_carries_the_four_where_the_record_holds_them(monkeypatch):
    import types

    monkeypatch.setattr(program_trace, "program_spans", lambda: run())
    cell = harness.load_cell(harness.load_manifest()["workloads"][0]["name"])
    assert set(NAMES) <= {m["name"] for m in cell.per_layer}
    ours = types.SimpleNamespace(per_layer=[m for m in cell.per_layer if m["name"] in NAMES])
    metrics = harness.read_layer_metrics(ours, {})
    assert set(metrics) == set(NAMES)
    assert metrics["setup_cache_misses"] == {"value": 1.0, "unit": "programs"}
    assert metrics["setup_step_load_s"] == {"value": pytest.approx(1.5), "unit": "s"}
