"""The manifest and the files it names: what the contract asks of
BENCHMARK.json, and that the harness finds every cell, configuration, traffic
mix, driver and reader by name."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark_tiny import ROOT
from benchmarks import harness

MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            yield entry["name"]
    for w in MANIFEST["workloads"]:
        yield w["traffic"]
    for c in MANIFEST["configs"]:
        yield from c["reduced"]


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in MANIFEST["end_to_end"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    cells = MANIFEST["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4) and all(w["chips"] in (1, 4) for w in cells)


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_every_name_is_made_of_the_contracts_characters(name):
    assert NAME.match(name)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    keys = {"name", "unit", "better", "source"}
    if metric in MANIFEST["end_to_end"]:
        keys |= {"bound"}
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        keys |= {"layer", "moves"}
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert metric["source"] in ("device_trace", "program_span", "program_counter",
                                    "host_clock")
        # found by name, and a reader
        reader = importlib.import_module(f"benchmarks.layer_metrics.{metric['name']}")
        assert callable(reader.read)
    assert set(metric) - {"workloads"} == keys
    assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    for cell in metric.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_and_the_cell_reports_what_it_must(name):
    cell = harness.load_cell(name)
    assert 1 <= len(cell.why) <= 200 and "\n" not in cell.why
    assert cell.limits, "a cell with no limit can never be correct"
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2 and cell.per_layer
    for metric in cell.per_layer:       # what a per-layer metric moves, its cells report
        assert metric["moves"] in reported
    importlib.import_module(f"benchmarks.drivers.{cell.traffic['driver']}")
    module = importlib.import_module(cell.config["reference"])
    for needed in ("make_params", "loss_fn", "train_flops_per_sample"):
        assert callable(getattr(module, needed))


@pytest.mark.parametrize("conf", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_file(conf):
    path = ROOT / conf["file"]
    assert any(path.is_relative_to(ROOT / p) for p in MANIFEST["paths"])
    body = json.loads(path.read_text())
    assert body["source"] == conf["source"] and body["reduced"] == conf["reduced"]
    assert any(w["config"] == conf["name"] for w in MANIFEST["workloads"])
    widths = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head|width|expansion")
    assert not [k for k in conf["reduced"] if widths.search(k)], "a width may never be reduced"


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert harness.load_peaks("TPU v5 lite")["flops_per_s"]["bfloat16"] == 197e12
    with pytest.raises(SystemExit):
        harness.load_peaks("TPU v9 imaginary")


def test_no_benchmark_file_imports_the_smoke_or_the_old_bench():
    for path in (ROOT / "benchmarks").rglob("*.py"):
        text = path.read_text()
        assert "import chip_smoke" not in text and "import bench\n" not in text


def test_run_py_refuses_the_cpu_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", CELLS[0],
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert not done.stdout.strip()
