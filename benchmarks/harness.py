"""What every cell's run shares: the manifest and the files it names, the
look for the chip, the table of peaks, the per-layer readers and the result
line. The windows themselves are in ``drivers/``.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(SystemExit):
    """Not the accelerator the cell asks for: exit non-zero, print no result."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: dict            # the configuration's file
    traffic_name: str
    traffic: dict           # the traffic mix's file
    limits: dict            # number compared -> limit (cells/<name>.json)
    end_to_end: list        # the manifest's entries this cell reports
    per_layer: list


def load_manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell_name: str, manifest: dict) -> bool:
    """Does the cell report this metric? With no ``workloads`` key a per-layer
    metric goes with every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in manifest["end_to_end"] if m["name"] == metric["moves"])
        return _reports(moved, cell_name, manifest)
    return True


def load_cell(name: str, manifest: dict | None = None) -> Cell:
    manifest = manifest or load_manifest()
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json: "
                         f"{[w['name'] for w in manifest['workloads']]}")
    conf = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    bench_dir = ROOT / manifest["paths"][0]
    return Cell(
        name=name, chips=entry["chips"], why=entry["why"],
        config_name=conf["name"], config=json.loads((ROOT / conf["file"]).read_text()),
        traffic_name=entry["traffic"],
        traffic=json.loads((bench_dir / "traffic" / f"{entry['traffic']}.json").read_text()),
        limits=json.loads((bench_dir / "cells" / f"{name}.json").read_text())["limits"],
        end_to_end=[m for m in manifest["end_to_end"] if _reports(m, name, manifest)],
        per_layer=[m for m in manifest["per_layer"] if _reports(m, name, manifest)])


def load_peaks(device_kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in peaks:
        raise NoChip(f"device kind {device_kind!r} is not in peaks.json "
                     f"({sorted(peaks)}): add it with its source, there is no default")
    return peaks[device_kind]


def find_chips(chips: int) -> tuple:
    """The devices the cell runs on and their peaks, or exit: no fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX reports {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX reports {len(devices)}")
    return devices[:chips], load_peaks(devices[0].device_kind)


def configure_compile_cache(cell_name: str) -> str:
    """The benchmark gives the directory, the program's one rule takes it.
    ``<checkout>/.jax_cache_bench/<cell>``: a fixed path inside the checkout
    whatever the machine's environment says, so that two checkouts share
    nothing; one directory to a cell, so that a size cap the machine sets on a
    cache directory (``JAX_COMPILATION_CACHE_MAX_SIZE``, 192 MiB on the
    machines with the chip; this cell's step and reference come to 112 MiB)
    is never shared between cells; and not ``.jax_cache``, which the program's
    tests trim. It goes in through ``JAX_COMPILATION_CACHE_DIR``, which JAX
    reads when it is imported: call this first. ``configure_compile_cache()``
    of the program then sets everything else (every program kept) and trims
    nothing."""
    if "jax" in sys.modules:
        raise RuntimeError("give the compile cache its directory before jax is imported")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache_bench" / cell_name)
    from deeplearning4j_tpu.monitoring.compile import configure_compile_cache as programs_rule

    return programs_rule()


def device_stamp(devices: list, memory_peak_bytes: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}


# ------------------------------------------------------------------ readers
def read_layer_metrics(cell: Cell, context: dict) -> dict:
    """Each per-layer metric of the cell through its own reader,
    ``layer_metrics/<name>.py``. A reader that finds nothing to read returns
    None and the metric is left out of the line."""
    out = {}
    for metric in cell.per_layer:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{metric['name']}")
        value = reader.read(context)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


# --------------------------------------------------------------- result line
def result_line(cell: Cell, run: dict, trace: bool) -> dict:
    """The one JSON object a run ends on. ``run`` is what the driver returns:
    ``end_to_end`` values by name, ``layer_context`` for the readers,
    ``device``, ``attempted``, ``failed`` and the ``verdict`` of the comparison."""
    if trace:
        metrics = read_layer_metrics(cell, run["layer_context"])
    else:
        metrics = {m["name"]: {"value": float(run["end_to_end"][m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end}
    verdict = run["verdict"]
    line = {"correct": bool(verdict["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": run["device"]}
    if trace and run.get("breakdown"):
        line["breakdown"] = run["breakdown"]
    line["workload"] = cell.name
    line["facts"] = run.get("facts", {})
    line["checks"] = {k: [c["value"], c["limit"]] for k, c in verdict["checks"].items()}
    return line


def print_result(line: dict, verdict: dict) -> None:
    for name, c in verdict["checks"].items():
        mark = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name}: {c['value']:.6g} limit {c['limit']:.6g} {mark}", file=sys.stderr)
    for name, v in verdict.get("unlimited", {}).items():
        print(f"check {name}: {v:.6g} (no limit, not compared)", file=sys.stderr)
    print(f"correct: {line['correct']}  worst leaves: {verdict.get('worst_leaves')}",
          file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
