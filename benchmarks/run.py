"""The benchmark's command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration, traffic mix, limits,
driver and per-layer readers by name, runs one window on the machine it is
started on, and ends on one JSON line. Off a TPU, or on a device that
``peaks.json`` does not list, it exits non-zero and prints no result.
"""

import time

CLOCK0 = time.perf_counter()        # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks import harness

    manifest = harness.load_manifest()
    cell = harness.load_cell(args.workload, manifest)
    harness.configure_compile_cache(cell.name)
    seconds = manifest["run_seconds"] if args.seconds is None else args.seconds
    devices, peaks = harness.find_chips(cell.chips)
    driver = importlib.import_module(f"benchmarks.drivers.{cell.traffic['driver']}")
    run = driver.run(cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
                     devices=devices, peaks=peaks, clock0=CLOCK0)
    line = harness.result_line(cell, run, bool(args.trace))
    harness.print_result(line, run["verdict"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
