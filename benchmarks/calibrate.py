"""The readings a cell's limits are set from, several seeds in one process:

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 [--program 0|1]
        [--control N] [--faults N] [--witness 0|1] [--dtype float32]

For each seed: the program's first three steps against the plain reference
(the lower reading); on the first N seeds the control — the reference in the
nearest precision below the stated one — against the reference, with
``--witness 1`` the reference in the stated precision itself (where a gap of
the program's comes from: rounding, if the witness shows it too), and each
fault a training cell can have planted in the reference put in the program's
place: half of the batch left out, and
on more chips one chip's share alone (the exchange left out). A state
returned unchanged reads 1 and needs no run. No window is measured. Each is
also judged by the cell's limits as committed (``<name>_correct``): the
program has to come out correct, the control and every fault not.
``--dtype float32`` runs the program in another type than the configuration
states: a witness for where a gap comes from, never a lower reading.
Prints one JSON line per seed with the leaves of the widest gaps, and writes
every reading, leaf by leaf, to ``chiprun_out/readings_<cell>_<seed>.json``
(where the cell keeps the first gradient, each leaf's turn in its place).
"""

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def gaps_by_leaf(got: list, want: list, names: list, top: int = 10) -> list:
    from benchmarks.reference_train import median_of_positive

    floor = median_of_positive(want)
    rows = [(abs(g - w) / max(w, floor, 1e-30), n, g, w) for g, w, n in zip(got, want, names)]
    return [[n, round(gap, 5), g, w] for gap, n, g, w in sorted(rows, reverse=True)[:top]]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--program", type=int, default=1)
    parser.add_argument("--control", type=int, default=3, help="on the first N seeds")
    parser.add_argument("--faults", type=int, default=3, help="on the first N seeds")
    parser.add_argument("--witness", type=int, default=0)
    parser.add_argument("--dtype", default=None)
    args = parser.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    # the control's program is as large again as the step and the reference:
    # under the machine's cap on a cache directory (192 MiB) the three evict
    # each other and every seed compiles (call A of PR 25: 100 s a seed)
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    harness.configure_compile_cache(cell.name + ".calibrate")
    from benchmarks import reference_train
    from benchmarks.drivers import fit

    devices, _ = harness.find_chips(cell.chips)
    if args.dtype:
        cell.config["builder_args"]["dtype"] = args.dtype
    import time

    import jax

    cache = {"hits": 0, "misses": 0}

    def count(event, **_):
        for kind in cache:
            if event.endswith("/cache_" + kind):
                cache[kind] += 1

    jax.monitoring.register_event_listener(count)
    clock = time.perf_counter()

    def lap(what, timings):
        nonlocal clock
        now = time.perf_counter()
        timings[what] = [round(now - clock, 1), dict(cache)]
        clock = now

    def judged(got, ref):
        """(every number, what the cell's limits as committed make of it:
        ``correct`` and the numbers over their limit)."""
        verdict = reference_train.compare(got, ref, cell.limits)
        numbers = {**{k: c["value"] for k, c in verdict["checks"].items()},
                   **verdict["unlimited"]}
        over = {k: [c["value"], c["limit"]] for k, c in verdict["checks"].items()
                if not c["value"] <= c["limit"]}
        return numbers, {"correct": verdict["correct"], "over": over}

    for nth, seed in enumerate(int(s) for s in args.seeds.split(",")):
        timings = {}
        prog = fit.Program(cell, seed, devices)
        out = {"workload": cell.name, "seed": seed, "dtype": args.dtype or "as configured"}
        readings = prog.first_steps() if args.program else None
        prog.free()
        lap("program", timings)
        ref = prog.reference()
        lap("reference", timings)
        names = ref["leaves"]
        if readings:
            out["program"], out["program_correct"] = judged(readings, ref)
            out["program_losses"], out["reference_losses"] = readings["losses"], ref["losses"]
            out["program_grad_leaves"] = gaps_by_leaf(readings["grad_norms"], ref["grad_norms"], names)
            out["program_change_leaves"] = gaps_by_leaf(readings["change_norms"],
                                                        ref["change_norms"], names)
        full = {"seed": seed, "leaves": names, "reference": ref, "program": readings}
        planted = {}
        stated = cell.config["compute_dtype"]
        if nth < args.control:
            planted["control_" + reference_train.CONTROL_PRECISION[stated]] = {
                "precision": reference_train.CONTROL_PRECISION[stated]}
        if args.witness:
            planted["witness_" + stated] = {"precision": stated}
        if nth < args.faults:
            planted["fault_half_batch"] = {"keep_fraction": 0.5}
            if cell.chips > 1:
                planted["fault_no_exchange"] = {"keep_fraction": 1.0 / cell.chips}
        for name, how in planted.items():
            other = prog.reference(**how)
            lap(name, timings)
            full[name] = other
            out[name], out[name + "_correct"] = judged(other, ref)
            if not name.startswith("fault"):
                out[name + "_grad_leaves"] = gaps_by_leaf(other["grad_norms"], ref["grad_norms"],
                                                          names, 5)
                out[name + "_change_leaves"] = gaps_by_leaf(other["change_norms"],
                                                            ref["change_norms"], names, 5)
        if "grad1" in ref:      # 436 MB a side in BERT-base: the leaves' turns are what is written
            for side in full.values():
                if isinstance(side, dict) and side is not ref and "grad1" in side:
                    turns, _ = reference_train.leaf_turns(side.pop("grad1"), ref["grad1"])
                    side["grad1_turns"] = [float(t) for t in turns]
            del ref["grad1"]
        out["seconds_and_cache"] = timings
        print(json.dumps(out), flush=True)
        dump = harness.ROOT / "chiprun_out" / f"readings_{cell.name}_{seed}.json"
        dump.parent.mkdir(exist_ok=True)
        dump.write_text(json.dumps(full))
    return 0


if __name__ == "__main__":
    sys.exit(main())
