"""The fault ``calibrate.py`` cannot plant in a cell of one row: half of the
row's positions left out.

    python3 benchmarks/calibrate_positions.py --workload <cell> --seeds 1,2 [--keep 0.5]

``calibrate.py``'s half a batch replaces every *row* by one of the leading
half of the rows; at batch 1 that is the batch itself and reads 0 on every
number. Here every *position* of a row is replaced by one of the leading
``--keep`` share of the row's positions (ids and labels alike), at the same
shapes, so the same compiled program: a step that sees only that share of its
tokens. For each seed the plain reference follows the cell's first three
batches whole and with the fault planted, and the two are put through the
comparison that decides ``correct`` (``reference_train.compare``) under the
cell's limits as committed: the fault has to come out not correct. No program
runs and no window is measured. Prints one JSON line a seed: every number the
fault reads, ``correct``, and the numbers over their limit.
"""

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def leading_positions_repeated(a, keep: float):
    """``a`` ``[rows, positions, ...]`` with every position replaced by one of
    the leading ``keep`` share of its row's, in order, repeated to the length."""
    import jax.numpy as jnp

    positions = a.shape[1]
    n = max(1, int(positions * keep))
    return jnp.concatenate([a[:, :n]] * -(-positions // n), axis=1)[:, :positions]


def judge(module, cfg: dict, limits: dict, key, batches, keep: float) -> dict:
    """The reference with the fault planted against the reference whole."""
    from benchmarks import reference_train

    takes = reference_train.takes_direction(limits)
    whole = reference_train.follow(module, cfg, key, batches, keep_gradient=takes)
    fault = reference_train.follow(
        module, cfg, key, [tuple(leading_positions_repeated(a, keep) for a in b) for b in batches],
        keep_gradient=takes)
    verdict = reference_train.compare(fault, whole, limits)
    return {"numbers": {**{k: c["value"] for k, c in verdict["checks"].items()},
                        **verdict["unlimited"]},
            "correct": verdict["correct"],
            "over": {k: [c["value"], c["limit"]] for k, c in verdict["checks"].items()
                     if not c["value"] <= c["limit"]}}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--keep", type=float, default=0.5)
    args = parser.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    if cell.chips != 1 or cell.config["inputs"]["features"]["kind"] != "tokens":
        raise SystemExit("a fault along the positions is for a one-chip cell of token rows")
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)      # as calibrate.py
    harness.configure_compile_cache(cell.name + ".calibrate")
    devices, _ = harness.find_chips(cell.chips)
    import importlib

    import jax

    from benchmarks import reference_train
    from benchmarks.traffic_gen import make_pool

    module = importlib.import_module(cell.config["reference"])
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = make_pool(cell.config["inputs"], cell.traffic, seed)
        batches = [tuple(jax.device_put(a, devices[0]) for a in b)
                   for b in pool[:reference_train.CHECK_STEPS]]
        out = judge(module, cell.config, cell.limits, jax.random.key(seed), batches, args.keep)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "fault": f"leading_{args.keep}_of_positions", **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
