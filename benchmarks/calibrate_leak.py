"""The fault of block diffusion's mask that ``calibrate.py`` and
``calibrate_positions.py`` cannot plant: a noisy block that also sees the clean
block of its own index, the very tokens it is to predict.

    python3 benchmarks/calibrate_leak.py --workload <cell> --seeds 1,2

For each seed the plain reference follows the cell's first three batches with
its mask as written and with the leak planted (the configuration module's
``visible`` widened by the clean block of a noisy query's own index), and the
two are put through the comparison that decides ``correct``
(``reference_train.compare``) under the cell's limits as committed: the leak
has to come out not correct. No program runs and no window is measured.
Prints one JSON line a seed: every number the leak reads, ``correct``, and the
numbers over their limit.
"""

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def leaked(visible):
    """``visible`` with each noisy query also seeing the clean block of its own index."""
    def widened(i, j, seq, block):
        own = (i < seq) & (j >= seq) & ((j - seq) // block == i // block)
        return visible(i, j, seq, block) | own
    return widened


@contextlib.contextmanager
def leak_planted(module):
    """The configuration module with the leak planted in its steps; its
    weights, and the experts' placement read off a probe through the mask,
    stay the sound run's."""
    visible, make_params = module.visible, module.make_params

    def placed_as_written(k, cfg):
        module.visible = visible
        try:
            return make_params(k, cfg)
        finally:
            module.visible = leaked(visible)

    module.visible, module.make_params = leaked(visible), placed_as_written
    try:
        yield module
    finally:
        module.visible, module.make_params = visible, make_params


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    args = parser.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)      # as calibrate.py
    harness.configure_compile_cache(cell.name + ".calibrate")
    devices, _ = harness.find_chips(cell.chips)
    import importlib

    import jax

    from benchmarks import reference_train
    from benchmarks.traffic_gen import make_pool

    module = importlib.import_module(cell.config["reference"])
    if not hasattr(module, "visible"):
        raise SystemExit(f"{cell.config['reference']} has no block-diffusion mask to leak")
    takes = reference_train.takes_direction(cell.limits)
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = make_pool(cell.config["inputs"], cell.traffic, seed)
        batches = [tuple(jax.device_put(a, devices[0]) for a in b)
                   for b in pool[:reference_train.CHECK_STEPS]]
        key = jax.random.key(seed)
        whole = reference_train.follow(module, cell.config, key, batches, keep_gradient=takes)
        with leak_planted(module):
            fault = reference_train.follow(module, cell.config, key, batches, keep_gradient=takes)
        verdict = reference_train.compare(fault, whole, cell.limits)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "fault": "noisy_block_sees_its_clean_block",
            "numbers": {**{k: c["value"] for k, c in verdict["checks"].items()},
                        **verdict["unlimited"]},
            "correct": verdict["correct"],
            "over": {k: [c["value"], c["limit"]] for k, c in verdict["checks"].items()
                     if not c["value"] <= c["limit"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
