"""The fit driver over four of the CPU's virtual devices, under
``ParallelWrapper``: the path a four-chip cell takes. A sound run is correct
against the reference at the global batch (so the batch sharding and the
gradient reduction are in the comparison), and each fault such a cell can
have — the exchange between chips left out among them — is not."""

import time

import pytest

from benchmark_tiny import PEAKS, devices_for, plant, tiny_cell
from benchmarks import harness
from benchmarks.drivers import fit

CHIPS = 4


def drive(patch=None):
    cell = tiny_cell("resnet50", chips=CHIPS)
    out = fit.run(cell, seed=2 ** 31 + 5, seconds=0.4, trace=False, devices=devices_for(cell),
                  peaks=PEAKS, clock0=time.perf_counter(), patch=patch)
    return out, harness.result_line(cell, out, trace=False)


def test_sound_run_over_four_devices_is_correct():
    out, line = drive()
    assert line["device"]["count"] == CHIPS
    assert line["correct"], line["checks"]
    assert line["metrics"]["train_samples_per_s_per_chip"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "no_exchange"])
def test_broken_timed_path_over_four_devices_is_not_correct(fault):
    _, line = drive(patch=plant(fault, CHIPS))
    assert not line["correct"], line["checks"]
