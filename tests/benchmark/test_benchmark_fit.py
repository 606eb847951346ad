"""The fit driver on one device at a tiny size on the CPU, for every
configuration under ``benchmarks/configs``: a sound run ends on
a line with the contract's keys and is correct (which is also the plain
reference against the zoo model in float32); with the timed path broken
underneath, ``correct`` comes out false for each fault the cell can have; and
the control — the reference in the precision below the stated one — is not
correct on three seeds."""

import time

import pytest

from benchmark_tiny import CONFIGS as CELLS, PEAKS, devices_for, plant, tiny_cell
from benchmarks import harness, reference_train
from benchmarks.drivers import fit



def drive(cell, seed=2 ** 31 + 77, patch=None):
    out = fit.run(cell, seed=seed, seconds=0.4, trace=False, devices=devices_for(cell),
                  peaks=PEAKS, clock0=time.perf_counter(), patch=patch)
    return out, harness.result_line(cell, out, trace=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_ends_on_the_contracts_line(name):
    cell = tiny_cell(name)
    out, line = drive(cell)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["attempted"] == out["facts"]["steps"] > 0 and line["failed"] == 0
    assert out["facts"]["compiled_in_window"] == 0
    assert set(line["checks"]) == set(cell.limits)
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault):
    cell = tiny_cell(name)
    _, line = drive(cell, patch=plant(fault, cell.chips))
    assert not line["correct"], line["checks"]
    assert any(value > limit for value, limit in line["checks"].values())


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_precision_below_is_not_correct(name, seed):
    cell = tiny_cell(name)
    prog = fit.Program(cell, seed, devices_for(cell))
    prog.free()
    stated = cell.config["compute_dtype"]
    control = prog.reference(precision=reference_train.CONTROL_PRECISION[stated])
    verdict = reference_train.compare(control, prog.reference(), cell.limits)
    assert not verdict["correct"], verdict["checks"]


def test_a_compile_inside_the_window_is_not_correct():
    cell = tiny_cell(CELLS[-1])

    def grows(model):
        real = model._make_train_step()
        calls = []

        def step(*args):
            calls.append(1)
            return real(*args)

        step._cache_size = lambda: 1 if len(calls) <= reference_train.CHECK_STEPS else 2
        model._jit_cache["train"] = step

    _, line = drive(cell, patch=grows)
    assert not line["correct"] and line["checks"]["compiled_in_window"] == [1, 0]
